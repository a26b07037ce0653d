"""Graph generators of the benchmark, kept apart from the program's own.

``crawl`` follows the crawl generator of the program's ``core.graphgen``
(``community_web``): pages carved into sites of power-law size, a Zipf
out-degree per page, intra-site links uniform within the site and a share
``beta`` of cross-site links drawn toward hub pages, the stream in crawl
order (all out-links of a page when it is fetched).  Two things differ,
so that every seed yields the same shapes: ids are not compacted (the
vertex count is the configuration's, not what the draw happened to
touch), and the draw is thinned to the configuration's edge count by
dropping a uniform sample of its surplus edges, never a page's first
out-link, so that no page is left without an edge (the program's layout
holds only vertices that some edge touches).

``arrivals`` draws live edges by the same law as the configuration's
generator, for traffic that ingests while it queries.

The graph is drawn once, from the configuration's own ``seed``; a run's
``--seed`` relabels its page ids by a random permutation (``relabel``),
the edge stream keeping its order.  The partitioner, the layout and
PageRank make no decision on an id's value.  WCC does: each vertex takes
the least id of its component, and the rounds that takes are the
distance from that least vertex to the farthest one.  So the permutation
keeps each component's least page its least, and every seed gives the
same set of sizes, rounds and arrivals, in another naming.  Different
graphs would not: the game stops after 8 to 12 rounds on some draws and
runs its 64 on others, and a job then takes 3.6 s or 6.4 s.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import connected_components


def _dedupe(src: np.ndarray, dst: np.ndarray, n: int):
    """Drop self loops and repeated (src, dst) pairs, keeping the stream
    order of each pair's first occurrence."""
    keep = src != dst
    src, dst = src[keep], dst[keep]
    _, idx = np.unique(src * n + dst, return_index=True)
    idx.sort()
    return src[idx], dst[idx]


def _sites(n: int, avg_site: int, rng) -> tuple:
    """Carve [0, n) into contiguous sites of power-law size."""
    sizes = []
    total = 0
    while total < n:
        s = min(int(rng.pareto(1.6) * avg_site / 2.0) + 4, n - total,
                40 * avg_site)
        sizes.append(s)
        total += s
    sizes = np.asarray(sizes)
    site_of = np.repeat(np.arange(sizes.shape[0]), sizes)[:n]
    starts = np.concatenate([[0], np.cumsum(sizes)])[:-1]
    return starts[site_of], sizes[site_of]


def crawl(num_vertices: int, num_edges: int, *, edge_factor: int,
          avg_site: int, beta: float, alpha: float, hub_zipf: float,
          seed: int) -> tuple:
    """(src, dst) int32 of exactly ``num_edges`` edges over
    ``num_vertices`` page ids, in crawl order.  Raises if the draw holds
    fewer edges than asked: ``edge_factor`` must leave a surplus."""
    n = int(num_vertices)
    rng = np.random.default_rng(seed)
    site_start, site_size = _sites(n, avg_site, rng)
    out_deg = np.minimum(rng.zipf(alpha, size=n) + edge_factor // 2,
                         10 * edge_factor)
    src = np.repeat(np.arange(n, dtype=np.int64), out_deg)
    m = src.shape[0]
    cross = rng.random(m) < beta
    dst = site_start[src] + rng.integers(0, np.maximum(site_size[src], 1))
    dst[cross] = rng.zipf(hub_zipf, size=int(cross.sum())) % n
    src, dst = _dedupe(src, dst, n)
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    surplus = src.shape[0] - int(num_edges)
    if surplus < 0:
        raise ValueError(f"crawl draw holds {src.shape[0]} edges, fewer "
                         f"than the {num_edges} asked; raise edge_factor")
    first = np.ones(src.shape[0], bool)
    first[1:] = src[1:] != src[:-1]
    drop = rng.choice(np.flatnonzero(~first), surplus, replace=False)
    keep = np.ones(src.shape[0], bool)
    keep[drop] = False
    return src[keep].astype(np.int32), dst[keep].astype(np.int32)


GENERATORS = {"crawl": crawl}


def relabel(src, dst, num_vertices: int, seed: int) -> np.ndarray:
    """The run's permutation of the page ids of the draw ``src, dst``: a
    random one, in which each weakly connected component's least page
    then swaps ids with the page that drew the component's least id."""
    n = int(num_vertices)
    pi = np.random.default_rng([seed, 4]).permutation(n)
    adj = sp.coo_matrix((np.ones(len(src), np.int8), (src, dst)),
                        shape=(n, n))
    count, comp = connected_components(adj, directed=False)
    by_new = np.argsort(pi)
    _, first = np.unique(comp[by_new], return_index=True)
    holder = by_new[first]                  # drew its component's least id
    least = np.full(count, n, np.int64)
    np.minimum.at(least, comp, np.arange(n))
    pi[least], pi[holder] = pi[holder], pi[least]
    return pi


def draw(graph: dict) -> tuple:
    """(src, dst) of the configuration's ``graph`` section, as drawn from
    its own ``seed``, before any run's relabelling."""
    params = {key: val for key, val in graph.items()
              if key not in ("generator", "num_vertices", "num_edges",
                             "seed")}
    try:
        gen = GENERATORS[graph["generator"]]
    except KeyError:
        raise ValueError(f"unknown generator {graph.get('generator')!r}; "
                         f"expected one of {sorted(GENERATORS)}") from None
    return gen(graph["num_vertices"], graph["num_edges"],
               seed=graph["seed"], **params)


def generate(graph: dict, seed: int) -> tuple:
    """(src, dst) of the configuration's ``graph`` section, its ids
    relabelled for the run's ``seed``."""
    src, dst = draw(graph)
    pi = relabel(src, dst, graph["num_vertices"], seed).astype(np.int32)
    return pi[src], pi[dst]


def arrivals(graph: dict, count: int, seed: int) -> tuple:
    """``count`` live edges over the configuration's vertex ids, drawn by
    the crawl law: a uniform source page, a target inside its site with
    probability 1 − beta, else a hub drawn by ``hub_zipf``.  Self loops
    are redrawn as the next page.  Site boundaries are drawn from the
    graph's seed stream, so intra-site arrivals stay within the graph's
    own sites; like the graph, the edges are the same for every run and
    relabelled for its ``seed``."""
    if graph["generator"] != "crawl":
        raise ValueError("live arrivals follow the crawl law only")
    n = int(graph["num_vertices"])
    site_start, site_size = _sites(n, graph["avg_site"],
                                   np.random.default_rng(graph["seed"]))
    rng = np.random.default_rng([graph["seed"], 1])
    src = rng.integers(0, n, count)
    dst = site_start[src] + rng.integers(0, np.maximum(site_size[src], 1))
    cross = rng.random(count) < graph["beta"]
    dst[cross] = rng.zipf(graph["hub_zipf"], size=int(cross.sum())) % n
    loop = src == dst
    dst[loop] = (dst[loop] + 1) % n
    pi = relabel(*draw(graph), n, seed).astype(np.int32)
    return pi[src], pi[dst]
