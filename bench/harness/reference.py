"""Plain NumPy references of the benchmark: the same semantics as the
program's analytics and partition, written without any of its code.

PageRank: damping ``d``; a page with no out-links spreads its rank over
every page; repeated edges count with their multiplicity.  WCC: each
vertex takes the least id of its weakly connected component.  Label
propagation: the ``num_seeds`` lowest ids hold their own id; every other
vertex takes the least label over its in-neighbours, repeated.  RF:
Σ_p |vertices touched by partition p| / |V|.  Balance: k · max load / |E|.
Master of a vertex: the partition holding most of its edge endpoints,
ties to the lowest partition id.

``precision="bf16"`` computes PageRank with every stored value rounded to
bfloat16 (sums accumulate in float32), the control that a comparison has
to reject.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import connected_components

LABEL_NONE = int(np.iinfo(np.int32).max)   # a label no seed has reached


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even) and
    return them as float32."""
    bits = np.asarray(x, np.float32).view(np.uint32)
    bias = ((bits >> 16) & 1) + np.uint32(0x7FFF)
    return ((bits + bias) & np.uint32(0xFFFF0000)).view(np.float32)


class PageRank:
    """The PageRank operator of one edge list, applied from any start."""

    def __init__(self, src, dst, num_vertices: int, damping: float,
                 precision: str = "f64"):
        if precision not in ("f64", "bf16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.n = int(num_vertices)
        self.src = np.asarray(src, np.int64)
        self.dst = np.asarray(dst, np.int64)
        self.d = float(damping)
        self.precision = precision
        outdeg = np.bincount(self.src, minlength=self.n)
        self.dangling = outdeg == 0
        self.inv_deg = np.where(self.dangling, 0.0,
                                1.0 / np.maximum(outdeg, 1))

    def cold(self) -> np.ndarray:
        return np.full(self.n, 1.0 / self.n)

    def step(self, rank: np.ndarray) -> np.ndarray:
        if self.precision == "bf16":
            rank = _bf16(rank)
            contrib = _bf16((rank * self.inv_deg).astype(np.float32))
            s = np.bincount(self.dst, weights=contrib[self.src],
                            minlength=self.n).astype(np.float32)
            dangle = np.float32(rank[self.dangling].sum(dtype=np.float32))
            new = ((1.0 - self.d) / self.n
                   + self.d * (_bf16(s) + dangle / self.n))
            return _bf16(new.astype(np.float32)).astype(np.float64)
        contrib = rank * self.inv_deg
        s = np.bincount(self.dst, weights=contrib[self.src],
                        minlength=self.n)
        dangle = rank[self.dangling].sum()
        return (1.0 - self.d) / self.n + self.d * (s + dangle / self.n)

    def run(self, iters: int, start: np.ndarray | None = None) -> np.ndarray:
        rank = self.cold() if start is None else np.asarray(start, float)
        for _ in range(int(iters)):
            rank = self.step(rank)
        return rank


def pagerank(src, dst, num_vertices: int, iters: int, damping: float,
             precision: str = "f64") -> np.ndarray:
    return PageRank(src, dst, num_vertices, damping, precision).run(iters)


def wcc(src, dst, num_vertices: int) -> np.ndarray:
    """Least vertex id of each vertex's weakly connected component."""
    n = int(num_vertices)
    a = sp.coo_matrix((np.ones(len(src), np.int8), (src, dst)),
                      shape=(n, n))
    _, comp = connected_components(a, directed=False)
    least = np.full(comp.max() + 1, n, np.int64)
    np.minimum.at(least, comp, np.arange(n))
    return least[comp]


class MinLabel:
    """Least-label propagation over one edge list: each round a vertex
    takes the least label among itself and its in-neighbours (and its
    out-neighbours when ``undirected``); the ``num_seeds`` lowest ids keep
    their own id."""

    def __init__(self, src, dst, num_vertices: int, *, undirected: bool,
                 num_seeds: int = 0):
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        if undirected:
            src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        order = np.argsort(dst, kind="stable")
        self.src = src[order]
        self.targets, self.starts = np.unique(dst[order], return_index=True)
        self.num_seeds = int(num_seeds)

    def step(self, label: np.ndarray) -> np.ndarray:
        new = label.copy()
        if self.src.size:
            inflow = np.minimum.reduceat(label[self.src], self.starts)
            new[self.targets] = np.minimum(new[self.targets], inflow)
        new[:self.num_seeds] = np.arange(self.num_seeds)
        return new

    def run(self, label: np.ndarray, iters: int) -> np.ndarray:
        for _ in range(int(iters)):
            label = self.step(label)
        return label


def labelprop_seeds(num_vertices: int) -> int:
    return max(2, int(num_vertices) // 256)


def label_op(program: str, src, dst, num_vertices: int) -> MinLabel:
    """``cc``: undirected, no seeds; ``labelprop``: directed, seeded."""
    if program == "cc":
        return MinLabel(src, dst, num_vertices, undirected=True)
    return MinLabel(src, dst, num_vertices, undirected=False,
                    num_seeds=labelprop_seeds(num_vertices))


def labels_cold(num_vertices: int, program: str) -> np.ndarray:
    """Start labels: every id its own (cc), or the seeds' own ids and
    ``LABEL_NONE`` elsewhere (labelprop)."""
    n = int(num_vertices)
    if program == "cc":
        return np.arange(n, dtype=np.int64)
    lab = np.full(n, LABEL_NONE, np.int64)
    ns = labelprop_seeds(n)
    lab[:ns] = np.arange(ns)
    return lab


def degree(src, dst, num_vertices: int) -> np.ndarray:
    n = int(num_vertices)
    return (np.bincount(np.asarray(src, np.int64), minlength=n)
            + np.bincount(np.asarray(dst, np.int64), minlength=n))


def neighbors(src, dst, v: int) -> np.ndarray:
    src = np.asarray(src)
    dst = np.asarray(dst)
    return np.unique(np.concatenate([dst[src == v], src[dst == v]]))


def replicas(src, dst, assign, num_vertices: int, k: int) -> tuple:
    """Distinct (partition, vertex) pairs with their endpoint counts."""
    n = int(num_vertices)
    a = np.asarray(assign, np.int64)
    key = np.concatenate([a * n + np.asarray(src, np.int64),
                          a * n + np.asarray(dst, np.int64)])
    uniq, cnt = np.unique(key, return_counts=True)
    return uniq // n, uniq % n, cnt


def replication_factor(src, dst, assign, num_vertices: int, k: int) -> float:
    part, _, _ = replicas(src, dst, assign, num_vertices, k)
    return part.shape[0] / float(num_vertices)


def balance(assign, k: int) -> float:
    loads = np.bincount(np.asarray(assign, np.int64), minlength=k)
    return float(k * loads.max() / max(1, len(assign)))


def masters(src, dst, assign, num_vertices: int, k: int) -> np.ndarray:
    """Master partition per vertex (−1 for a vertex with no edge)."""
    part, vert, cnt = replicas(src, dst, assign, num_vertices, k)
    order = np.lexsort((part, -cnt, vert))
    vert, part = vert[order], part[order]
    first = np.ones(vert.shape[0], bool)
    first[1:] = vert[1:] != vert[:-1]
    out = np.full(int(num_vertices), -1, np.int64)
    out[vert[first]] = part[first]
    return out


def layout_faults(layout, src, dst, assign, num_vertices: int,
                  k: int) -> int:
    """Count how far a vertex-cut layout departs from its assignment.

    Each partition's table must hold exactly the edges assigned to it
    (as global (src, dst) pairs, repeats counted), list each vertex it
    touches once, mark as master exactly the master partition's copy,
    and carry every mirror once in its halo tables.  Returns the number
    of departures (0 for a sound layout)."""
    n = int(num_vertices)
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    assign = np.asarray(assign, np.int64)
    faults = 0
    gid = np.asarray(layout.vert_gid, np.int64)
    vmask = np.asarray(layout.vert_mask)
    is_master = np.asarray(layout.is_master)
    emask = np.asarray(layout.edge_mask)
    master_of = masters(src, dst, assign, n, k)
    part, vert, _ = replicas(src, dst, assign, n, k)
    for p in range(k):
        want = np.sort(src[assign == p] * n + dst[assign == p])
        es = np.asarray(layout.edge_src[p])[emask[p]]
        ed = np.asarray(layout.edge_dst[p])[emask[p]]
        if es.size and (es.max() >= gid.shape[1] or ed.max() >= gid.shape[1]):
            faults += 1
            continue
        got = np.sort(gid[p][es] * n + gid[p][ed])
        if got.shape != want.shape:
            faults += 1 + abs(got.shape[0] - want.shape[0])
        else:
            faults += int((got != want).sum())
        verts = gid[p][vmask[p]]
        want_v = vert[part == p]
        if verts.shape != want_v.shape or not np.array_equal(
                np.sort(verts), want_v):
            faults += 1
        else:
            faults += int((is_master[p][vmask[p]]
                           != (master_of[verts] == p)).sum())
    mirrors = part.shape[0] - int((master_of >= 0).sum())
    if int(np.asarray(layout.halo_cnt).sum()) != mirrors:
        faults += 1
    return faults
