"""Vertex-cut partition layout: from an edge→partition assignment to the
static padded per-device tables the GAS engine runs on.

PowerGraph semantics (paper §II-B): each vertex that appears in several
partitions has one **master** replica (here: the partition holding most of
its edges, ties → lowest id) and mirrors elsewhere.  Per GAS iteration the
mirrors' partial aggregates flow to the master (gather), the master applies
the update, and the new value flows back (scatter).  Communication per
iteration is therefore proportional to the number of mirrors, i.e. to
(RF − 1)·|V| — the quantity CLUGP minimizes.

Two wire formats are materialized for the exchange layer
(``repro.dist.halo``):

- the **dense** tables (``red_index`` / ``owner`` / ``own_slot``) that back
  the padded all_gather path — bytes ∝ k²·L_max no matter how good the
  partition is; and
- the **halo routing tables**: for every ordered device pair (p, q) the
  static send list of p's mirror slots owned by q and the matching recv
  list of q's master slots, padded per-pair to ``H_max`` so they jit.
  The mirror-only backend moves 2·k·(k−1)·H_max values per iteration —
  within per-pair padding of the ideal 2·mirrors volume, so partition
  quality shows up on the wire.

All tables are padded to static shapes so the engine jits/shard_maps:

  edge_src/edge_dst (k, E_max)    local-slot endpoints, padded with L_max
  vert_gid          (k, L_max)    local slot → global vertex id (pad: V)
  owner / own_slot  (k, L_max)    master device + slot there
  red_index         (k, k·L_max)  flat all_gather entry → my owned slot
  out_deg           (k, L_max)    global out-degree (pagerank)
  halo_send         (k, k, H_max) [p, q, h] → p's mirror slot whose h-th
                                  value goes to owner q (pad: L_max)
  halo_recv         (k, k, H_max) [q, p, h] → q's master slot where the
                                  h-th value from p lands (pad: L_max)
  halo_cnt          (k, k)        [p, q] → number of REAL mirror lanes in
                                  halo_send[p, q] (lanes are packed at the
                                  front of each pair row, so the first
                                  halo_cnt[p, q] entries are valid)

``halo_cnt`` is what makes the **ragged** exchanges possible: the padded
halo wire ships H_max = max over all pairs for *every* pair, so one hot
(p, q) cell inflates the whole all_to_all.  The ragged exchange instead
runs k−1 ``ppermute`` hops — hop s moves the (p, (p+s) mod k) lanes for
every p at once — each padded only to that *distance's* max population
H_s = max_p halo_cnt[p, (p+s) mod k] (``halo_schedule``).  Skewed
replication factors (the common case on web graphs) make Σ_s H_s ≪
(k−1)·H_max.
"""
from __future__ import annotations

import os
import warnings
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from .. import obs


@dataclass
class PartitionLayout:
    k: int
    num_vertices: int
    num_edges: int
    e_max: int
    l_max: int
    h_max: int               # per-device-pair halo pad length
    edge_src: np.ndarray     # (k, E_max) int32, local slots; pad = l_max
    edge_dst: np.ndarray     # (k, E_max)
    edge_mask: np.ndarray    # (k, E_max) bool
    vert_gid: np.ndarray     # (k, L_max) int32; pad = num_vertices
    vert_mask: np.ndarray    # (k, L_max) bool
    is_master: np.ndarray    # (k, L_max) bool
    owner: np.ndarray        # (k, L_max) int32 master device; pad = 0
    own_slot: np.ndarray     # (k, L_max) int32 slot in owner's table; pad 0
    red_index: np.ndarray    # (k, k*L_max) int32 → my slot or l_max (drop)
    out_deg: np.ndarray      # (k, L_max) int32 global out-degree
    halo_send: np.ndarray    # (k, k, H_max) int32 mirror slots; pad = l_max
    halo_recv: np.ndarray    # (k, k, H_max) int32 master slots; pad = l_max
    halo_cnt: np.ndarray     # (k, k) int32 real lanes per ordered pair
    frontier: np.ndarray     # (k, L_max) bool: replicated vertex (its
    #                          master aggregate depends on mirror lanes);
    #                          interior = vert_mask & ~frontier
    mirrors_total: int       # Σ_v (|P(v)| − 1)

    # per-device tables every backend needs, and each wire format's own
    COMMON_TABLES = ("edge_src", "edge_dst", "edge_mask", "vert_gid",
                     "vert_mask", "is_master", "out_deg")
    EXCHANGE_TABLES = {"dense": ("owner", "own_slot", "red_index"),
                       "halo": ("halo_send", "halo_recv"),
                       # quantized rides the same routing tables; only the
                       # payload encoding differs (int8 codes + scales)
                       "quantized": ("halo_send", "halo_recv"),
                       # the ragged exchanges slice prefixes of the same
                       # tables per ppermute distance (lanes are packed at
                       # the front of each pair row); the static schedule
                       # itself travels in the exchange instance, not as a
                       # device array.  ``frontier`` is what lets the
                       # overlapped body apply interior vertices while the
                       # ring is still in flight.
                       "ragged": ("halo_send", "halo_recv", "frontier"),
                       "ragged_quantized": ("halo_send", "halo_recv",
                                            "frontier")}

    def device_arrays(self, exchange: str | None = None) -> dict:
        """The pytree of arrays each device needs (leading k axis).
        ``exchange`` restricts the wire-format tables to one backend so the
        other format's tables (red_index is the largest, k²·L_max) never
        ship to devices; None includes both."""
        if exchange is not None and exchange not in self.EXCHANGE_TABLES:
            raise ValueError(
                f"unknown exchange {exchange!r}; expected one of "
                f"{sorted(self.EXCHANGE_TABLES)}")
        keys = self.COMMON_TABLES + (
            tuple(t for ts in self.EXCHANGE_TABLES.values() for t in ts)
            if exchange is None else self.EXCHANGE_TABLES[exchange])
        return {f: getattr(self, f) for f in dict.fromkeys(keys)}

    def interior_frontier_stats(self) -> dict:
        """Interior/frontier split of the local vertex tables — the
        overlap headroom of the partition.  Interior vertices (single
        replica) can be gathered/applied while the ragged ring is still
        in flight; frontier vertices (replication > 1) must wait for
        their mirror lanes.  Returns per-partition interior counts and
        fractions plus the global interior fraction — another lens on
        partition quality next to RF (RF → 1 drives interior_frac → 1)."""
        local = self.vert_mask.sum(axis=1)
        interior = (self.vert_mask & ~self.frontier).sum(axis=1)
        with np.errstate(invalid="ignore"):
            frac = np.where(local > 0, interior / np.maximum(local, 1), 1.0)
        total_local = int(local.sum())
        return {
            "interior_per_part": interior.astype(int).tolist(),
            "local_per_part": local.astype(int).tolist(),
            "interior_frac_per_part": [round(float(f), 6) for f in frac],
            "interior_frac": (float(interior.sum()) / total_local
                              if total_local else 1.0),
            "interior_frac_min": float(frac.min(initial=1.0)),
        }

    # -- communication model (bytes per GAS iteration, per §Fig-8 bench) --
    #
    # ONE public entry point: ``comm_bytes(...)`` routes every wire-format
    # model by keyword.  The historical per-format methods
    # (``comm_bytes_mirror_sync`` … ``comm_bytes_dense``) are
    # ``DeprecationWarning`` shims over it, identity-tested.

    # every name ``comm_bytes`` routes: the five engine wire formats plus
    # the two bounds ("ideal" = 2·mirrors, "allreduce" = dense psum) and
    # the legacy table key "dense_gather" (alias of "dense")
    COMM_MODELS = ("allreduce", "dense", "dense_gather", "halo", "ideal",
                   "quantized", "ragged", "ragged_quantized")

    def comm_bytes(self, exchange: str | None = None, *, programs: int = 1,
                   fused: bool = False, lossy: bool = True,
                   value_bytes: int = 4, top_delta: float = 0.25,
                   parts_per_device: int | None = None):
        """Modelled mirror-sync wire bytes per GAS iteration, keyword-
        routed:

        - ``comm_bytes()`` — the full per-exchange table (the Fig. 8
          accounting): ideal / ragged_quantized / quantized / ragged /
          halo / dense_gather / allreduce.
        - ``comm_bytes(exchange)`` — one model.  ``exchange`` is any of
          ``COMM_MODELS``; ``lossy`` is ``halo.lossy_payload(combine,
          dtype)`` — min/int programs ship the exact full-width payload
          on the quantized backends.
        - ``comm_bytes(exchange, programs=N, fused=True)`` — N
          homogeneous programs as one fused step (single collective per
          phase; the int4 fused wire when quantized + lossy).
        - ``comm_bytes(exchange, parts_per_device=m)`` — what ONE chip of
          a mesh of k/m devices, m partitions on each, sends to the other
          chips over the interconnect; lanes between partitions on the
          same chip never leave it.  The halo and dense wires hold any m
          dividing k (halo: its m partitions' H_max-padded lanes to the
          k − m partitions elsewhere; dense: its (m, L_max) block to the
          k/m − 1 other chips); the other wires route one partition a
          chip, where each chip sends 1/k of the whole wire.
        """
        if parts_per_device is not None:
            return self._bytes_per_chip(
                exchange, parts_per_device, programs=programs, fused=fused,
                lossy=lossy, value_bytes=value_bytes, top_delta=top_delta)
        if exchange is None:
            if fused or programs != 1:
                raise ValueError(
                    "comm_bytes(programs=..., fused=...) needs an "
                    "explicit exchange=")
            return {"ideal": self._bytes_ideal(value_bytes),
                    "ragged_quantized": self._bytes_ragged_quantized(
                        top_delta),
                    "quantized": self._bytes_halo_quantized(),
                    "ragged": self._bytes_ragged(value_bytes),
                    "halo": self._bytes_halo(value_bytes),
                    "dense_gather": self._bytes_dense_gather(value_bytes),
                    "allreduce": self._bytes_allreduce(value_bytes)}
        if exchange not in self.COMM_MODELS:
            raise ValueError(
                f"unknown exchange {exchange!r}; expected one of "
                f"{self.COMM_MODELS}")
        if fused and exchange == "quantized" and lossy:
            return self._bytes_fused_quantized(programs)
        single = {
            "dense": lambda: self._bytes_dense_gather(value_bytes),
            "dense_gather": lambda: self._bytes_dense_gather(value_bytes),
            "halo": lambda: self._bytes_halo(value_bytes),
            "quantized": lambda: (self._bytes_halo_quantized() if lossy
                                  else self._bytes_halo(value_bytes)),
            "ragged": lambda: self._bytes_ragged(value_bytes),
            "ragged_quantized": lambda: (
                self._bytes_ragged_quantized(top_delta) if lossy
                else self._bytes_ragged(value_bytes)),
            "ideal": lambda: self._bytes_ideal(value_bytes),
            "allreduce": lambda: self._bytes_allreduce(value_bytes),
        }[exchange]()
        return programs * single

    def _bytes_per_chip(self, exchange: str, m: int, *, programs: int,
                        fused: bool, lossy: bool, value_bytes: int,
                        top_delta: float) -> int:
        """``comm_bytes(exchange, parts_per_device=m)``."""
        if m < 1 or self.k % m:
            raise ValueError(f"{m} partitions a device do not divide "
                             f"k = {self.k}")
        if exchange == "halo" or (exchange == "quantized" and not lossy):
            return programs * 2 * m * (self.k - m) * self.h_max * value_bytes
        if exchange in ("dense", "dense_gather"):
            return programs * 2 * (self.k - m) * self.l_max * value_bytes
        if exchange not in ("quantized", "ragged", "ragged_quantized"):
            raise ValueError(f"{exchange!r} is not a wire of the mesh "
                             "engine")
        if m != 1:
            raise ValueError(f"the {exchange!r} wire routes one partition "
                             "per device")
        return self.comm_bytes(exchange, programs=programs, fused=fused,
                               lossy=lossy, value_bytes=value_bytes,
                               top_delta=top_delta) // self.k

    def _bytes_dense_gather(self, value_bytes: int = 4) -> int:
        """Dense backend: all_gather(k, L_max) twice — every device receives
        k·L_max values per phase regardless of mirror count."""
        return 2 * self.k * self.k * self.l_max * value_bytes

    def _bytes_halo(self, value_bytes: int = 4) -> int:
        """Halo backend: all_to_all(k, H_max) twice — each device puts
        (k−1)·H_max values on the wire per phase (the self block never
        leaves the device)."""
        return 2 * self.k * (self.k - 1) * self.h_max * value_bytes

    def halo_schedule(self) -> tuple:
        """Static per-distance lane counts for the ragged ring exchange:
        entry s−1 is H_s = max_p halo_cnt[p, (p+s) mod k] for hop
        distance s = 1..k−1.  Every device sends its (p → (p+s) mod k)
        lanes on hop s, padded only to that distance's max population;
        H_s = 0 hops are skipped at trace time."""
        k = self.k
        ar = np.arange(k)
        return tuple(int(self.halo_cnt[ar, (ar + s) % k].max(initial=0))
                     for s in range(1, k))

    def _bytes_ragged(self, value_bytes: int = 4) -> int:
        """Ragged exact exchange: per phase every device sends Σ_s H_s
        values over k−1 ppermute hops (no self lane, no cross-pair
        padding) — always ≤ the padded halo volume, and equal to the
        ideal 2·mirrors volume when the per-distance maxima are tight."""
        return 2 * self.k * sum(self.halo_schedule()) * value_bytes

    def _bytes_ragged_quantized(self, top_delta: float = 0.25) -> int:
        """Ragged top-Δ exchange: per hop the sender ships only the
        T_s = max(1, ⌈top_delta·H_s⌉) largest-|Δ| lanes as (int16 lane
        index + int8 code) pairs plus one fp32 max-abs scale — the rest
        stays in the error-feedback residual for a later iteration."""
        total = 0
        for h in self.halo_schedule():
            if h == 0:
                continue
            t = min(h, max(1, int(np.ceil(top_delta * h))))
            total += 3 * t + 4          # 2 B index + 1 B code + scale/H_s
        return 2 * self.k * total

    def _bytes_halo_quantized(self, code_bytes: int = 1,
                              scale_bytes: int = 4) -> int:
        """Quantized halo backend (fp32 programs): each of the k·(k−1)
        off-diagonal lane groups ships H_max int8 codes plus one fp32
        max-abs scale per phase — ~4× below the exact halo wire once
        H_max ≫ scale_bytes.  Min/int programs ship the exact halo
        payload instead (see ``repro.dist.halo``)."""
        return 2 * self.k * (self.k - 1) * (
            self.h_max * code_bytes + scale_bytes)

    # the fused quantized wire ships fp16 scales over 8 subgroups per
    # (destination, program) lane row — 16 B/row (halo._NUM_SCALE_GROUPS)
    FUSED_SCALE_BYTES = 16

    def _bytes_fused_quantized(self, n_programs: int) -> int:
        """Fused multi-program quantized wire (``repro.dist.halo``
        ``*_multi`` on the quantized backend): N lossy programs share one
        all_to_all per phase whose codes are int4 nibble-packed two per
        byte, with fp16 scales over 8 subgroups per (destination,
        program) lane row — (H/2 + 16)/(H + 4) ≈ 0.55× the bytes of N
        separate int8 quantized steps.  The encoder pads each row up to
        a multiple of 8 internally (``halo._quantize_groups``), so the
        wire width is ⌈H_max/8⌉·8 nibbles — H_max itself need not
        divide by 8."""
        h8 = -(-self.h_max // 8) * 8
        return 2 * self.k * (self.k - 1) * n_programs * (
            h8 // 2 + self.FUSED_SCALE_BYTES)

    def _bytes_ideal(self, value_bytes: int = 4) -> int:
        """Ragged lower bound: every mirror value moves exactly once per
        phase — 2·mirrors·bytes per iteration."""
        return 2 * self.mirrors_total * value_bytes

    def _bytes_allreduce(self, value_bytes: int = 4) -> int:
        """dense psum baseline: ring all-reduce over (V,) per device."""
        return 2 * (self.k - 1) * self.num_vertices * value_bytes

    # -- deprecated per-format methods (thin shims over comm_bytes) --

    def _deprecated(self, old: str, new: str):
        warnings.warn(
            f"PartitionLayout.{old} is deprecated; use "
            f"PartitionLayout.{new}", DeprecationWarning, stacklevel=3)

    def comm_bytes_mirror_sync(self, value_bytes: int = 4) -> int:
        self._deprecated("comm_bytes_mirror_sync", "comm_bytes('dense')")
        return self.comm_bytes("dense", value_bytes=value_bytes)

    def comm_bytes_halo(self, value_bytes: int = 4) -> int:
        self._deprecated("comm_bytes_halo", "comm_bytes('halo')")
        return self.comm_bytes("halo", value_bytes=value_bytes)

    def comm_bytes_ragged(self, value_bytes: int = 4) -> int:
        self._deprecated("comm_bytes_ragged", "comm_bytes('ragged')")
        return self.comm_bytes("ragged", value_bytes=value_bytes)

    def comm_bytes_ragged_quantized(self, top_delta: float = 0.25,
                                    value_bytes: int = 4) -> int:
        self._deprecated("comm_bytes_ragged_quantized",
                         "comm_bytes('ragged_quantized')")
        return self.comm_bytes("ragged_quantized", top_delta=top_delta,
                               value_bytes=value_bytes)

    def comm_bytes_halo_quantized(self, code_bytes: int = 1,
                                  scale_bytes: int = 4) -> int:
        self._deprecated("comm_bytes_halo_quantized",
                         "comm_bytes('quantized')")
        return self._bytes_halo_quantized(code_bytes, scale_bytes)

    def comm_bytes_fused_quantized(self, n_programs: int) -> int:
        self._deprecated("comm_bytes_fused_quantized",
                         "comm_bytes('quantized', programs=N, fused=True)")
        return self._bytes_fused_quantized(n_programs)

    def comm_bytes_exchange(self, exchange: str, *, lossy: bool = True,
                            value_bytes: int = 4) -> int:
        self._deprecated("comm_bytes_exchange", "comm_bytes(exchange)")
        return self.comm_bytes(exchange, lossy=lossy,
                               value_bytes=value_bytes)

    def comm_bytes_fused(self, n_programs: int, exchange: str, *,
                         lossy: bool = True, value_bytes: int = 4) -> int:
        self._deprecated(
            "comm_bytes_fused",
            "comm_bytes(exchange, programs=N, fused=True)")
        return self.comm_bytes(exchange, programs=n_programs, fused=True,
                               lossy=lossy, value_bytes=value_bytes)

    def comm_bytes_ideal(self, value_bytes: int = 4) -> int:
        self._deprecated("comm_bytes_ideal", "comm_bytes('ideal')")
        return self.comm_bytes("ideal", value_bytes=value_bytes)

    def comm_bytes_dense(self, value_bytes: int = 4) -> int:
        self._deprecated("comm_bytes_dense", "comm_bytes('allreduce')")
        return self.comm_bytes("allreduce", value_bytes=value_bytes)


def _pad_to(n: int, pad_multiple: int) -> int:
    return int(np.ceil(max(n, 1) / pad_multiple) * pad_multiple)


# numpy's sorts, gathers and bincounts release the GIL: the layout's
# per-partition passes over disjoint slices run side by side
HOST_THREADS = min(8, os.cpu_count() or 1)


def _host_map(fn, items) -> list:
    """``[fn(x) for x in items]``, on ``HOST_THREADS`` threads.  A pool
    is made per call (a pool kept across calls would not survive a
    fork)."""
    items = list(items)
    if HOST_THREADS < 2 or len(items) < 2:
        return [fn(x) for x in items]
    with ThreadPoolExecutor(min(HOST_THREADS, len(items))) as pool:
        return list(pool.map(fn, items))


def _edges_by_partition(assign: np.ndarray, k: int) -> list:
    """For each partition, the indices of its edges in stream order.

    The stream is cut into one contiguous chunk a thread; each chunk is
    sorted by partition with a stable sort of 16-bit keys (numpy's radix
    sort), and a partition's runs are then joined in chunk order."""
    assign = np.asarray(assign)
    E = assign.shape[0]
    cuts = [E * c // HOST_THREADS for c in range(HOST_THREADS + 1)]

    def sort(c):
        lo, hi = cuts[c], cuts[c + 1]
        a = assign[lo:hi]
        order = np.argsort(a.astype(np.int16) if k <= (1 << 15) else a,
                           kind="stable")
        order += lo
        ends = np.zeros(k + 1, dtype=np.int64)
        np.cumsum(np.bincount(a, minlength=k)[:k], out=ends[1:])
        return order, ends

    runs = _host_map(sort, range(HOST_THREADS))
    return _host_map(lambda p: np.concatenate(
        [order[ends[p]:ends[p + 1]] for order, ends in runs]), range(k))


def build_layout(src: np.ndarray, dst: np.ndarray, assign: np.ndarray,
                 num_vertices: int, k: int,
                 pad_multiple: int = 8) -> PartitionLayout:
    """Vectorized layout builder — bincount/radix-sort/searchsorted
    passes, no per-vertex Python loops (≥5× the reference builder at 10k
    vertices; see ``build_layout_reference`` for the retained oracle).
    The edges are grouped by partition, and each partition's vertices
    counted in a (V,) table where k·V ≤ 2^25, else found by sorting its
    endpoints: the same tables either way.  The per-partition passes run
    side by side on ``HOST_THREADS`` threads (numpy releases the GIL);
    only the master election walks the partitions in order.

    Accepts device-resident (jax) arrays directly: the jit/sharded
    partitioner backends hand their edge→partition assignment straight in
    and the single ``np.asarray`` below is the only host transfer — no
    per-edge host loop ever touches the assignment."""
    with obs.span("layout.build", k=k):
        return _build_layout(src, dst, assign, num_vertices, k,
                             pad_multiple)


def _build_layout(src, dst, assign, num_vertices: int, k: int,
                  pad_multiple: int) -> PartitionLayout:
    E = src.shape[0]
    V = num_vertices
    src = np.asarray(src)
    dst = np.asarray(dst)
    # a partition's vertices are found in a (V,) count table when k·V is
    # small enough (≤ 2^25), else by sorting its endpoints
    dense = k * V <= (1 << 25)
    edges = _edges_by_partition(assign, k)
    gdeg = np.bincount(src, minlength=V)          # global out degree

    def scan(p):
        """Partition p's edge endpoints, its vertices (ascending: the
        order of its local slots) and each one's endpoint count."""
        s, d = src[edges[p]], dst[edges[p]]
        if dense:
            cnt = np.bincount(s, minlength=V) + np.bincount(d, minlength=V)
            verts = np.flatnonzero(cnt)
            return s, d, verts, cnt[verts]
        verts, cnt = np.unique(np.concatenate([s, d]), return_counts=True)
        return s, d, verts, cnt

    parts = _host_map(scan, range(k))

    # master election: per vertex, the partition with max endpoint count,
    # ties → lowest partition id
    best = np.zeros(V, dtype=np.int64)
    master_of = np.zeros(V, dtype=np.int32)
    replic = np.zeros(V, dtype=np.int32)
    for p, (_, _, verts, cnt) in enumerate(parts):
        win = cnt > best[verts]
        best[verts[win]] = cnt[win]
        master_of[verts[win]] = p
        replic[verts] += 1
    # each vertex's slot in its master partition
    own_slot_of = np.zeros(V, dtype=np.int32)
    owners = []
    for p, (_, _, verts, _) in enumerate(parts):
        own = master_of[verts]
        owners.append(own)
        mine = np.flatnonzero(own == p)
        own_slot_of[verts[mine]] = mine
    l_max = _pad_to(max((v.shape[0] for _, _, v, _ in parts), default=1),
                    pad_multiple)
    e_max = _pad_to(max((s.shape[0] for s, _, _, _ in parts), default=1),
                    pad_multiple)
    # mirror lanes per ordered (mirror partition, owner partition) pair
    halo_cnt = np.stack([np.bincount(own[own != p], minlength=k)
                         for p, own in enumerate(owners)]).astype(np.int32)
    h_max = _pad_to(int(halo_cnt.max(initial=0)), pad_multiple)

    vert_gid = np.empty((k, l_max), dtype=np.int32)
    vert_mask = np.zeros((k, l_max), dtype=bool)
    is_master = np.zeros((k, l_max), dtype=bool)
    out_deg = np.zeros((k, l_max), dtype=np.int32)
    owner = np.zeros((k, l_max), dtype=np.int32)
    own_slot = np.zeros((k, l_max), dtype=np.int32)
    frontier = np.zeros((k, l_max), dtype=bool)
    # reduce map: flat all_gather entry (j*L_max + slot) → my slot (if I am
    # the owner of that entry's vertex) else l_max (dropped)
    red_index = np.empty((k, k * l_max), dtype=np.int32)
    edge_src = np.empty((k, e_max), dtype=np.int32)
    edge_dst = np.empty((k, e_max), dtype=np.int32)
    edge_mask = np.zeros((k, e_max), dtype=bool)
    # halo routing tables: one lane per mirror replica, grouped by the
    # ordered (mirror, owner) pair in local-slot order and padded to the
    # max pair population H_max — every mirror is routed exactly once
    halo_send = np.empty((k, k, h_max), dtype=np.int32)
    halo_recv = np.empty((k, k, h_max), dtype=np.int32)

    def fill(p):
        """Row p of every table, and partition p's columns of red_index
        and halo_recv: the partitions write disjoint cells."""
        s, d, verts, _ = parts[p]
        own, n, m = owners[p], verts.shape[0], s.shape[0]
        slots = own_slot_of[verts]
        vert_gid[p, :n] = verts
        vert_gid[p, n:] = V
        vert_mask[p, :n] = True
        is_master[p, :n] = own == p
        out_deg[p, :n] = gdeg[verts]
        owner[p, :n] = own
        own_slot[p, :n] = slots
        frontier[p, :n] = replic[verts] > 1
        cols = red_index[:, p * l_max:(p + 1) * l_max]
        cols.fill(l_max)
        cols[own, np.arange(n)] = slots
        if dense:
            local = np.empty(V, dtype=np.int32)     # vertex → local slot
            local[verts] = np.arange(n, dtype=np.int32)
            edge_src[p, :m] = local[s]
            edge_dst[p, :m] = local[d]
        else:
            edge_src[p, :m] = np.searchsorted(verts, s)
            edge_dst[p, :m] = np.searchsorted(verts, d)
        edge_src[p, m:] = l_max
        edge_dst[p, m:] = l_max
        edge_mask[p, :m] = True
        mirror = np.flatnonzero(own != p)
        mirror = mirror[np.argsort(own[mirror], kind="stable")]
        to = own[mirror]
        first = np.zeros(k, dtype=np.int64)
        np.cumsum(halo_cnt[p, :-1], out=first[1:])
        lane = np.arange(mirror.shape[0]) - first[to]
        halo_send[p].fill(l_max)
        halo_recv[:, p].fill(l_max)
        halo_send[p, to, lane] = mirror
        halo_recv[to, p, lane] = slots[mirror]

    _host_map(fill, range(k))
    mirrors_total = int(np.maximum(replic - 1, 0).sum())

    return PartitionLayout(
        k=k, num_vertices=num_vertices, num_edges=E, e_max=e_max,
        l_max=l_max, h_max=h_max, edge_src=edge_src, edge_dst=edge_dst,
        edge_mask=edge_mask, vert_gid=vert_gid, vert_mask=vert_mask,
        is_master=is_master, owner=owner, own_slot=own_slot,
        red_index=red_index, out_deg=out_deg, halo_send=halo_send,
        halo_recv=halo_recv, halo_cnt=halo_cnt, frontier=frontier,
        mirrors_total=mirrors_total)


def build_layout_reference(src: np.ndarray, dst: np.ndarray,
                           assign: np.ndarray, num_vertices: int, k: int,
                           pad_multiple: int = 8) -> PartitionLayout:
    """The seed O(V·k) dict/loop builder, retained as the equivalence
    oracle for ``build_layout`` (tests compare every table)."""
    E = src.shape[0]
    order = np.argsort(assign, kind="stable")
    s, d, a = src[order], dst[order], assign[order]
    bounds = np.searchsorted(a, np.arange(k + 1))

    # global out degree
    gdeg = np.zeros(num_vertices, dtype=np.int64)
    np.add.at(gdeg, src, 1)

    # per-partition local vertex tables + master election by edge count
    locals_: list[np.ndarray] = []
    per_part_counts: list[dict] = []
    for p in range(k):
        lo, hi = bounds[p], bounds[p + 1]
        verts, cnt = np.unique(np.concatenate([s[lo:hi], d[lo:hi]]),
                               return_counts=True)
        locals_.append(verts)
        per_part_counts.append(dict(zip(verts.tolist(), cnt.tolist())))

    # master = partition with max edge count of v (ties → lowest partition)
    best_cnt = np.zeros(num_vertices, dtype=np.int64)
    master_of = np.full(num_vertices, -1, dtype=np.int64)
    for p in range(k):
        verts = locals_[p]
        cnt = np.array([per_part_counts[p][int(v)] for v in verts],
                       dtype=np.int64)
        better = cnt > best_cnt[verts]
        upd = verts[better]
        best_cnt[upd] = cnt[better]
        master_of[upd] = p

    l_max = max((len(v) for v in locals_), default=1)
    l_max = _pad_to(l_max, pad_multiple)
    e_max = _pad_to(int(max(bounds[1:] - bounds[:-1], default=1)),
                    pad_multiple)

    vert_gid = np.full((k, l_max), num_vertices, dtype=np.int32)
    vert_mask = np.zeros((k, l_max), dtype=bool)
    is_master = np.zeros((k, l_max), dtype=bool)
    out_deg = np.zeros((k, l_max), dtype=np.int32)
    slot_of = {}         # (p, gid) -> slot
    for p in range(k):
        verts = locals_[p]
        n = len(verts)
        vert_gid[p, :n] = verts
        vert_mask[p, :n] = True
        is_master[p, :n] = master_of[verts] == p
        out_deg[p, :n] = gdeg[verts]
        for sl, v in enumerate(verts.tolist()):
            slot_of[(p, v)] = sl

    owner = np.zeros((k, l_max), dtype=np.int32)
    own_slot = np.zeros((k, l_max), dtype=np.int32)
    for p in range(k):
        verts = locals_[p]
        for sl, v in enumerate(verts.tolist()):
            o = int(master_of[v])
            owner[p, sl] = o
            own_slot[p, sl] = slot_of[(o, v)]

    # reduce map: flat all_gather entry (j*L_max + slot) → my slot (if I am
    # the owner of that entry's vertex) else l_max (dropped)
    red_index = np.full((k, k * l_max), l_max, dtype=np.int32)
    for j in range(k):
        verts = locals_[j]
        for sl, v in enumerate(verts.tolist()):
            o = int(master_of[v])
            red_index[o, j * l_max + sl] = slot_of[(o, v)]

    edge_src = np.full((k, e_max), l_max, dtype=np.int32)
    edge_dst = np.full((k, e_max), l_max, dtype=np.int32)
    edge_mask = np.zeros((k, e_max), dtype=bool)
    for p in range(k):
        lo, hi = bounds[p], bounds[p + 1]
        n = hi - lo
        if n == 0:
            continue
        edge_src[p, :n] = [slot_of[(p, int(x))] for x in s[lo:hi]]
        edge_dst[p, :n] = [slot_of[(p, int(x))] for x in d[lo:hi]]
        edge_mask[p, :n] = True

    # halo routing: per ordered (mirror, owner) pair, mirrors in local-slot
    # order — the same grouping the vectorized builder emits.
    pair_lanes: dict = {}
    for p in range(k):
        for sl, v in enumerate(locals_[p].tolist()):
            o = int(master_of[v])
            if o == p:
                continue
            pair_lanes.setdefault((p, o), []).append(
                (sl, slot_of[(o, v)]))
    h_max = max((len(v) for v in pair_lanes.values()), default=0)
    h_max = _pad_to(h_max, pad_multiple)
    halo_send = np.full((k, k, h_max), l_max, dtype=np.int32)
    halo_recv = np.full((k, k, h_max), l_max, dtype=np.int32)
    halo_cnt = np.zeros((k, k), dtype=np.int32)
    for (p, o), lanes in pair_lanes.items():
        halo_cnt[p, o] = len(lanes)
        for h, (sl, osl) in enumerate(lanes):
            halo_send[p, o, h] = sl
            halo_recv[o, p, h] = osl

    replic = np.zeros(num_vertices, dtype=np.int64)
    for p in range(k):
        replic[locals_[p]] += 1
    mirrors_total = int(np.maximum(replic - 1, 0).sum())

    frontier = np.zeros((k, l_max), dtype=bool)
    for p in range(k):
        verts = locals_[p]
        frontier[p, :len(verts)] = replic[verts] > 1

    return PartitionLayout(
        k=k, num_vertices=num_vertices, num_edges=E, e_max=e_max,
        l_max=l_max, h_max=h_max, edge_src=edge_src, edge_dst=edge_dst,
        edge_mask=edge_mask, vert_gid=vert_gid, vert_mask=vert_mask,
        is_master=is_master, owner=owner, own_slot=own_slot,
        red_index=red_index, out_deg=out_deg, halo_send=halo_send,
        halo_recv=halo_recv, halo_cnt=halo_cnt, frontier=frontier,
        mirrors_total=mirrors_total)
