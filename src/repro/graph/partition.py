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

import warnings
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
                   value_bytes: int = 4, top_delta: float = 0.25):
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
        """
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


def build_layout(src: np.ndarray, dst: np.ndarray, assign: np.ndarray,
                 num_vertices: int, k: int,
                 pad_multiple: int = 8) -> PartitionLayout:
    """Vectorized layout builder — pure np.unique/searchsorted/bincount
    passes, no per-vertex Python loops (≥5× the reference builder at 10k
    vertices; see ``build_layout_reference`` for the retained oracle).

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
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    assign = np.asarray(assign)
    order = np.argsort(assign, kind="stable")
    s, d, a = src[order], dst[order], assign[order].astype(np.int64)
    bounds = np.searchsorted(a, np.arange(k + 1))

    # global out degree
    gdeg = np.bincount(src, minlength=num_vertices)

    # one row per (partition, vertex) replica, with its endpoint count.
    # np.unique on the fused key sorts by (partition, vertex), so rows are
    # grouped by partition with vertices ascending — the same order the
    # reference builder's per-partition np.unique produces.
    key = np.concatenate([a, a]) * num_vertices + np.concatenate([s, d])
    uniq, cnt = np.unique(key, return_counts=True)
    up = uniq // num_vertices        # partition of each replica row
    uv = uniq % num_vertices         # vertex gid of each replica row
    n_rows = uniq.shape[0]

    # master election: per vertex, the partition with max endpoint count,
    # ties → lowest partition id.  lexsort is keyed last-to-first.
    elect = np.lexsort((up, -cnt, uv))
    uv_e, up_e = uv[elect], up[elect]
    first = np.ones(n_rows, dtype=bool)
    np.not_equal(uv_e[1:], uv_e[:-1], out=first[1:])
    master_of = np.full(num_vertices, -1, dtype=np.int64)
    master_of[uv_e[first]] = up_e[first]

    part_sizes = np.bincount(up, minlength=k)
    l_max = _pad_to(int(part_sizes.max(initial=1)), pad_multiple)
    e_max = _pad_to(int(max(bounds[1:] - bounds[:-1], default=1)),
                    pad_multiple)

    # local slot of each replica row = rank within its partition group
    row_start = np.searchsorted(up, np.arange(k + 1))
    slot = np.arange(n_rows) - row_start[up]

    if k * num_vertices <= (1 << 25):
        # dense inverse map: O(1) per lookup, ≤128 MiB of int32
        _lookup = np.empty(k * num_vertices, dtype=np.int32)
        _lookup[uniq] = slot

        def slot_of(parts: np.ndarray, verts: np.ndarray) -> np.ndarray:
            """Vectorized (partition, gid) → local slot."""
            return _lookup[parts * num_vertices + verts]
    else:
        def slot_of(parts: np.ndarray, verts: np.ndarray) -> np.ndarray:
            """Vectorized (partition, gid) → local slot via sorted keys."""
            return slot[np.searchsorted(uniq, parts * num_vertices + verts)]

    replic = np.bincount(uv, minlength=num_vertices)

    vert_gid = np.full((k, l_max), num_vertices, dtype=np.int32)
    vert_mask = np.zeros((k, l_max), dtype=bool)
    is_master = np.zeros((k, l_max), dtype=bool)
    out_deg = np.zeros((k, l_max), dtype=np.int32)
    owner = np.zeros((k, l_max), dtype=np.int32)
    own_slot = np.zeros((k, l_max), dtype=np.int32)
    frontier = np.zeros((k, l_max), dtype=bool)
    row_owner = master_of[uv]
    row_own_slot = slot_of(row_owner, uv)
    row_is_master = row_owner == up
    row_deg = gdeg[uv]
    row_frontier = replic[uv] > 1
    # rows are grouped by partition, so per-partition contiguous slice
    # copies beat a (k, slot) fancy scatter by ~5×
    for p in range(k):
        r0, r1 = int(row_start[p]), int(row_start[p + 1])
        n = r1 - r0
        if n == 0:
            continue
        rows = slice(r0, r1)
        vert_gid[p, :n] = uv[rows]
        vert_mask[p, :n] = True
        is_master[p, :n] = row_is_master[rows]
        out_deg[p, :n] = row_deg[rows]
        owner[p, :n] = row_owner[rows]
        own_slot[p, :n] = row_own_slot[rows]
        frontier[p, :n] = row_frontier[rows]

    # reduce map: flat all_gather entry (j*L_max + slot) → my slot (if I am
    # the owner of that entry's vertex) else l_max (dropped)
    red_index = np.full((k, k * l_max), l_max, dtype=np.int32)
    red_index[row_owner, up * l_max + slot] = row_own_slot

    edge_src = np.full((k, e_max), l_max, dtype=np.int32)
    edge_dst = np.full((k, e_max), l_max, dtype=np.int32)
    edge_mask = np.zeros((k, e_max), dtype=bool)
    if E:
        src_slots = slot_of(a, s)
        dst_slots = slot_of(a, d)
        # edges are sorted by partition: contiguous copies, no scatter
        for p in range(k):
            lo, hi = int(bounds[p]), int(bounds[p + 1])
            n = hi - lo
            if n == 0:
                continue
            edge_src[p, :n] = src_slots[lo:hi]
            edge_dst[p, :n] = dst_slots[lo:hi]
            edge_mask[p, :n] = True

    # halo routing tables: one lane per mirror replica, grouped by the
    # ordered (mirror partition, owner partition) pair and padded to the
    # max pair population H_max — every mirror is routed exactly once.
    mir = row_owner != up
    mp_, mq = up[mir], row_owner[mir]
    m_slot, m_own_slot = slot[mir], row_own_slot[mir]
    pair = mp_ * k + mq
    po = np.argsort(pair, kind="stable")
    pair_s = pair[po]
    lane = np.arange(pair_s.shape[0]) - np.searchsorted(pair_s, pair_s)
    h_max = _pad_to(int(lane.max(initial=-1)) + 1, pad_multiple)
    halo_send = np.full((k, k, h_max), l_max, dtype=np.int32)
    halo_recv = np.full((k, k, h_max), l_max, dtype=np.int32)
    halo_send[mp_[po], mq[po], lane] = m_slot[po]
    halo_recv[mq[po], mp_[po], lane] = m_own_slot[po]
    halo_cnt = np.bincount(pair, minlength=k * k).reshape(k, k) \
        .astype(np.int32)

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
