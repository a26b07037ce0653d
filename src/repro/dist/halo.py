"""Exchange abstraction for the vertex-cut GAS engine's mirror sync.

The engine's per-iteration communication is two phases over the mirror
replicas (paper §II-B): mirror partials reduce to masters (gather), master
values broadcast back to mirrors (scatter).  This module gives the engine a
pluggable wire format for those phases:

- ``DenseExchange`` — the seed path: ``all_gather`` the full padded
  (L_max,) slab from every device and index into it with the static
  ``red_index`` / ``(owner, own_slot)`` tables.  Bytes ∝ k²·L_max per
  phase, independent of partition quality.
- ``HaloExchange`` — mirror-routed: each device packs only its mirror
  slots into per-destination lanes (``halo_send``) and a single
  ``all_to_all`` delivers every lane to its owner, which scatters via
  ``halo_recv``.  Bytes ∝ k·(k−1)·H_max per phase — within per-pair
  padding of the ideal 2·mirrors volume, so CLUGP's mirror reduction is
  the engine's real wire cost.
- ``RaggedHaloExchange`` — halo routing without the cross-pair padding:
  the padded ``all_to_all`` ships H_max lanes for *every* ordered pair,
  so one hot (p, q) cell inflates the whole collective.  The ragged
  exchange instead walks the k−1 ring distances with one ``ppermute``
  each — hop s moves every device's (p → (p+s) mod k) lanes at once,
  padded only to that distance's max population H_s (the layout's
  ``halo_schedule``, baked into the exchange instance as a static
  tuple so it jits).  Σ_s H_s ≤ (k−1)·H_max always, and the gap is the
  replication-factor skew CLUGP leaves behind — bytes land within
  per-distance padding of the ideal 2·mirrors volume.  Zero-population
  distances are skipped at trace time.
- ``RaggedQuantizedHaloExchange`` — ragged routing with a **top-Δ**
  sparsified payload: per hop the sender quantizes only the
  T_s = ⌈top_delta·H_s⌉ largest-|Δ| lanes of its error-feedback delta
  (int16 lane indices + int8 codes + one fp32 scale), leaving the rest
  in the residual for a later iteration.  As a fixed-point program
  converges its deltas concentrate, so shipping the heavy quarter per
  step loses little transient speed while cutting bytes below even the
  dense-delta quantized wire.
- ``QuantizedHaloExchange`` — halo routing with a compressed payload:
  each destination lane group quantizes to int8 codes + one fp32 max-abs
  scale (``dist.compress.quantize_rows``), cutting the per-mirror payload
  ~4× on top of the halo routing cut.  What goes on the wire is the
  **delta** against a reconstruction reference both endpoints advance in
  lockstep, with the quantization error carried in an error-feedback
  residual (1-bit-SGD style) threaded through the iteration carry — as a
  fixed-point program (pagerank) converges its deltas shrink, the scales
  shrink with them, and the reconstruction converges to the exact values
  instead of dithering at one quantization step.  ``combine="min"`` /
  integer programs (CC's label propagation) are already exact in int32, so
  they skip quantization and ship the exact halo payload.

Every backend exposes the same stateful operations (state is ``()`` for
the exact backends and a pytree of lane-shaped reference/residual arrays
for the quantized one, so it threads through ``fori_loop`` carries):

  init_state(dev, dtype, combine)                  -> state
  reduce_to_masters(partials, dev, combine, state)  -> (total, state)
  broadcast_from_masters(masters, dev, combine, state) -> (values, state)
  reduce_stacked / broadcast_stacked                — same, on (k, …) stacks

``dev`` is the layout's ``device_arrays()`` pytree — full (k, …) stacks
in the stacked forms, and in the shard_map forms the device's **local
stacks**: its m = k/D partitions, (m, …), where D is the size of the mesh
axis and device d holds partitions d·m … d·m + m − 1.  ``combine`` is
``"sum"`` (pagerank) or ``"min"`` (label propagation).  The stacked
forms model the collective with a transpose (all_to_all) / broadcast
(all_gather), so tests and host benchmarks run the identical math.

Only ``DenseExchange`` and ``HaloExchange`` route several partitions per
device (one collective over the D devices; lanes between partitions on
the same chip stay there).  The quantized and ragged wires route one
partition per device and refuse local stacks of more than one
(``_one_partition``); the quantized wire's exact payloads ride the halo
wire and so run at any m.  The fused ``*_multi`` per-device halves take
one partition, unstacked.

**Multi-lane (fused multi-program) operations.**  N homogeneous GAS
programs over the same layout can share one exchange per phase: values
grow a leading program axis ((N, L_max) per device), lanes become
(k, N, H_max), and ONE collective ships every program's mirror traffic —
the ``*_multi`` halves below (``init_state_multi`` /
``reduce_to_masters_multi`` / ``broadcast_from_masters_multi`` /
``reduce_stacked_multi`` / ``broadcast_stacked_multi``).  For the exact
backends the fused payload is exactly the concatenation of the separate
payloads; the quantized backend switches to the **fused wire format**:
int4 delta codes packed two-per-byte along the lane axis, with fp16
max-abs scales over 8 subgroups per (destination, program) lane row
(H_max is padded to a multiple of 8, so rows split evenly and the nibble
count is even).  Per-program, per-subgroup scales mean one hot program or
lane can't wash out another's precision — with a single scale per row the
coarse int4 grid stops being a contraction under error feedback and the
iteration plateaus instead of converging.  Halving the code width is what
makes fusing N programs genuinely cheaper than N separate quantized steps
((H/2 + 16)/(H + 4) ≈ 0.55×); the coarser int4 step is absorbed by the
same error-feedback residual, so fixed-point programs still converge to
the exact fixed point, just along a slightly longer transient.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .collectives import varying
from .compress import dequantize_rows, quantize_rows


def _pad_value(combine: str, dtype) -> jnp.ndarray:
    """Identity element fed into padded send lanes; recv pads are dropped
    by the segment reduce regardless, so this only has to be shape-safe
    (and, for the quantized path, keep pad lanes exactly zero)."""
    dtype = jnp.dtype(dtype)
    if combine == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(jnp.iinfo(dtype).max, dtype)
    return jnp.asarray(3e38, dtype)


def _segment_combine(vals, segments, num_segments: int, combine: str):
    if combine == "sum":
        return jax.ops.segment_sum(vals, segments,
                                   num_segments=num_segments)
    return jax.ops.segment_min(vals, segments, num_segments=num_segments)


def _merge(local, received, combine: str):
    if combine == "sum":
        return local + received
    return jnp.minimum(local, received)


def _pack(values, lanes, combine: str):
    """values (L_max,) → (k, H_max) send lanes; pad lanes read the
    combine identity appended at index L_max."""
    pad = jnp.full((1,), _pad_value(combine, values.dtype), values.dtype)
    return jnp.concatenate([values, pad])[lanes]


def _unpack(new_master, recv, dev):
    """Scatter received master values into this device's mirror slots
    (each valid lane targets a distinct slot; pads land in the dropped
    L_max bucket); master slots keep their local value."""
    l_max = new_master.shape[0]
    scattered = jnp.zeros((l_max + 1,), new_master.dtype).at[
        dev["halo_send"].reshape(-1)].set(recv.reshape(-1))[:l_max]
    return jnp.where(dev["is_master"], new_master, scattered)


def _transposed(send):
    """The stacked form's all_to_all: (k, k, H_max) lanes by (source,
    destination) → by (destination, source)."""
    return jnp.swapaxes(send, 0, 1)


def _one_partition(wire: str, values, dev, state):
    """The one partition of a device's local stacks, for the wires that
    route one partition per device: (values, tables, state) without
    their leading axis.  Local stacks of more than one are refused."""
    m = values.shape[0]
    if m != 1:
        raise ValueError(
            f"the {wire!r} wire routes one partition per device and this "
            f"mesh puts {m} on each; use the 'halo' or 'dense' wire, or a "
            "mesh of k devices")
    return jax.tree_util.tree_map(lambda x: x[0], (values, dev, state))


def _restacked(values, state):
    """What a one-partition half returns, as local stacks of one."""
    return values[None], jax.tree_util.tree_map(lambda x: x[None], state)


# --------------------------------------------------- multi-lane helpers

def _pack_multi(values, lanes, combine: str):
    """values (N, L_max) → (k, N, H_max) send lanes (program axis rides
    inside each destination block, so one collective ships all N)."""
    n = values.shape[0]
    pad = jnp.full((n, 1), _pad_value(combine, values.dtype), values.dtype)
    ext = jnp.concatenate([values, pad], axis=1)        # (N, L_max+1)
    return jnp.moveaxis(ext[:, lanes], 0, 1)            # (k, N, H_max)


def _unpack_multi(new_master, recv, dev):
    """new_master (N, L_max), recv (k, N, H_max) → (N, L_max) values."""
    return jax.vmap(lambda m, r: _unpack(m, r, dev))(
        new_master, jnp.moveaxis(recv, 1, 0))


def _segment_combine_multi(recv, slots, num_segments: int, combine: str):
    """recv (k, N, H_max) lanes + shared (k, H_max) slot table →
    per-program (N, num_segments-1) reductions."""
    flat_slots = slots.reshape(-1)
    return jax.vmap(
        lambda r: _segment_combine(r.reshape(-1), flat_slots,
                                   num_segments, combine)[:num_segments - 1]
    )(jnp.moveaxis(recv, 1, 0))


_Q4MAX = 7.0
# each (destination, program) lane row splits into this many scale
# subgroups: finer groups isolate hot lanes so the coarse int4 grid stays
# a contraction under error feedback (one scale per whole row diverges),
# while 8 fp16 scales cost only 16 B per row on the wire.  h_max is
# padded to a multiple of 8 (``partition._pad_to``), so rows always
# split evenly and the nibble pack always sees an even lane count.
_NUM_SCALE_GROUPS = 8


def _quantize_groups(err):
    """int4 codes + one fp16 scale per 1/8th of the trailing lane row.

    Rows whose lane count is not a multiple of ``_NUM_SCALE_GROUPS`` are
    zero-padded up to one before grouping — pad lanes quantize to code 0
    and decoders slice them back off — so the returned codes always have
    a trailing dim divisible by 8 (and therefore even, which is what the
    nibble pack needs), whatever ``h_max`` the layout was padded to."""
    n = err.shape[-1]
    n8 = -(-n // _NUM_SCALE_GROUPS) * _NUM_SCALE_GROUPS
    if n8 != n:
        err = jnp.pad(err, [(0, 0)] * (err.ndim - 1) + [(0, n8 - n)])
    shp = err.shape
    grp = err.reshape(*shp[:-1], _NUM_SCALE_GROUPS,
                      n8 // _NUM_SCALE_GROUPS)
    amax = jnp.max(jnp.abs(grp), axis=-1)
    scales = jnp.where(amax > 0, amax / _Q4MAX, 1.0).astype(jnp.float16)
    s = jnp.maximum(scales.astype(jnp.float32), 1e-30)[..., None]
    codes = jnp.clip(jnp.round(grp / s), -_Q4MAX, _Q4MAX).astype(jnp.int8)
    return codes.reshape(shp), scales


def _dequantize_groups(codes, scales):
    """Inverse grid step; both endpoints apply the identical fp16 scales
    received on the wire, so sender/receiver references stay in lockstep."""
    shp = codes.shape
    grp = codes.reshape(*shp[:-1], _NUM_SCALE_GROUPS,
                        shp[-1] // _NUM_SCALE_GROUPS)
    return (grp.astype(jnp.float32) *
            scales.astype(jnp.float32)[..., None]).reshape(shp)


def _nibble_pack(codes):
    """int8 codes in [-7, 7], even trailing dim → two codes per byte."""
    lo = codes[..., 0::2] & 0xF
    hi = codes[..., 1::2] & 0xF
    return (lo | (hi << 4)).astype(jnp.int8)


def _nibble_unpack(packed):
    """Inverse of ``_nibble_pack`` (arithmetic shifts sign-extend)."""
    lo = jnp.right_shift(jnp.left_shift(packed, 4).astype(jnp.int8), 4)
    hi = jnp.right_shift(packed, 4)
    return jnp.stack([lo, hi], axis=-1).reshape(
        *packed.shape[:-1], 2 * packed.shape[-1])


@dataclass(frozen=True)
class DenseExchange:
    """Padded all_gather mirror sync (the seed wire format)."""
    axis: str | None = None
    name = "dense"

    def init_state(self, dev, dtype, combine: str = "sum"):
        return ()

    # -- per-device halves (inside shard_map over ``axis``): gather every
    # device's (m, L_max) local stack into the (k, L_max) stack, then the
    # stacked half over this device's m partitions --
    def reduce_to_masters(self, partials, dev, combine: str = "sum",
                          state=()):
        return self.reduce_stacked(self._gather(partials), dev, combine,
                                   state)

    def broadcast_from_masters(self, new_masters, dev, combine: str = "sum",
                               state=()):
        return self.broadcast_stacked(self._gather(new_masters), dev,
                                      combine, state)

    def _gather(self, local):
        g = jax.lax.all_gather(local, self.axis)            # (D, m, L_max)
        return g.reshape(-1, local.shape[-1])               # (k, L_max)

    # -- stacked halves ((k, L_max) arrays on one device) --
    def reduce_stacked(self, partials, dev, combine: str = "sum", state=()):
        flat = partials.reshape(-1)
        return jax.vmap(
            lambda d: self._reduce_flat(flat, d, combine))(dev), state

    def broadcast_stacked(self, masters, dev, combine: str = "sum",
                          state=()):
        return jax.vmap(
            lambda d: masters[d["owner"], d["own_slot"]])(dev), state

    @staticmethod
    def _reduce_flat(flat_gathered, dev, combine: str):
        l_max = dev["vert_gid"].shape[0]
        return _segment_combine(flat_gathered, dev["red_index"],
                                l_max + 1, combine)[:l_max]

    # -- multi-lane halves (fused programs; values carry a leading N) --
    def init_state_multi(self, dev, dtype, combine: str, n: int):
        return ()

    def reduce_to_masters_multi(self, partials, dev, combine: str = "sum",
                                state=()):
        g = jax.lax.all_gather(partials, self.axis)         # (k, N, L_max)
        flat = jnp.moveaxis(g, 1, 0).reshape(g.shape[1], -1)
        return jax.vmap(
            lambda f: self._reduce_flat(f, dev, combine))(flat), state

    def broadcast_from_masters_multi(self, new_masters, dev,
                                     combine: str = "sum", state=()):
        g = jax.lax.all_gather(new_masters, self.axis)      # (k, N, L_max)
        return jax.vmap(
            lambda gn: gn[dev["owner"], dev["own_slot"]]
        )(jnp.moveaxis(g, 1, 0)), state

    def reduce_stacked_multi(self, partials, dev, combine: str = "sum",
                             state=()):
        # partials (k, N, L_max): each program reduces over its own flat
        # (k·L_max) gather, per destination device
        flat = jnp.moveaxis(partials, 1, 0).reshape(partials.shape[1], -1)
        return jnp.moveaxis(jax.vmap(
            lambda f: jax.vmap(
                lambda d: self._reduce_flat(f, d, combine))(dev)
        )(flat), 0, 1), state

    def broadcast_stacked_multi(self, masters, dev, combine: str = "sum",
                                state=()):
        per_prog = jnp.moveaxis(masters, 1, 0)              # (N, k, L_max)
        return jnp.moveaxis(jax.vmap(
            lambda m: jax.vmap(
                lambda d: m[d["owner"], d["own_slot"]])(dev)
        )(per_prog), 0, 1), state

    def bytes_per_iter(self, layout, value_bytes: int = 4) -> int:
        return layout.comm_bytes("dense", value_bytes=value_bytes)


@dataclass(frozen=True)
class HaloExchange:
    """Mirror-routed all_to_all sync over the layout's halo tables.

    Reduce: pack mirror values into (k, H_max) destination lanes, one
    all_to_all, scatter-combine received lanes into master slots, merge
    with the local partial (a master's own contribution never leaves the
    device).  Broadcast runs the same route backwards: masters pack
    ``halo_recv`` lanes, mirrors scatter via ``halo_send``; master slots
    keep their local value.
    """
    axis: str | None = None
    name = "halo"

    def init_state(self, dev, dtype, combine: str = "sum"):
        return ()

    # -- per-device halves (inside shard_map over ``axis``): the device's
    # m partitions pack (m, k, H_max) lanes, one all_to_all over the D
    # devices routes them (``_routed``), and each local partition
    # combines or scatters the lanes it received, as in the stacked form --
    def reduce_to_masters(self, partials, dev, combine: str = "sum",
                          state=()):
        return self._reduce(partials, dev, combine, self._routed), state

    def broadcast_from_masters(self, new_masters, dev, combine: str = "sum",
                               state=()):
        return self._broadcast(new_masters, dev, combine,
                               self._routed), state

    def _routed(self, send):
        """(m, k, H_max) lanes by (local source, destination partition) →
        (m, k, H_max) by (local destination, source partition).  Lanes go
        out in one (D, m, m, H_max) block per destination device; the
        device's own block, the lanes between partitions on the same
        chip, never leaves it."""
        m, k, h = send.shape
        blocks = jnp.swapaxes(send.reshape(m, k // m, m, h), 0, 1)
        got = jax.lax.all_to_all(blocks, self.axis, 0, 0)  # (D, m, m, H)
        return got.transpose(2, 0, 1, 3).reshape(m, k, h)

    # -- stacked halves: all_to_all over k virtual devices == transpose --
    def reduce_stacked(self, partials, dev, combine: str = "sum", state=()):
        return self._reduce(partials, dev, combine, _transposed), state

    def broadcast_stacked(self, masters, dev, combine: str = "sum",
                          state=()):
        return self._broadcast(masters, dev, combine, _transposed), state

    @staticmethod
    def _reduce(partials, dev, combine: str, route):
        l_max = partials.shape[1]
        send = jax.vmap(
            lambda v, idx: _pack(v, idx, combine)
        )(partials, dev["halo_send"])                       # (·, k, H_max)
        recv = route(send)

        def one(recv_q, slots_q, partial_q):
            agg = _segment_combine(recv_q.reshape(-1),
                                   slots_q.reshape(-1),
                                   l_max + 1, combine)[:l_max]
            return _merge(partial_q, agg, combine)

        return jax.vmap(one)(recv, dev["halo_recv"], partials)

    @staticmethod
    def _broadcast(masters, dev, combine: str, route):
        send = jax.vmap(
            lambda v, idx: _pack(v, idx, combine)
        )(masters, dev["halo_recv"])                        # (·, k, H_max)
        recv = route(send)
        return jax.vmap(
            lambda m, r, d: _unpack(m, r, d)
        )(masters, recv, dev)

    # -- multi-lane halves (fused programs; values carry a leading N) --
    def init_state_multi(self, dev, dtype, combine: str, n: int):
        return ()

    def reduce_to_masters_multi(self, partials, dev, combine: str = "sum",
                                state=()):
        l_max = partials.shape[1]
        send = _pack_multi(partials, dev["halo_send"], combine)
        recv = jax.lax.all_to_all(send, self.axis, 0, 0)    # (k, N, H_max)
        agg = _segment_combine_multi(recv, dev["halo_recv"], l_max + 1,
                                     combine)
        return _merge(partials, agg, combine), state

    def broadcast_from_masters_multi(self, new_masters, dev,
                                     combine: str = "sum", state=()):
        send = _pack_multi(new_masters, dev["halo_recv"], combine)
        recv = jax.lax.all_to_all(send, self.axis, 0, 0)    # (k, N, H_max)
        return _unpack_multi(new_masters, recv, dev), state

    def reduce_stacked_multi(self, partials, dev, combine: str = "sum",
                             state=()):
        l_max = partials.shape[2]
        send = jax.vmap(
            lambda v, idx: _pack_multi(v, idx, combine)
        )(partials, dev["halo_send"])                   # (k, k, N, H_max)
        recv = jnp.swapaxes(send, 0, 1)
        agg = jax.vmap(
            lambda r, s: _segment_combine_multi(r, s, l_max + 1, combine)
        )(recv, dev["halo_recv"])
        return _merge(partials, agg, combine), state

    def broadcast_stacked_multi(self, masters, dev, combine: str = "sum",
                                state=()):
        send = jax.vmap(
            lambda v, idx: _pack_multi(v, idx, combine)
        )(masters, dev["halo_recv"])                    # (k, k, N, H_max)
        recv = jnp.swapaxes(send, 0, 1)
        return jax.vmap(
            lambda m, r, d: _unpack_multi(m, r, d)
        )(masters, recv, dev), state

    def bytes_per_iter(self, layout, value_bytes: int = 4) -> int:
        return layout.comm_bytes("halo", value_bytes=value_bytes)


def lossy_payload(combine: str, dtype) -> bool:
    """Whether the quantized backend may delta-code a program's payload:
    only fp sum-combine values tolerate lossy codes — min-combine and
    integer payloads (CC labels) must ship exact.  The one rule the
    exchange, the dry-run byte models, and the CI gate all derive from."""
    return combine == "sum" and jnp.issubdtype(jnp.dtype(dtype),
                                               jnp.floating)


def _ef_encode_fused(lanes, sref, sres):
    """Error-feedback delta encoder for the fused (multi-program) wire:
    int4 codes nibble-packed two-per-byte along the (even) lane axis,
    fp16 scales over ``_NUM_SCALE_GROUPS`` subgroups per (destination,
    program) lane row.  Same lockstep reference/residual algebra as
    ``_ef_encode``; only the code width, scale granularity, and packing
    differ — H/2 + 16 wire bytes per row vs. the separate int8 steps'
    H + 4, the fused driver's < 0.6× byte win."""
    err = lanes - sref + sres
    codes, scales = _quantize_groups(err)
    deq = _dequantize_groups(codes, scales)[..., :err.shape[-1]]
    return sref + deq, err - deq, _nibble_pack(codes), scales


def _ef_decode_fused(packed, scales, n):
    """Unpack + dequantize a fused wire payload back to ``n`` lanes
    (the encoder may have zero-padded the row up to a multiple of 8)."""
    return _dequantize_groups(_nibble_unpack(packed), scales)[..., :n]


def _ef_encode(lanes, sref, sres):
    """Error-feedback delta encoder for one phase's send lanes.

    err = (lanes − sref) + sres is what the receiver is missing plus the
    carried quantization error; it quantizes per lane group, both
    endpoints advance their reference by the identical dequantized step
    (sref ← sref + deq), and the un-sent remainder becomes the next
    iteration's residual — so sref tracks lanes with an unbiased, shrinking
    error as the program converges."""
    err = lanes - sref + sres
    codes, scales = quantize_rows(err)
    deq = dequantize_rows(codes, scales)
    return sref + deq, err - deq, codes, scales


@dataclass(frozen=True)
class QuantizedHaloExchange:
    """Halo routing with an int8 delta-coded payload (error feedback).

    Same static lane tables as ``HaloExchange``; the wire payload per
    phase is (k, H_max) int8 codes + (k,) fp32 per-lane-group scales —
    ~4× fewer bytes than the fp32 halo lanes.  Each endpoint pair keeps a
    reconstruction reference per lane (``sref`` on the sender, ``rref``
    on the receiver) advanced in lockstep by the dequantized delta, and
    the sender carries the quantization error in ``sres`` (error
    feedback), so a converging fixed-point iteration (pagerank) lands on
    the exact fixed point instead of dithering at one quantization step.

    ``combine="min"`` / integer payloads (CC labels) are exact in int32
    already — quantizing would corrupt the min lattice — so those
    programs get the plain halo wire format (``init_state`` returns the
    empty state and every op delegates).
    """
    axis: str | None = None
    name = "quantized"

    @property
    def _exact(self) -> HaloExchange:
        return HaloExchange(axis=self.axis)

    def init_state(self, dev, dtype, combine: str = "sum"):
        if not lossy_payload(combine, dtype):
            return ()
        zeros = jnp.zeros(dev["halo_send"].shape, jnp.float32)
        lane_state = {"sref": zeros, "sres": zeros, "rref": zeros}
        # zeros are the same on every device; one step makes them vary
        return varying({"reduce": lane_state, "bcast": dict(lane_state)},
                       self.axis)

    # -- per-device halves (inside shard_map over ``axis``); the lossy
    # payload routes one partition per device --
    def reduce_to_masters(self, partials, dev, combine: str = "sum",
                          state=()):
        if not state:
            return self._exact.reduce_to_masters(partials, dev, combine,
                                                 state)
        partial, dev, state = _one_partition(self.name, partials, dev, state)
        st = state["reduce"]
        l_max = partial.shape[0]
        lanes = _pack(partial, dev["halo_send"], combine)
        sref, sres, codes, scales = _ef_encode(lanes, st["sref"],
                                               st["sres"])
        rcodes = jax.lax.all_to_all(codes, self.axis, 0, 0)   # int8 wire
        rscales = jax.lax.all_to_all(scales, self.axis, 0, 0)
        rref = st["rref"] + dequantize_rows(rcodes, rscales)
        agg = _segment_combine(rref.reshape(-1),
                               dev["halo_recv"].reshape(-1),
                               l_max + 1, combine)[:l_max]
        total = _merge(partial, agg, combine)
        return _restacked(total, {**state, "reduce": {
            "sref": sref, "sres": sres, "rref": rref}})

    def broadcast_from_masters(self, new_masters, dev, combine: str = "sum",
                               state=()):
        if not state:
            return self._exact.broadcast_from_masters(new_masters, dev,
                                                      combine, state)
        new_master, dev, state = _one_partition(self.name, new_masters, dev,
                                                state)
        st = state["bcast"]
        lanes = _pack(new_master, dev["halo_recv"], combine)
        sref, sres, codes, scales = _ef_encode(lanes, st["sref"],
                                               st["sres"])
        rcodes = jax.lax.all_to_all(codes, self.axis, 0, 0)   # int8 wire
        rscales = jax.lax.all_to_all(scales, self.axis, 0, 0)
        rref = st["rref"] + dequantize_rows(rcodes, rscales)
        values = _unpack(new_master, rref, dev)
        return _restacked(values, {**state, "bcast": {
            "sref": sref, "sres": sres, "rref": rref}})

    # -- stacked halves: all_to_all over k virtual devices == transpose --
    def reduce_stacked(self, partials, dev, combine: str = "sum", state=()):
        if not state:
            return self._exact.reduce_stacked(partials, dev, combine,
                                              state)
        st = state["reduce"]
        l_max = partials.shape[1]
        lanes = jax.vmap(
            lambda v, idx: _pack(v, idx, combine)
        )(partials, dev["halo_send"])                       # (k, k, H_max)
        sref, sres, codes, scales = _ef_encode(lanes, st["sref"],
                                               st["sres"])
        rref = st["rref"] + dequantize_rows(jnp.swapaxes(codes, 0, 1),
                                            jnp.swapaxes(scales, 0, 1))

        def one(rref_q, slots_q, partial_q):
            agg = _segment_combine(rref_q.reshape(-1), slots_q.reshape(-1),
                                   l_max + 1, combine)[:l_max]
            return _merge(partial_q, agg, combine)

        total = jax.vmap(one)(rref, dev["halo_recv"], partials)
        return total, {**state, "reduce": {"sref": sref, "sres": sres,
                                           "rref": rref}}

    def broadcast_stacked(self, masters, dev, combine: str = "sum",
                          state=()):
        if not state:
            return self._exact.broadcast_stacked(masters, dev, combine,
                                                 state)
        st = state["bcast"]
        lanes = jax.vmap(
            lambda v, idx: _pack(v, idx, combine)
        )(masters, dev["halo_recv"])                        # (k, k, H_max)
        sref, sres, codes, scales = _ef_encode(lanes, st["sref"],
                                               st["sres"])
        rref = st["rref"] + dequantize_rows(jnp.swapaxes(codes, 0, 1),
                                            jnp.swapaxes(scales, 0, 1))
        values = jax.vmap(
            lambda m, r, d: _unpack(m, r, d)
        )(masters, rref, dev)
        return values, {**state, "bcast": {"sref": sref, "sres": sres,
                                           "rref": rref}}

    # -- multi-lane halves: the fused wire format (int4 packed codes) --
    def init_state_multi(self, dev, dtype, combine: str, n: int):
        if not lossy_payload(combine, dtype):
            return ()
        # program axis slots in before the lane axis, so the same state
        # pytree serves the per-device ((k, H) tables → (k, N, H) state)
        # and stacked ((k, k, H) → (k, k, N, H)) forms
        shape = dev["halo_send"].shape
        zeros = jnp.zeros((*shape[:-1], n, shape[-1]), jnp.float32)
        lane_state = {"sref": zeros, "sres": zeros, "rref": zeros}
        # zeros are the same on every device; one step makes them vary
        return varying({"reduce": lane_state, "bcast": dict(lane_state)},
                       self.axis)

    def reduce_to_masters_multi(self, partials, dev, combine: str = "sum",
                                state=()):
        if not state:
            return self._exact.reduce_to_masters_multi(partials, dev,
                                                       combine, state)
        st = state["reduce"]
        l_max = partials.shape[1]
        lanes = _pack_multi(partials, dev["halo_send"], combine)
        sref, sres, packed, scales = _ef_encode_fused(lanes, st["sref"],
                                                      st["sres"])
        rpacked = jax.lax.all_to_all(packed, self.axis, 0, 0)  # int4 wire
        rscales = jax.lax.all_to_all(scales, self.axis, 0, 0)
        rref = st["rref"] + _ef_decode_fused(rpacked, rscales,
                                             st["rref"].shape[-1])
        agg = _segment_combine_multi(rref, dev["halo_recv"], l_max + 1,
                                     combine)
        total = _merge(partials, agg, combine)
        return total, {**state, "reduce": {"sref": sref, "sres": sres,
                                           "rref": rref}}

    def broadcast_from_masters_multi(self, new_masters, dev,
                                     combine: str = "sum", state=()):
        if not state:
            return self._exact.broadcast_from_masters_multi(
                new_masters, dev, combine, state)
        st = state["bcast"]
        lanes = _pack_multi(new_masters, dev["halo_recv"], combine)
        sref, sres, packed, scales = _ef_encode_fused(lanes, st["sref"],
                                                      st["sres"])
        rpacked = jax.lax.all_to_all(packed, self.axis, 0, 0)  # int4 wire
        rscales = jax.lax.all_to_all(scales, self.axis, 0, 0)
        rref = st["rref"] + _ef_decode_fused(rpacked, rscales,
                                             st["rref"].shape[-1])
        values = _unpack_multi(new_masters, rref, dev)
        return values, {**state, "bcast": {"sref": sref, "sres": sres,
                                           "rref": rref}}

    def reduce_stacked_multi(self, partials, dev, combine: str = "sum",
                             state=()):
        if not state:
            return self._exact.reduce_stacked_multi(partials, dev,
                                                    combine, state)
        st = state["reduce"]
        l_max = partials.shape[2]
        lanes = jax.vmap(
            lambda v, idx: _pack_multi(v, idx, combine)
        )(partials, dev["halo_send"])                   # (k, k, N, H_max)
        sref, sres, packed, scales = _ef_encode_fused(lanes, st["sref"],
                                                      st["sres"])
        rref = st["rref"] + _ef_decode_fused(jnp.swapaxes(packed, 0, 1),
                                             jnp.swapaxes(scales, 0, 1),
                                             st["rref"].shape[-1])
        agg = jax.vmap(
            lambda r, s: _segment_combine_multi(r, s, l_max + 1, combine)
        )(rref, dev["halo_recv"])
        total = _merge(partials, agg, combine)
        return total, {**state, "reduce": {"sref": sref, "sres": sres,
                                           "rref": rref}}

    def broadcast_stacked_multi(self, masters, dev, combine: str = "sum",
                                state=()):
        if not state:
            return self._exact.broadcast_stacked_multi(masters, dev,
                                                       combine, state)
        st = state["bcast"]
        lanes = jax.vmap(
            lambda v, idx: _pack_multi(v, idx, combine)
        )(masters, dev["halo_recv"])                    # (k, k, N, H_max)
        sref, sres, packed, scales = _ef_encode_fused(lanes, st["sref"],
                                                      st["sres"])
        rref = st["rref"] + _ef_decode_fused(jnp.swapaxes(packed, 0, 1),
                                             jnp.swapaxes(scales, 0, 1),
                                             st["rref"].shape[-1])
        values = jax.vmap(
            lambda m, r, d: _unpack_multi(m, r, d)
        )(masters, rref, dev)
        return values, {**state, "bcast": {"sref": sref, "sres": sres,
                                           "rref": rref}}

    def bytes_per_iter(self, layout, value_bytes: int = 4,
                       combine: str = "sum", dtype=jnp.float32) -> int:
        # exact payloads pass through at full width; the lossy wire
        # format is fixed by quantize_rows: int8 codes + one fp32 scale
        # per lane group, whatever the value dtype was
        return layout.comm_bytes("quantized",
                                 lossy=lossy_payload(combine, dtype),
                                 value_bytes=value_bytes)


# ------------------------------------------------- ragged ring exchanges

def _scatter_last(idx, vals, n):
    """Dense (..., n) array with ``vals`` placed at ``idx`` along the
    last axis (indices within a row are distinct — top_k output)."""
    flat_i = idx.reshape(-1, idx.shape[-1])
    flat_v = vals.reshape(-1, vals.shape[-1])
    out = jax.vmap(
        lambda i, v: jnp.zeros((n,), vals.dtype).at[i].set(v)
    )(flat_i, flat_v)
    return out.reshape(*idx.shape[:-1], n)


def _row(table, i, h):
    """Traced row ``table[i, :h]`` of a (k, H_max) per-device table."""
    return jax.lax.dynamic_index_in_dim(table, i, 0, keepdims=False)[:h]


def _acc_init(shape, dtype, combine: str):
    """Hopwise reduce accumulator init: the same fill ``segment_sum`` /
    ``segment_min`` start their output buffers from, so accumulating
    hop-by-hop reproduces the deferred segment reduce bit-for-bit
    (x + 0 is exact; min against the fill is the identity)."""
    dtype = jnp.dtype(dtype)
    if combine == "sum":
        return jnp.zeros(shape, dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.full(shape, jnp.iinfo(dtype).max, dtype)
    return jnp.full(shape, jnp.inf, dtype)


def _hop_accumulate(acc, slots, recv, combine: str):
    """Fold one hop's received lanes into the running (L_max+1,) master
    accumulator the moment they land.  Valid lanes within a hop target
    distinct master slots (one source partition → distinct vertices);
    pads all target the dropped L_max bucket.  Per slot this applies at
    most one contribution per hop, in hop order — exactly the input
    order the deferred ``_segment_combine`` over the concatenated hops
    reduces in, so the two forms agree bitwise."""
    if combine == "sum":
        return acc.at[slots].add(recv)
    return acc.at[slots].min(recv)


DEFAULT_TOP_DELTA = 0.25


@dataclass(frozen=True)
class RaggedHaloExchange:
    """Mirror-routed sync over k−1 ppermute ring hops, each padded only
    to its own distance's lane population (``schedule`` — the layout's
    ``halo_schedule()``, static so the instance hashes as a jit key).

    Hop s pairs every device p with owner (p+s) mod k; lanes are packed
    at the front of each (p, q) row of the halo tables, so the prefix
    slice [:H_s] covers every real lane at that distance.  Reduce runs
    all hops, then ONE segment-combine over the concatenated received
    lanes; broadcast scatters each hop straight into the mirror slots
    (each mirror receives from exactly one owner on exactly one hop).

    ``hopwise=True`` on the reduce halves folds each hop's lanes into a
    running master accumulator the moment they arrive instead of
    deferring one big segment reduce — bit-identical output
    (``_hop_accumulate``), but every hop's recv is consumable as soon
    as its ppermute lands, which is what lets the overlapped GAS body
    (``engine._gas_body(overlap=True)``) interleave interior compute
    with the ring without lengthening the collective critical path.
    """
    axis: str | None = None
    schedule: tuple = ()
    name = "ragged"

    @property
    def k(self) -> int:
        return len(self.schedule) + 1

    def _hops(self):
        """(distance, H_s) for the populated distances only."""
        return [(s, h) for s, h in enumerate(self.schedule, 1) if h > 0]

    def init_state(self, dev, dtype, combine: str = "sum"):
        return ()

    # -- per-device halves (inside shard_map over ``axis``): one
    # partition per device --
    def reduce_to_masters(self, partials, dev, combine: str = "sum",
                          state=(), *, hopwise: bool = False):
        partial, dev, _ = _one_partition(self.name, partials, dev, ())
        return _restacked(self._reduce_one(partial, dev, combine, hopwise),
                          state)

    def broadcast_from_masters(self, new_masters, dev, combine: str = "sum",
                               state=()):
        new_master, dev, _ = _one_partition(self.name, new_masters, dev, ())
        return _restacked(self._broadcast_one(new_master, dev, combine),
                          state)

    def _reduce_one(self, partial, dev, combine: str, hopwise: bool):
        """Reduce of one partition's (L_max,) partial over the ring."""
        l_max = partial.shape[0]
        k = self.k
        me = jax.lax.axis_index(self.axis)
        if hopwise:
            hops = self._hops()
            if not hops:
                return partial
            acc = _acc_init((l_max + 1,), partial.dtype, combine)
            for s, h in hops:
                send = _pack(partial,
                             _row(dev["halo_send"], (me + s) % k, h),
                             combine)
                recv = jax.lax.ppermute(
                    send, self.axis, [(p, (p + s) % k) for p in range(k)])
                acc = _hop_accumulate(
                    acc, _row(dev["halo_recv"], (me - s) % k, h), recv,
                    combine)
            return _merge(partial, acc[:l_max], combine)
        recvs, slots = [], []
        for s, h in self._hops():
            send = _pack(partial, _row(dev["halo_send"], (me + s) % k, h),
                         combine)
            recv = jax.lax.ppermute(
                send, self.axis, [(p, (p + s) % k) for p in range(k)])
            recvs.append(recv)
            slots.append(_row(dev["halo_recv"], (me - s) % k, h))
        if not recvs:
            return partial
        agg = _segment_combine(jnp.concatenate(recvs),
                               jnp.concatenate(slots),
                               l_max + 1, combine)[:l_max]
        return _merge(partial, agg, combine)

    def _broadcast_one(self, new_master, dev, combine: str):
        """Broadcast of one partition's (L_max,) masters over the ring."""
        l_max = new_master.shape[0]
        k = self.k
        me = jax.lax.axis_index(self.axis)
        scattered = jnp.zeros((l_max + 1,), new_master.dtype)
        for s, h in self._hops():
            # owner q ships to mirror (q−s) mod k — the reverse route of
            # reduce hop s, so the same H_s covers it
            send = _pack(new_master,
                         _row(dev["halo_recv"], (me - s) % k, h), combine)
            recv = jax.lax.ppermute(
                send, self.axis, [(p, (p - s) % k) for p in range(k)])
            wslot = _row(dev["halo_send"], (me + s) % k, h)
            scattered = scattered.at[wslot].set(recv)
        return jnp.where(dev["is_master"], new_master, scattered[:l_max])

    # -- stacked halves: ppermute over k virtual devices == jnp.roll --
    def reduce_stacked(self, partials, dev, combine: str = "sum", state=(),
                       *, hopwise: bool = False):
        l_max = partials.shape[1]
        ar = jnp.arange(self.k)
        if hopwise:
            hops = self._hops()
            if not hops:
                return partials, state
            acc = _acc_init((self.k, l_max + 1), partials.dtype, combine)
            for s, h in hops:
                rows = dev["halo_send"][ar, (ar + s) % self.k, :h]
                send = jax.vmap(
                    lambda v, r: _pack(v, r, combine))(partials, rows)
                recv = jnp.roll(send, s, axis=0)
                wslots = dev["halo_recv"][ar, (ar - s) % self.k, :h]
                acc = jax.vmap(
                    lambda a, sl, r: _hop_accumulate(a, sl, r, combine)
                )(acc, wslots, recv)
            return jax.vmap(
                lambda pq, a: _merge(pq, a[:l_max], combine)
            )(partials, acc), state
        recvs, slots = [], []
        for s, h in self._hops():
            rows = dev["halo_send"][ar, (ar + s) % self.k, :h]
            send = jax.vmap(
                lambda v, r: _pack(v, r, combine))(partials, rows)
            recvs.append(jnp.roll(send, s, axis=0))
            slots.append(dev["halo_recv"][ar, (ar - s) % self.k, :h])
        if not recvs:
            return partials, state
        recv_all = jnp.concatenate(recvs, axis=1)
        slot_all = jnp.concatenate(slots, axis=1)

        def one(r, sl, pq):
            agg = _segment_combine(r, sl, l_max + 1, combine)[:l_max]
            return _merge(pq, agg, combine)

        return jax.vmap(one)(recv_all, slot_all, partials), state

    def broadcast_stacked(self, masters, dev, combine: str = "sum",
                          state=()):
        l_max = masters.shape[1]
        ar = jnp.arange(self.k)
        scattered = jnp.zeros((self.k, l_max + 1), masters.dtype)
        for s, h in self._hops():
            rows = dev["halo_recv"][ar, (ar - s) % self.k, :h]
            send = jax.vmap(
                lambda v, r: _pack(v, r, combine))(masters, rows)
            recv = jnp.roll(send, -s, axis=0)
            wslots = dev["halo_send"][ar, (ar + s) % self.k, :h]
            scattered = jax.vmap(
                lambda a, w, r: a.at[w].set(r))(scattered, wslots, recv)
        return jnp.where(dev["is_master"], masters,
                         scattered[:, :l_max]), state

    # -- multi-lane halves: exact payloads concatenate, so fusing is a
    # static python loop over programs sharing each hop's route --
    def init_state_multi(self, dev, dtype, combine: str, n: int):
        return ()

    def reduce_to_masters_multi(self, partials, dev, combine: str = "sum",
                                state=(), *, hopwise: bool = False):
        outs = [self._reduce_one(p, dev, combine, hopwise)
                for p in partials]
        return jnp.stack(outs), state

    def broadcast_from_masters_multi(self, new_masters, dev,
                                     combine: str = "sum", state=()):
        outs = [self._broadcast_one(m, dev, combine) for m in new_masters]
        return jnp.stack(outs), state

    def reduce_stacked_multi(self, partials, dev, combine: str = "sum",
                             state=(), *, hopwise: bool = False):
        outs = [self.reduce_stacked(p, dev, combine, hopwise=hopwise)[0]
                for p in jnp.moveaxis(partials, 1, 0)]
        return jnp.moveaxis(jnp.stack(outs), 0, 1), state

    def broadcast_stacked_multi(self, masters, dev, combine: str = "sum",
                                state=()):
        outs = [self.broadcast_stacked(m, dev, combine)[0]
                for m in jnp.moveaxis(masters, 1, 0)]
        return jnp.moveaxis(jnp.stack(outs), 0, 1), state

    def bytes_per_iter(self, layout, value_bytes: int = 4) -> int:
        return layout.comm_bytes("ragged", value_bytes=value_bytes)


@dataclass(frozen=True)
class RaggedQuantizedHaloExchange:
    """Ragged ring routing with a top-Δ sparsified error-feedback
    payload: per hop only the T_s = ⌈top_delta·H_s⌉ largest-|Δ| lanes of
    the delta ship, as int16 lane indices + int8 codes + one fp32
    max-abs scale; un-sent lanes simply stay outstanding in the
    reference gap and ship a later iteration once they dominate.
    References advance in lockstep like ``QuantizedHaloExchange``
    (``sref`` on the sender row, ``rref`` on the receiver row), but
    there is deliberately NO carried ``sres`` residual: under top-Δ
    sparsification the outstanding delta (lanes − sref) already *is*
    the residual, and a separate carry would double-count every un-sent
    lane each round (err ← 2·err — exponential divergence; the padded
    encoder tolerates the carry only because it quantizes every lane,
    which makes that recurrence contract).

    Non-lossy programs (min-combine / integer payloads) delegate to the
    exact ``RaggedHaloExchange`` wire, like the padded quantized backend
    does."""
    axis: str | None = None
    schedule: tuple = ()
    top_delta: float = DEFAULT_TOP_DELTA
    name = "ragged_quantized"

    @property
    def k(self) -> int:
        return len(self.schedule) + 1

    @property
    def _exact(self) -> RaggedHaloExchange:
        return RaggedHaloExchange(axis=self.axis, schedule=self.schedule)

    def _hops(self):
        return [(s, h) for s, h in enumerate(self.schedule, 1) if h > 0]

    def _top(self, h: int) -> int:
        return min(h, max(1, math.ceil(self.top_delta * h)))

    def init_state(self, dev, dtype, combine: str = "sum"):
        if not lossy_payload(combine, dtype):
            return ()
        # lead dims: () for the per-device (k, H_max) tables, (k,) for
        # the stacked (k, k, H_max) ones — one state pytree serves both
        lead = dev["halo_send"].shape[:-2]

        def lanes():
            return tuple({"sref": jnp.zeros((*lead, h), jnp.float32),
                          "rref": jnp.zeros((*lead, h), jnp.float32)}
                         for _, h in self._hops())

        return varying({"reduce": lanes(), "bcast": lanes()}, self.axis)

    def _encode(self, lanes, st, h):
        """Top-Δ error-feedback step for one hop: returns the advanced
        sender state and the (idx, codes, scales) wire triplet.  The
        outstanding delta is recomputed from the reference each call —
        quantization error and un-sent lanes both live in (lanes −
        sref) and need no separate carry (see the class docstring)."""
        err = lanes - st["sref"]
        t = self._top(h)
        _, idx = jax.lax.top_k(jnp.abs(err), t)
        vals = jnp.take_along_axis(err, idx, -1)
        codes, scales = quantize_rows(vals)
        deq = _scatter_last(idx, dequantize_rows(codes, scales), h)
        return ({"sref": st["sref"] + deq, "rref": st["rref"]},
                (idx.astype(jnp.int16), codes, scales))

    @staticmethod
    def _decode(ridx, rcodes, rscales, h):
        return _scatter_last(ridx.astype(jnp.int32),
                             dequantize_rows(rcodes, rscales), h)

    # -- per-device halves (inside shard_map over ``axis``): one
    # partition per device --
    def reduce_to_masters(self, partials, dev, combine: str = "sum",
                          state=(), *, hopwise: bool = False):
        if not state:
            return self._exact.reduce_to_masters(partials, dev, combine,
                                                 state, hopwise=hopwise)
        partial, dev, state = _one_partition(self.name, partials, dev, state)
        return _restacked(*self._reduce_one(partial, dev, combine, state,
                                            hopwise))

    def broadcast_from_masters(self, new_masters, dev, combine: str = "sum",
                               state=()):
        if not state:
            return self._exact.broadcast_from_masters(new_masters, dev,
                                                      combine, state)
        new_master, dev, state = _one_partition(self.name, new_masters, dev,
                                                state)
        return _restacked(*self._broadcast_one(new_master, dev, combine,
                                               state))

    def _reduce_one(self, partial, dev, combine: str, state, hopwise: bool):
        """Lossy reduce of one partition's (L_max,) partial."""
        l_max = partial.shape[0]
        k = self.k
        me = jax.lax.axis_index(self.axis)
        acc = _acc_init((l_max + 1,), partial.dtype, combine)
        new_st, rrefs, slots = [], [], []
        for (s, h), st in zip(self._hops(), state["reduce"]):
            lanes = _pack(partial, _row(dev["halo_send"], (me + s) % k, h),
                          combine)
            st, wire = self._encode(lanes, st, h)
            perm = [(p, (p + s) % k) for p in range(k)]
            ridx, rcodes, rscales = (
                jax.lax.ppermute(w, self.axis, perm) for w in wire)
            rref = st["rref"] + self._decode(ridx, rcodes, rscales, h)
            new_st.append({**st, "rref": rref})
            slot = _row(dev["halo_recv"], (me - s) % k, h)
            if hopwise:
                # consume this hop's advanced reference immediately —
                # same per-slot contribution sequence as the deferred
                # segment reduce (see RaggedHaloExchange docstring)
                acc = _hop_accumulate(acc, slot, rref.astype(partial.dtype),
                                      combine)
            else:
                rrefs.append(rref)
                slots.append(slot)
        if not new_st:
            return partial, state
        if hopwise:
            return _merge(partial, acc[:l_max], combine), \
                {**state, "reduce": tuple(new_st)}
        agg = _segment_combine(jnp.concatenate(rrefs),
                               jnp.concatenate(slots),
                               l_max + 1, combine)[:l_max]
        return _merge(partial, agg, combine), \
            {**state, "reduce": tuple(new_st)}

    def _broadcast_one(self, new_master, dev, combine: str, state):
        """Lossy broadcast of one partition's (L_max,) masters."""
        l_max = new_master.shape[0]
        k = self.k
        me = jax.lax.axis_index(self.axis)
        scattered = jnp.zeros((l_max + 1,), new_master.dtype)
        new_st = []
        for (s, h), st in zip(self._hops(), state["bcast"]):
            lanes = _pack(new_master,
                          _row(dev["halo_recv"], (me - s) % k, h), combine)
            st, wire = self._encode(lanes, st, h)
            perm = [(p, (p - s) % k) for p in range(k)]
            ridx, rcodes, rscales = (
                jax.lax.ppermute(w, self.axis, perm) for w in wire)
            rref = st["rref"] + self._decode(ridx, rcodes, rscales, h)
            new_st.append({**st, "rref": rref})
            wslot = _row(dev["halo_send"], (me + s) % k, h)
            scattered = scattered.at[wslot].set(rref)
        values = jnp.where(dev["is_master"], new_master,
                           scattered[:l_max])
        return values, {**state, "bcast": tuple(new_st)}

    # -- stacked halves: ppermute over k virtual devices == jnp.roll --
    def reduce_stacked(self, partials, dev, combine: str = "sum", state=(),
                       *, hopwise: bool = False):
        if not state:
            return self._exact.reduce_stacked(partials, dev, combine,
                                              state, hopwise=hopwise)
        l_max = partials.shape[1]
        ar = jnp.arange(self.k)
        acc = _acc_init((self.k, l_max + 1), partials.dtype, combine)
        new_st, rrefs, slots = [], [], []
        for (s, h), st in zip(self._hops(), state["reduce"]):
            rows = dev["halo_send"][ar, (ar + s) % self.k, :h]
            lanes = jax.vmap(
                lambda v, r: _pack(v, r, combine))(partials, rows)
            st, wire = self._encode(lanes, st, h)
            ridx, rcodes, rscales = (jnp.roll(w, s, axis=0) for w in wire)
            rref = st["rref"] + self._decode(ridx, rcodes, rscales, h)
            new_st.append({**st, "rref": rref})
            wslots = dev["halo_recv"][ar, (ar - s) % self.k, :h]
            if hopwise:
                acc = jax.vmap(
                    lambda a, sl, r: _hop_accumulate(a, sl, r, combine)
                )(acc, wslots, rref.astype(partials.dtype))
            else:
                rrefs.append(rref)
                slots.append(wslots)
        if not new_st:
            return partials, state
        if hopwise:
            return jax.vmap(
                lambda pq, a: _merge(pq, a[:l_max], combine)
            )(partials, acc), {**state, "reduce": tuple(new_st)}
        recv_all = jnp.concatenate(rrefs, axis=1)
        slot_all = jnp.concatenate(slots, axis=1)

        def one(r, sl, pq):
            agg = _segment_combine(r, sl, l_max + 1, combine)[:l_max]
            return _merge(pq, agg, combine)

        return jax.vmap(one)(recv_all, slot_all, partials), \
            {**state, "reduce": tuple(new_st)}

    def broadcast_stacked(self, masters, dev, combine: str = "sum",
                          state=()):
        if not state:
            return self._exact.broadcast_stacked(masters, dev, combine,
                                                 state)
        l_max = masters.shape[1]
        ar = jnp.arange(self.k)
        scattered = jnp.zeros((self.k, l_max + 1), masters.dtype)
        new_st = []
        for (s, h), st in zip(self._hops(), state["bcast"]):
            rows = dev["halo_recv"][ar, (ar - s) % self.k, :h]
            lanes = jax.vmap(
                lambda v, r: _pack(v, r, combine))(masters, rows)
            st, wire = self._encode(lanes, st, h)
            ridx, rcodes, rscales = (jnp.roll(w, -s, axis=0) for w in wire)
            rref = st["rref"] + self._decode(ridx, rcodes, rscales, h)
            new_st.append({**st, "rref": rref})
            wslots = dev["halo_send"][ar, (ar + s) % self.k, :h]
            scattered = jax.vmap(
                lambda a, w, r: a.at[w].set(r))(scattered, wslots, rref)
        values = jnp.where(dev["is_master"], masters,
                           scattered[:, :l_max])
        return values, {**state, "bcast": tuple(new_st)}

    # -- multi-lane halves: per-program states, shared hop routes --
    def init_state_multi(self, dev, dtype, combine: str, n: int):
        if not lossy_payload(combine, dtype):
            return ()
        return tuple(self.init_state(dev, dtype, combine)
                     for _ in range(n))

    def reduce_to_masters_multi(self, partials, dev, combine: str = "sum",
                                state=(), *, hopwise: bool = False):
        if not state:
            return self._exact.reduce_to_masters_multi(
                partials, dev, combine, state, hopwise=hopwise)
        outs, sts = [], []
        for p, st in zip(partials, state):
            o, ns = self._reduce_one(p, dev, combine, st, hopwise)
            outs.append(o)
            sts.append(ns)
        return jnp.stack(outs), tuple(sts)

    def broadcast_from_masters_multi(self, new_masters, dev,
                                     combine: str = "sum", state=()):
        if not state:
            return self._exact.broadcast_from_masters_multi(
                new_masters, dev, combine, state)
        outs, sts = [], []
        for m, st in zip(new_masters, state):
            o, ns = self._broadcast_one(m, dev, combine, st)
            outs.append(o)
            sts.append(ns)
        return jnp.stack(outs), tuple(sts)

    def reduce_stacked_multi(self, partials, dev, combine: str = "sum",
                             state=(), *, hopwise: bool = False):
        if not state:
            return self._exact.reduce_stacked_multi(
                partials, dev, combine, state, hopwise=hopwise)
        outs, sts = [], []
        for p, st in zip(jnp.moveaxis(partials, 1, 0), state):
            o, ns = self.reduce_stacked(p, dev, combine, st,
                                        hopwise=hopwise)
            outs.append(o)
            sts.append(ns)
        return jnp.moveaxis(jnp.stack(outs), 0, 1), tuple(sts)

    def broadcast_stacked_multi(self, masters, dev, combine: str = "sum",
                                state=()):
        if not state:
            return self._exact.broadcast_stacked_multi(masters, dev,
                                                       combine, state)
        outs, sts = [], []
        for m, st in zip(jnp.moveaxis(masters, 1, 0), state):
            o, ns = self.broadcast_stacked(m, dev, combine, st)
            outs.append(o)
            sts.append(ns)
        return jnp.moveaxis(jnp.stack(outs), 0, 1), tuple(sts)

    def bytes_per_iter(self, layout, value_bytes: int = 4,
                       combine: str = "sum", dtype=jnp.float32) -> int:
        return layout.comm_bytes("ragged_quantized",
                                 lossy=lossy_payload(combine, dtype),
                                 top_delta=self.top_delta,
                                 value_bytes=value_bytes)


EXCHANGES = {"dense": DenseExchange, "halo": HaloExchange,
             "quantized": QuantizedHaloExchange,
             "ragged": RaggedHaloExchange,
             "ragged_quantized": RaggedQuantizedHaloExchange}

# the ONE list of valid wire-format names — session / dryrun /
# benchmarks / argparse choices all resolve through this instead of
# re-spelling the five names
EXCHANGE_NAMES = tuple(EXCHANGES)

# the ragged wire formats need the layout's static per-distance schedule
RAGGED_EXCHANGES = ("ragged", "ragged_quantized")


def get_exchange(name: str, layout=None, *, axis: str | None = None,
                 top_delta: float | None = None):
    """Exchange registry: ``name`` ∈ ``EXCHANGE_NAMES``; ``axis`` is the
    mesh axis for the shard_map halves (stacked halves ignore it).  The
    ragged wire formats additionally need ``layout`` — their static
    per-distance lane schedule (``layout.halo_schedule()``) is baked
    into the (hashable) instance so it can key jit caches.
    ``top_delta`` tunes the ragged-quantized sparsification fraction."""
    if name not in EXCHANGES:
        raise ValueError(
            f"unknown exchange {name!r}; expected one of "
            f"{sorted(EXCHANGE_NAMES)}")
    if name in RAGGED_EXCHANGES:
        if layout is None:
            raise ValueError(
                f"exchange {name!r} needs layout= for its static "
                "per-distance lane schedule (layout.halo_schedule())")
        schedule = tuple(int(h) for h in layout.halo_schedule())
        if name == "ragged":
            return RaggedHaloExchange(axis=axis, schedule=schedule)
        return RaggedQuantizedHaloExchange(
            axis=axis, schedule=schedule,
            top_delta=DEFAULT_TOP_DELTA if top_delta is None else top_delta)
    return EXCHANGES[name](axis=axis)
