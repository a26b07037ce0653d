"""IR analyzers: reusable jaxpr / post-SPMD-HLO passes.

The HLO parsers (``collective_bytes`` / ``collective_permute_count``)
moved here from ``repro.launch.dryrun`` — the dry-run gates are now
clients, as is any test that wants to assert on compiled wire traffic.
The jaxpr passes catch whole *classes* of regression the unit tests
only catch instance-by-instance:

- ``retrace_count`` — compile-cache churn (the k-sweep promise is ONE
  trace for any number of k values);
- ``dtype_drift`` — silent same-kind widenings (f32→f64 under x64,
  f16→f32 re-promotion of a quantized wire payload, s32→s64 index
  inflation) that double comm/memory without changing results;
- ``scatter_copy_sites`` — computed-index scatters carried through a
  loop body, the XLA:CPU buffer-copy-per-iteration class that cost
  542 µs/edge before the arithmetic one-hot rewrite (EXPERIMENTS.md
  §Perf-partitioner);
- ``unreduced_divergence`` — shard_map outputs claimed replicated while
  the body computes an axis-varying value that never crossed a
  reduction (the bug ``check_vma=False`` stops catching).

Everything here imports jax lazily-enough to keep ``repro.analysis``
(the lint layer) jax-free.
"""
from __future__ import annotations

import re

import numpy as np

import jax
from jax.extend.core import Literal

# ---------------------------------------------------------------------------
# Post-SPMD HLO text parsers (moved verbatim from launch/dryrun.py)
# ---------------------------------------------------------------------------

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3": 1,
               "f8e5m2": 1, "s16": 2, "u16": 2}


# the opcode, not the instruction name: jax names instructions after the
# op that emitted them (``%ppermute.56 = f32[23] collective-permute(…)``)
OPCODE_RE = re.compile(r"\s(" + "|".join(COLLECTIVE_KINDS)
                       + r")(-start|-done)?\(")


def hlo_collectives(hlo_text: str):
    """(kind, async suffix, output type text) of every collective
    instruction, async ``-done`` halves skipped (a start/done pair is one
    collective, and the done's output repeats the start's)."""
    for line in hlo_text.splitlines():
        _, sep, rest = line.partition("=")
        if not sep:
            continue
        m = OPCODE_RE.search(rest)
        if m is None or m.group(2) == "-done":
            continue
        yield m.group(1), m.group(2), rest[:m.start()]


def collective_bytes(hlo_text: str) -> dict:
    """Per-device output bytes of every collective instruction, by kind.

    Anchored on the opcode right of ``=`` and summing every
    ``dtype[dims]`` in the output type — which may be a tuple:  XLA:CPU
    lowers ``all_to_all`` to ``(f32[1,H], …×k) all-to-all(…)``."""
    out = {}
    for kind, suffix, out_type in hlo_collectives(hlo_text):
        shapes = SHAPE_RE.findall(out_type)
        if suffix == "-start" and len(shapes) > 1:
            # async start tuples are (aliased operand, result, …): the
            # first element is the input, not wire traffic
            shapes = shapes[1:]
        b = 0
        for dt, dims in shapes:
            size = 1
            for d in dims.split(","):
                if d:
                    size *= int(d)
            b += size * DTYPE_BYTES.get(dt, 4)
        out[kind] = out.get(kind, 0) + b
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def collective_permute_count(hlo_text: str) -> int:
    """Number of collective-permute instructions in the post-SPMD HLO,
    a start/done pair counted once.  The overlapped ragged body must
    keep this count identical to the phase-ordered body: overlap
    re-orders compute around the k−1 ring hops, it must never add or
    drop a hop."""
    return sum(kind == "collective-permute"
               for kind, _, _ in hlo_collectives(hlo_text))


# ---------------------------------------------------------------------------
# Retrace detection (generalizes core.partitioner.sweep_trace_count)
# ---------------------------------------------------------------------------

def trace_counter(fn):
    """Wrap ``fn`` so each *trace* (Python execution under jit) bumps a
    counter; compiled-cache hits don't re-enter Python.  Returns
    ``(wrapped, count)`` — jit the wrapped function, drive it, then call
    ``count()``."""
    n = {"traces": 0}

    def wrapped(*args, **kwargs):
        n["traces"] += 1
        return fn(*args, **kwargs)

    return wrapped, (lambda: n["traces"])


def retrace_count(fn, arg_sets, *, jit_kwargs=None) -> int:
    """Trace count of jitted ``fn`` driven over every ``args`` tuple in
    ``arg_sets``.  A shape-stable function must report 1 no matter how
    many call sites hit it — 1-per-call means an arg is leaking into the
    trace key (python scalar k, a weak-typed constant, a non-hashable
    static)."""
    wrapped, count = trace_counter(fn)
    jfn = jax.jit(wrapped, **(jit_kwargs or {}))
    for args in arg_sets:
        out = jfn(*args)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x, out)
    return count()


# ---------------------------------------------------------------------------
# Jaxpr traversal helpers
# ---------------------------------------------------------------------------

def _as_jaxpr(obj):
    """ClosedJaxpr | Jaxpr → Jaxpr."""
    return obj.jaxpr if hasattr(obj, "jaxpr") else obj


def _sub_jaxprs(eqn):
    """Every nested jaxpr hanging off an eqn's params (scan/while/cond
    bodies, pjit/closed_call jaxprs, shard_map bodies, custom_* calls)."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if hasattr(v, "eqns"):              # raw Jaxpr
                yield v
            elif hasattr(v, "jaxpr") and hasattr(_as_jaxpr(v), "eqns"):
                yield _as_jaxpr(v)              # ClosedJaxpr


def iter_eqns(jaxpr, path=()):
    """Depth-first (eqn, path) over a jaxpr and every nested body; the
    path is the chain of enclosing primitive names."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn, path
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, path + (eqn.primitive.name,))


def make_jaxpr(fn, *args, **kwargs):
    """Thin alias so callers don't import jax just for this."""
    return jax.make_jaxpr(fn)(*args, **kwargs)


# ---------------------------------------------------------------------------
# Dtype drift
# ---------------------------------------------------------------------------

def dtype_drift(jaxpr_or_fn, *args, allow=()) -> list[dict]:
    """Same-kind widening conversions anywhere in the jaxpr.

    f32→f64 (x64 leaking in), f16/bf16→f32 (a quantized wire payload
    getting re-promoted before the collective), s32→s64 (index
    inflation) — each doubles bytes silently.  *Kind changes* are not
    drift: u8→f32 is deliberate dequantization, f32→s32 is a cast.
    ``allow`` is an iterable of ``("float16", "float32")``-style name
    pairs to exempt."""
    jaxpr = (jaxpr_or_fn if hasattr(jaxpr_or_fn, "eqns")
             or hasattr(jaxpr_or_fn, "jaxpr")
             else jax.make_jaxpr(jaxpr_or_fn)(*args))
    allowed = {(str(a), str(b)) for a, b in allow}
    sites = []
    for eqn, path in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        old = np.dtype(eqn.invars[0].aval.dtype)
        new = np.dtype(eqn.params["new_dtype"])
        if old.kind == new.kind and new.itemsize > old.itemsize \
                and (old.name, new.name) not in allowed:
            sites.append({
                "old": old.name, "new": new.name,
                "shape": tuple(eqn.invars[0].aval.shape),
                "path": "/".join(path) or "<top>",
            })
    return sites


# ---------------------------------------------------------------------------
# Loop-carried computed-index scatters
# ---------------------------------------------------------------------------

LOOP_PRIMITIVES = frozenset({"scan", "while", "while_loop", "fori_loop"})
SCATTER_PRIMITIVES = frozenset({
    "scatter", "scatter-add", "scatter_add", "scatter_mul", "scatter_min",
    "scatter_max",
})


def scatter_copy_sites(jaxpr_or_fn, *args) -> list[dict]:
    """Computed-index scatters inside loop bodies.

    XLA:CPU can't fuse a scatter whose indices are data-dependent when
    it sits in a loop-carried position: each iteration pays a buffer
    copy plus a scatter kernel call.  The transform pass paid
    542 µs/edge to exactly this before the arithmetic one-hot rewrite
    got it to 9.9 µs/edge — a ``jnp.where(arange(k) == p, …)`` select
    is the fix, not an allowlist entry.

    "Computed" means the index *dataflows from a loop-varying input*
    (the scan carry/xs, the while carry) — a static offset reaches the
    scatter through consts/literals only and each iteration hits the
    same slot, which XLA handles as a dynamic-update-slice."""
    jaxpr = (jaxpr_or_fn if hasattr(jaxpr_or_fn, "eqns")
             or hasattr(jaxpr_or_fn, "jaxpr")
             else jax.make_jaxpr(jaxpr_or_fn)(*args))
    sites = []

    def loop_varying_seed(jaxpr, eqn_name, params):
        if eqn_name == "scan":
            # invars = [consts…, carry…, xs…]; consts are loop-invariant
            return set(jaxpr.invars[params.get("num_consts", 0):])
        return set(jaxpr.invars)

    def visit(jaxpr, path, dyn):
        jaxpr = _as_jaxpr(jaxpr)
        dyn = set(dyn)
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            in_dyn = any(not isinstance(v, Literal) and v in dyn
                         for v in eqn.invars)
            in_loop = any(p in LOOP_PRIMITIVES for p in path)
            if in_loop and len(eqn.invars) > 1 and any(
                    name.startswith(p) for p in SCATTER_PRIMITIVES):
                idx = eqn.invars[1]
                if not isinstance(idx, Literal) and idx in dyn:
                    sites.append({
                        "primitive": name,
                        "operand_shape": tuple(eqn.invars[0].aval.shape),
                        "path": "/".join(path),
                    })
            if in_dyn:
                dyn.update(eqn.outvars)
            for sub in _sub_jaxprs(eqn):
                sub_j = _as_jaxpr(sub)
                if name in LOOP_PRIMITIVES:
                    seed = loop_varying_seed(sub_j, name, eqn.params)
                else:
                    # non-loop body (cond branch, pjit): inherit the
                    # caller's dynamicity positionally when shapes line
                    # up, else stay conservative and taint everything
                    ins = eqn.invars[-len(sub_j.invars):] \
                        if len(sub_j.invars) <= len(eqn.invars) else None
                    seed = ({bv for bv, ov in zip(sub_j.invars, ins)
                             if not isinstance(ov, Literal)
                             and ov in dyn}
                            if ins is not None else set(sub_j.invars))
                visit(sub_j, path + (name,), seed)

    visit(jaxpr, (), set())
    return sites


# ---------------------------------------------------------------------------
# Unreduced divergence across shard_map outputs
# ---------------------------------------------------------------------------

# collectives that *clear* per-device variance over the reduced axis …
REDUCING_PRIMITIVES = frozenset({"psum", "pmax", "pmin", "pmean",
                                 "all_gather", "all_gather_invariant"})
# … and ones that keep values device-varying even though they communicate
VARIANCE_PRESERVING = frozenset({"ppermute", "all_to_all", "pshuffle"})


def _eqn_axes(eqn):
    axes = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return set(axes)


def _spec_axes(spec) -> tuple:
    """The mesh axes a shard_map ``PartitionSpec`` shards over (empty =
    replicated)."""
    axes = []
    for entry in spec:
        if entry is not None:
            axes.extend(entry if isinstance(entry, tuple) else (entry,))
    return tuple(axes)


def _body_divergence(inner, in_axes, out_axes, mesh_axes):
    varying: set = set()

    def is_varying(atom):
        return not isinstance(atom, Literal) and atom in varying

    for var, axes in zip(inner.invars, in_axes):
        if axes:               # sharded input: per-device slice differs
            varying.add(var)
    for eqn in inner.eqns:
        name = eqn.primitive.name
        if name == "axis_index":
            out_varying = True
        elif name in REDUCING_PRIMITIVES:
            axes = _eqn_axes(eqn)
            # reducing over the mesh axis clears variance; reducing some
            # *other* axis (vmapped name) does not
            out_varying = (any(is_varying(v) for v in eqn.invars)
                           and not (axes & mesh_axes or not axes))
        elif name in VARIANCE_PRESERVING:
            out_varying = any(is_varying(v) for v in eqn.invars)
        else:
            # default (including nested scan/cond bodies, conservatively):
            # any varying input makes every output varying
            out_varying = any(is_varying(v) for v in eqn.invars)
        if out_varying:
            varying.update(eqn.outvars)
    out = []
    for i, (var, axes) in enumerate(zip(inner.outvars, out_axes)):
        if not axes and is_varying(var):
            out.append(i)
    return out


def unreduced_divergence(jaxpr_or_fn, *args) -> list[dict]:
    """shard_map outputs declared replicated (empty out_specs) whose
    value is axis-varying and never crossed a reduction.

    This is the divergence class ``check_vma=False`` (which the sharded
    partitioner runs with) stops catching at runtime: every device returns a
    *different* array through an out_spec that promises they're all the
    same, and downstream code silently reads device 0's copy.  Returns
    one record per diverging output with the shard_map's position path.
    """
    jaxpr = (jaxpr_or_fn if hasattr(jaxpr_or_fn, "eqns")
             or hasattr(jaxpr_or_fn, "jaxpr")
             else jax.make_jaxpr(jaxpr_or_fn)(*args))
    findings = []
    for eqn, path in iter_eqns(jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        inner = _as_jaxpr(eqn.params["jaxpr"])
        mesh = eqn.params.get("mesh")
        mesh_axes = set(getattr(mesh, "axis_names", ()) or ())
        in_axes = [_spec_axes(s) for s in eqn.params["in_specs"]]
        out_axes = [_spec_axes(s) for s in eqn.params["out_specs"]]
        for i in _body_divergence(inner, in_axes, out_axes, mesh_axes):
            findings.append({
                "output": i,
                "aval": str(inner.outvars[i].aval),
                "path": "/".join(path) or "<top>",
            })
    return findings
