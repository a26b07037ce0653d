import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Graph dry-run: compile one GAS step per (program × exchange wire) on
a k-device mesh of virtual CPU devices, parse the collective bytes out
of the post-SPMD HLO, and gate their ordering (``--check``).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --graph \
      --graph-scale 13 --graph-k 8 --check
"""
import argparse
import json
import sys
import time
import traceback
import warnings
from pathlib import Path

from repro.dist.halo import EXCHANGE_NAMES
from repro.launch.compile_cache import enable_compile_cache

RESULTS = Path(__file__).resolve().parents[3] / "results"

# The HLO collective parsers live in repro.analysis.ir — the names
# below are deprecation shims so external `dryrun.collective_bytes`
# callers keep working; in-file call sites use the ir implementations.
from repro.analysis.ir import (COLLECTIVE_KINDS, DTYPE_BYTES,  # noqa: F401
                               SHAPE_RE)
from repro.analysis.ir import collective_bytes as _collective_bytes
from repro.analysis.ir import \
    collective_permute_count as _collective_permute_count


def collective_bytes(hlo_text: str) -> dict:
    """Deprecated shim — use ``repro.analysis.ir.collective_bytes``."""
    warnings.warn(
        "repro.launch.dryrun.collective_bytes moved to "
        "repro.analysis.ir.collective_bytes", DeprecationWarning,
        stacklevel=2)
    return _collective_bytes(hlo_text)


def collective_permute_count(hlo_text: str) -> int:
    """Deprecated shim — use
    ``repro.analysis.ir.collective_permute_count``."""
    warnings.warn(
        "repro.launch.dryrun.collective_permute_count moved to "
        "repro.analysis.ir.collective_permute_count", DeprecationWarning,
        stacklevel=2)
    return _collective_permute_count(hlo_text)


# every engine wire format, straight from the exchange registry —
# dryrun stopped re-spelling the list
GRAPH_EXCHANGES = EXCHANGE_NAMES

# the padded all_to_all backends count a self lane in their HLO output
# shape that never crosses the wire; the ragged ppermute ring has no
# self hop, so its HLO bytes ARE the wire bytes
SELF_LANE_EXCHANGES = ("halo", "quantized")
# the fused-vs-separate CI gate compiles this homogeneous (f32, sum)
# bundle as ONE fused step and compares its wire bytes against the sum
# of the three separate quantized steps (threshold FUSED_GATE_RATIO)
FUSED_BUNDLE = ("pagerank", "ppr", "centrality")
FUSED_GATE_RATIO = 0.6
# the overlapped ragged body re-orders interior compute around the k−1
# ppermute ring hops (per-hop partial combine).  CI compiles these cells
# with overlap=True and requires wire bytes AND collective-permute count
# identical to the phase-ordered cell: overlap hides hop latency, it
# must never add, drop, or grow a hop.
OVERLAP_CELLS = (("pagerank", "ragged"), ("sssp", "ragged"),
                 ("pagerank", "ragged_quantized"))
# the early-exit cell EXECUTES pagerank under tol on the bench graph and
# gates iters_run strictly under the cap, with the tol run's values
# bit-identical to a fixed-iters run at the reported iters_run
EARLY_EXIT_TOL = 1e-6
EARLY_EXIT_CAP = 60


def _graph_comm_model(lay, exchange: str, lossy: bool) -> int:
    """The layout's modelled bytes/iter for one (program, backend) cell.
    ``lossy`` is ``halo.lossy_payload(program.combine, program.dtype)`` —
    min/int programs (CC labels) ship the exact full-width payload on
    the quantized backends, so their model is the exact-wire volume."""
    return lay.comm_bytes(exchange, lossy=lossy)


def run_graph_cell(out_dir: Path, scale: int = 10, k: int = 8,
                   iters: int = 1, tag: str = "") -> list[dict]:
    """GAS-engine dry-run: lower one GAS step per (program × exchange
    backend) on a k-device mesh — the full ``repro.graph`` program
    library (pagerank/cc/labelprop/sssp/bfs/degree/centrality/ppr)
    across dense / halo / quantized — and parse the measured collective
    bytes out of the post-SPMD HLO, next to the layout's modelled
    volumes.  A final fused cell compiles the ``FUSED_BUNDLE`` programs
    as ONE multi-program step (single exchange per phase, int4 fused
    wire) so ``check_graph_ordering`` can gate fused < 0.6 × Σ separate.
    One JSON record per cell; the full table also lands in
    ``results/BENCH_dryrun.json`` (the CI ``graph-dryrun`` job's
    artifact and regression gate).

    HLO bytes are per-device; ×k (minus the all_to_all self lane, which
    never crosses the wire) gives the fleet wire volume comparable to
    the ``PartitionLayout.comm_bytes(exchange)`` models and the
    ``comm_bytes("ideal")`` lower bound.

    The whole partition → layout → GAS-cell chain is driven through the
    ``GraphSession`` façade — this function only owns the HLO parsing and
    the record bookkeeping.
    """
    from repro.core import CLUGPConfig, web_graph
    from repro.dist.halo import lossy_payload
    from repro.graph import PROGRAM_NAMES
    from repro.launch.mesh import make_graph_mesh
    from repro.session import GraphSession, SessionConfig, resolve_program

    g = web_graph(scale=scale, edge_factor=8, seed=0)
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig.optimized(k)))
    sess.partition(g.src, g.dst, g.num_vertices).layout()
    lay = sess.partition_layout
    mesh = make_graph_mesh(k)
    base = {"bench": "graph_dryrun", "k": k, "scale": scale,
            "iters": iters, "num_vertices": g.num_vertices,
            "num_edges": g.num_edges, "l_max": lay.l_max,
            "h_max": lay.h_max, "mirrors": lay.mirrors_total,
            "comm_bytes_ideal": lay.comm_bytes("ideal")}

    def compile_cell(rec, step_arg, exchange, overlap=False):
        t0 = time.time()
        try:
            jitted, args = sess.dryrun_step(step_arg, mesh=mesh,
                                            iters=iters,
                                            exchange=exchange,
                                            overlap=overlap)
            compiled = jitted.lower(*args).compile()
            hlo = compiled.as_text()
            coll = _collective_bytes(hlo)
            total = coll["total"] * k
            # collectives sit once in the fori_loop body, so the HLO
            # count (and the self-lane correction) is per iteration
            # whatever ``iters`` is.  The all_to_all self lane (counted
            # by the HLO output shape, never on the wire) carries one
            # lane group's payload: model / (2 phases × k·(k−1) groups)
            # — which generalizes to the fused cell's N-program rows.
            # The ragged ppermute ring has no self hop (distances run
            # 1..k−1), and dense all_gathers none either: correction 0.
            self_lane = (rec["comm_bytes_model"] // (2 * k * (k - 1))
                         if exchange in SELF_LANE_EXCHANGES else 0)
            wire = total - 2 * k * self_lane
            rec.update({
                "status": "ok",
                "compile_s": round(time.time() - t0, 1),
                "collective_bytes_per_device": coll,
                "collective_bytes_total": total,
                "collective_bytes_wire": wire,
                "collective_permute_count": _collective_permute_count(hlo),
            })
            ov = " × overlap" if overlap else ""
            print(f"[graph × {rec['program']} × {exchange}{ov}] OK  "
                  f"hlo={wire:.3e}B/iter (fleet wire)  "
                  f"model={rec['comm_bytes_model']:.3e}B  "
                  f"ideal={rec['comm_bytes_ideal']:.3e}B")
        except Exception as e:  # noqa: BLE001
            rec["status"] = f"FAIL: {type(e).__name__}: {e}"
            rec["traceback"] = traceback.format_exc()[-2000:]
            print(f"[graph × {rec['program']} × {exchange}] FAIL: {e}",
                  file=sys.stderr)
        return rec

    recs = []
    for pname in PROGRAM_NAMES:
        prog = resolve_program(pname, g.num_vertices)
        lossy = lossy_payload(prog.combine, prog.dtype)
        for exchange in GRAPH_EXCHANGES:
            rec = {**base, "program": pname, "exchange": exchange,
                   "fused": False, "overlap": False,
                   "lossy_payload": lossy,
                   "comm_bytes_model": _graph_comm_model(lay, exchange,
                                                         lossy)}
            recs.append(compile_cell(rec, pname, exchange))
        ok = {r["exchange"]: r for r in recs
              if r["program"] == pname and r.get("status") == "ok"}
        if len(ok) == len(GRAPH_EXCHANGES):
            d = ok["dense"]["collective_bytes_wire"]
            h = ok["halo"]["collective_bytes_wire"]
            q = ok["quantized"]["collective_bytes_wire"]
            rg = ok["ragged"]["collective_bytes_wire"]
            rq = ok["ragged_quantized"]["collective_bytes_wire"]
            print(f"  {pname}: dense→halo {h / max(d, 1):.3f}×  "
                  f"halo→quantized {q / max(h, 1):.3f}×  "
                  f"halo→ragged {rg / max(h, 1):.3f}×  "
                  f"quantized→ragged_q {rq / max(q, 1):.3f}×  "
                  f"(ideal/dense = "
                  f"{ok['dense']['comm_bytes_ideal'] / max(d, 1):.3f})")

    # the fused cell: FUSED_BUNDLE as ONE multi-program quantized step
    bundle = [resolve_program(p, g.num_vertices) for p in FUSED_BUNDLE]
    lossy = lossy_payload(bundle[0].combine, bundle[0].dtype)
    rec = {**base, "program": "+".join(FUSED_BUNDLE),
           "exchange": "quantized", "fused": True, "overlap": False,
           "fused_programs": list(FUSED_BUNDLE), "lossy_payload": lossy,
           "comm_bytes_model": lay.comm_bytes(
               "quantized", programs=len(bundle), fused=True, lossy=lossy)}
    rec = compile_cell(rec, list(FUSED_BUNDLE), "quantized")
    recs.append(rec)
    sep = [r for r in recs
           if r["program"] in FUSED_BUNDLE and r["exchange"] == "quantized"
           and r.get("status") == "ok"]
    if rec.get("status") == "ok" and len(sep) == len(FUSED_BUNDLE):
        total_sep = sum(r["collective_bytes_wire"] for r in sep)
        print(f"  fused {rec['program']}: "
              f"{rec['collective_bytes_wire']:.3e}B vs separate "
              f"{total_sep:.3e}B → "
              f"{rec['collective_bytes_wire'] / max(total_sep, 1):.3f}× "
              f"(gate < {FUSED_GATE_RATIO})")

    # overlapped ragged cells: interior compute interleaved with the
    # ring hops — same traffic, same hop count, by construction and gate
    for pname, exchange in OVERLAP_CELLS:
        prog = resolve_program(pname, g.num_vertices)
        lossy = lossy_payload(prog.combine, prog.dtype)
        rec = {**base, "program": pname, "exchange": exchange,
               "fused": False, "overlap": True, "lossy_payload": lossy,
               "comm_bytes_model": _graph_comm_model(lay, exchange,
                                                     lossy)}
        recs.append(compile_cell(rec, pname, exchange, overlap=True))

    # early-exit executed cell: pagerank under tol, then a fixed-iters
    # rerun at the reported iters_run — must be bit-identical
    import numpy as np
    try:
        t0 = time.time()
        v_tol, iters_run = sess.run(
            "pagerank", iters=EARLY_EXIT_CAP, exchange="ragged",
            tol=EARLY_EXIT_TOL, return_iters=True)
        v_fix = sess.run("pagerank", iters=int(iters_run),
                         exchange="ragged")
        rec = {**base, "program": "pagerank", "exchange": "ragged",
               "fused": False, "overlap": False, "tol": EARLY_EXIT_TOL,
               "iters_cap": EARLY_EXIT_CAP, "iters_run": int(iters_run),
               "early_exit_bitmatch":
                   bool(np.array_equal(np.asarray(v_tol),
                                       np.asarray(v_fix))),
               "status": "ok",
               "compile_s": round(time.time() - t0, 1)}
        print(f"[graph × pagerank × ragged × tol={EARLY_EXIT_TOL}] OK  "
              f"iters_run={rec['iters_run']}/{EARLY_EXIT_CAP}  "
              f"bitmatch={rec['early_exit_bitmatch']}")
    except Exception as e:  # noqa: BLE001
        rec = {**base, "program": "pagerank", "exchange": "ragged",
               "fused": False, "overlap": False, "tol": EARLY_EXIT_TOL,
               "iters_cap": EARLY_EXIT_CAP,
               "status": f"FAIL: {type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
        print(f"[graph × pagerank × ragged × tol] FAIL: {e}",
              file=sys.stderr)
    recs.append(rec)
    out_dir.mkdir(parents=True, exist_ok=True)
    fname = out_dir / (f"graph__gas__k{k}"
                       f"{('__' + tag) if tag else ''}.json")
    fname.write_text(json.dumps(recs, indent=1))
    bench_rows = [{kk: v for kk, v in r.items() if kk != "traceback"}
                  for r in recs]
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "BENCH_dryrun.json").write_text(
        json.dumps(bench_rows, indent=1))
    return recs


def check_graph_ordering(recs: list[dict]) -> list[str]:
    """The CI regression gate on the paper's headline quantity: **per
    program**, measured wire bytes/iter must order quantized < halo <
    dense, and the ragged ring must never ship more than its padded
    counterpart: ragged ≤ halo (equality only when every distance's lane
    count is already H_max) and, for lossy payloads, ragged_quantized <
    quantized.  Programs whose quantized cells ship an exact payload
    (min/int — the record's ``lossy_payload`` flag, derived from the
    program spec) allow quantized == halo and require ragged_quantized ==
    ragged (the non-lossy ragged_quantized path delegates to the exact
    ring).  ragged_quantized vs ragged is deliberately NOT gated: at tiny
    per-hop lane counts the index+scale overhead (3·T+4 vs 4·H bytes)
    can exceed the exact payload.  Fused rows (``fused: true``) are
    excluded from the per-program ordering and instead gate the fused
    win: the fused step's wire bytes must be < ``FUSED_GATE_RATIO`` × the
    sum of its bundle programs' separate quantized steps.  Overlap rows
    (``overlap: true``) gate the interleaved ragged body: wire bytes and
    collective-permute count must equal the phase-ordered cell exactly.
    Early-exit rows (``tol`` set) gate ``iters_run`` strictly under the
    cap with the tol run bit-identical to a fixed-iters run at
    ``iters_run``.  Returns the list of violations (empty == pass)."""
    msgs = [f"{r.get('program', '?')}/{r.get('exchange', '?')}: "
            f"{r.get('status')}"
            for r in recs if r.get("status") != "ok"]
    by = {(r["program"], r["exchange"]): r
          for r in recs if r.get("status") == "ok" and not r.get("fused")
          and not r.get("overlap") and r.get("tol") is None}
    for prog in sorted({p for p, _ in by}):
        cells = {e: by.get((prog, e)) for e in GRAPH_EXCHANGES}
        if any(c is None for c in cells.values()):
            continue    # the missing cell is already reported above
        wire = {e: c["collective_bytes_wire"] for e, c in cells.items()}
        d, h, q = wire["dense"], wire["halo"], wire["quantized"]
        rg, rq = wire["ragged"], wire["ragged_quantized"]
        if h >= d:
            msgs.append(f"{prog}: halo bytes/iter {h} ≥ dense {d}")
        if rg > h:
            msgs.append(f"{prog}: ragged bytes/iter {rg} > halo {h}")
        if cells["quantized"].get("lossy_payload", True):
            if q >= h:
                msgs.append(f"{prog}: quantized bytes/iter {q} ≥ halo {h}")
            if rq >= q:
                msgs.append(f"{prog}: ragged_quantized bytes/iter {rq} "
                            f"≥ quantized {q}")
        else:
            if q > h:
                msgs.append(f"{prog}: quantized bytes/iter {q} > halo {h}")
            if rq != rg:
                msgs.append(f"{prog}: exact-payload ragged_quantized "
                            f"bytes/iter {rq} != ragged {rg}")
    for r in recs:
        if not r.get("fused") or r.get("status") != "ok":
            continue
        bundle = r.get("fused_programs") or r["program"].split("+")
        sep = [by.get((p, "quantized")) for p in bundle]
        if None in sep:
            missing = [p for p, c in zip(bundle, sep) if c is None]
            msgs.append(f"{r['program']}: fused gate needs separate "
                        f"quantized cells for {missing}")
            continue
        total_sep = sum(c["collective_bytes_wire"] for c in sep)
        fused_wire = r["collective_bytes_wire"]
        if fused_wire >= FUSED_GATE_RATIO * total_sep:
            msgs.append(
                f"{r['program']}: fused bytes/iter {fused_wire} ≥ "
                f"{FUSED_GATE_RATIO} × Σ separate ({total_sep})")
    # overlap gate: the interleaved body is a pure re-ordering — wire
    # bytes and collective-permute count must equal the phase-ordered
    # cell exactly
    for r in recs:
        if not r.get("overlap") or r.get("status") != "ok":
            continue
        ref = by.get((r["program"], r["exchange"]))
        if ref is None:
            msgs.append(f"{r['program']}/{r['exchange']}: overlap gate "
                        f"needs the phase-ordered cell")
            continue
        if r["collective_bytes_wire"] != ref["collective_bytes_wire"]:
            msgs.append(
                f"{r['program']}/{r['exchange']}: overlapped bytes/iter "
                f"{r['collective_bytes_wire']} != phase-ordered "
                f"{ref['collective_bytes_wire']}")
        if (r.get("collective_permute_count")
                != ref.get("collective_permute_count")):
            msgs.append(
                f"{r['program']}/{r['exchange']}: overlapped "
                f"collective-permute count "
                f"{r.get('collective_permute_count')} != phase-ordered "
                f"{ref.get('collective_permute_count')}")
    # early-exit gate: tol must stop strictly before the cap, and the
    # tol run must be bit-identical to a fixed run at iters_run
    for r in recs:
        if (r.get("tol") is None or r.get("fused")
                or r.get("status") != "ok"):
            continue
        if not r["iters_run"] < r["iters_cap"]:
            msgs.append(
                f"{r['program']}/{r['exchange']}: tol={r['tol']} ran "
                f"iters_run={r['iters_run']} — not strictly under the "
                f"cap {r['iters_cap']}")
        if not r.get("early_exit_bitmatch"):
            msgs.append(
                f"{r['program']}/{r['exchange']}: tol run not "
                f"bit-identical to fixed-iters run at "
                f"iters_run={r.get('iters_run')}")
    return msgs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", action="store_true", required=True,
                    help="GAS-engine cells: compile one step per (program "
                         "× exchange backend) for the full program "
                         "library plus the fused 3-program bundle, report "
                         "measured collective bytes vs the layout's "
                         "modelled volumes, and write "
                         "results/BENCH_dryrun.json")
    ap.add_argument("--graph-scale", type=int, default=10)
    ap.add_argument("--graph-k", type=int, default=8)
    ap.add_argument("--check", action="store_true",
                    help="with --graph: exit 1 unless measured wire bytes "
                         "order quantized < halo < dense per program "
                         "(exact int payloads allow quantized == halo), "
                         "ragged ≤ halo and ragged_quantized < quantized "
                         "(== ragged for exact payloads), the fused "
                         "bundle ships < 0.6× the bytes of its separate "
                         "quantized steps, the overlapped ragged cells "
                         "match their phase-ordered twins in bytes and "
                         "collective-permute count, and the tol cell "
                         "early-exits under its cap bit-identically")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=str(RESULTS / "dryrun"))
    args = ap.parse_args()
    enable_compile_cache()

    recs = run_graph_cell(Path(args.out), scale=args.graph_scale,
                          k=args.graph_k, tag=args.tag)
    if args.check:
        msgs = check_graph_ordering(recs)
        for m in msgs:
            print(f"collective-bytes gate: {m}", file=sys.stderr)
        if not msgs:
            print("collective-bytes gate: quantized < halo < dense, "
                  "ragged ≤ halo and ragged_quantized < quantized "
                  "hold for every program, the fused bundle "
                  f"ships < {FUSED_GATE_RATIO}× its separate steps, "
                  "overlap cells match phase-ordered bytes and "
                  "collective-permute count, and tol early-exits "
                  "under the cap bit-identically")
        sys.exit(1 if msgs else 0)
    n_fail = sum(str(r.get("status", "")).startswith("FAIL") for r in recs)
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
