"""Whole runs of the benchmark's cells on the CPU at tiny sizes, past the
look for a chip: sound runs come out correct, and each fault a cell can
have, planted in the program underneath, comes out not correct; so does
the control, the plain reference computed in bfloat16 in the program's
place.  The ``queries`` loop, which no cell drives yet, runs here on a
cell of the tests' own.  And ``bench/run.py`` itself refuses to run
without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

import run as bench_run  # noqa: E402
from harness import reference, registry  # noqa: E402
from harness.record import Recorder  # noqa: E402

TINY = ["config.graph.num_vertices=1024", "config.graph.num_edges=14000",
        "config.partition.k=4"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# a served web-uk2002 under live edges, at the tiny size
SERVE_CELL = {"name": "serve-tiny", "config": "web-uk2002",
              "traffic": "serve-tiny", "chips": 1}
SERVE_TRAFFIC = {"loop": "queries", "query_rate": 60, "popularity_zipf": 1.1,
                 "mix": {"score:pagerank": 0.15, "score:cc": 0.15,
                         "score:labelprop": 0.15, "score:degree": 0.15,
                         "neighbors": 0.2, "owner": 0.2},
                 "edge_rate": 300, "warmup_edges": 256}
SERVE_SETTINGS = {"window": 256, "tol": 1e-6, "max_iters": 100,
                  "max_batch": 64, "rf_watermark": 1.05, "restream_passes": 2}
SERVE_LIMITS = {"pagerank_gap": 3e-4}


def run_tiny(cell: str, seed: int, seconds: float = 0.6):
    bench = registry.spec(ROOT)
    if cell == "serve-tiny":
        w, traffic = dict(SERVE_CELL), json.loads(json.dumps(SERVE_TRAFFIC))
        limits = dict(SERVE_LIMITS)
    else:
        w = registry.workload(bench, cell)
        traffic = registry.traffic(w["traffic"])
        limits = registry.measured_limits(cell)
    config = registry.config(bench, w["config"], ROOT)
    if cell == "serve-tiny":
        config["serve"] = dict(SERVE_SETTINGS)
    bench_run.apply_overrides(config, traffic, TINY)
    ctx = bench_run.Ctx(cell=w, config=config, traffic=traffic, seed=seed,
                        seconds=seconds, limits=limits, rec=Recorder(),
                        peaks=V5E)
    jax.clear_caches()
    try:
        line = bench_run.run_cell(ctx, jax.devices()[:w["chips"]], False)
    finally:
        jax.clear_caches()
    return line


def test_batch_run_is_correct_and_reports_its_metrics():
    line = run_tiny("web-batch", 2**31 + 3)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"setup_s", "job_s"}
    assert list(line)[-1] == "checks"
    assert line["device"]["count"] == len(jax.devices())


def test_serve_run_is_correct_and_reports_its_metrics():
    line = run_tiny("serve-tiny", 2**31 + 5, seconds=2.0)
    assert line["correct"], line["checks"]
    # no metric of BENCHMARK.json but the set-up time names this cell
    assert set(line["metrics"]) == {"setup_s"}
    assert line["attempted"] > 100
    assert line["checks"]["pagerank_residual"]["limit"] == 1e-6
    assert line["checks"]["label_residual"]["limit"] == 0


# --------------------------------------------------------------- faults

def _unchanged_state(monkeypatch):
    """Every GAS iteration hands back the state it was given."""
    from repro.graph import engine
    for body in ("_gas_body", "_gas_body_multi"):
        monkeypatch.setattr(engine, body,
                            lambda *a, **k: (lambda i, carry: carry))


def _half_the_edges(monkeypatch):
    """The layout holds only the first half of the edge stream."""
    from repro import session
    real = session.build_layout

    def half(src, dst, assign, n, k, pad):
        h = len(src) // 2
        return real(src[:h], dst[:h], assign[:h], n, k, pad)
    monkeypatch.setattr(session, "build_layout", half)


def _no_exchange(monkeypatch):
    """Mirrors never reach their masters: the exchange is left out."""
    from repro.dist import halo
    monkeypatch.setattr(halo.HaloExchange, "reduce_stacked",
                        lambda self, partials, dev, combine="sum",
                        state=(): (partials, state))
    for name in ("reduce_stacked_multi", "reduce_to_masters",
                 "reduce_to_masters_multi"):
        monkeypatch.setattr(halo.HaloExchange, name,
                            lambda self, partial, dev, combine="sum",
                            state=(): (partial, state))


def _altered_answer(monkeypatch):
    """One vertex's PageRank is altered where it is produced."""
    from repro import session
    real = session.GraphSession.run

    def run(self, program="pagerank", **kw):
        out = real(self, program, **kw)
        if program == "pagerank":
            vals, it = out
            vals = vals.copy()
            vals[7] *= 1.01
            out = (vals, it)
        return out
    monkeypatch.setattr(session.GraphSession, "run", run)


def _bf16_control(monkeypatch):
    """The plain reference, computed in bfloat16, in the program's place."""
    from repro import session
    real = session.GraphSession.run

    def run(self, program="pagerank", **kw):
        out = real(self, program, **kw)
        if program == "pagerank":
            src, dst = self.edges
            _, it = out
            out = (reference.pagerank(src, dst, self.num_vertices, it, 0.85,
                                      precision="bf16"), it)
        return out
    monkeypatch.setattr(session.GraphSession, "run", run)


FAULTS = {"unchanged_state": _unchanged_state,
          "half_the_edges": _half_the_edges,
          "no_exchange": _no_exchange,
          "altered_answer": _altered_answer,
          "bf16_control": _bf16_control}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_batch_fault_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    line = run_tiny("web-batch", 17)
    assert not line["correct"], line["checks"]
    assert line["failed"] == line["attempted"]


def _lost_window(monkeypatch):
    """A flush acknowledges its window but never adds it to the graph."""
    from repro import serve

    def flush(self):
        if self._buffered == 0:
            return False
        self._buf_src, self._buf_dst, self._buffered = [], [], 0
        self.stats["windows"] += 1
        return True
    monkeypatch.setattr(serve.GraphServer, "flush_window", flush)


def _altered_reply(monkeypatch):
    """Owner replies name the next partition over."""
    from repro import serve
    real = serve.GraphServer._answer

    def answer(self, kind, key, verts):
        out = real(self, kind, key, verts)
        return (out + 1) % self.sess.k if kind == "owner" else out
    monkeypatch.setattr(serve.GraphServer, "_answer", answer)


def _bf16_control_serve(monkeypatch):
    """The plain PageRank, computed in bfloat16, answers in the server's
    place."""
    from repro import session
    real = session.GraphSession.run_many

    def run_many(self, programs, **kw):
        outs, it = real(self, programs, **kw)
        src, dst = self.edges
        outs = [reference.pagerank(src, dst, self.num_vertices, it, 0.85,
                                   precision="bf16")
                if getattr(p, "name", p) == "pagerank" else o
                for p, o in zip(programs, outs)]
        return outs, it
    monkeypatch.setattr(session.GraphSession, "run_many", run_many)


def _early_stop(monkeypatch):
    """The server's GAS loop stops after two iterations, converged or
    not, and reports the two it ran."""
    from repro import session
    real = session.GraphSession.run_many

    def run_many(self, programs, **kw):
        if kw.get("tol") is not None:
            kw["iters"] = 2
        return real(self, programs, **kw)
    monkeypatch.setattr(session.GraphSession, "run_many", run_many)


def _stale_cache(monkeypatch):
    """A flush swaps the grown graph in but keeps serving the score
    vectors computed before it."""
    from repro import serve
    real = serve.GraphServer._swap

    def swap(self, *args):
        kept = dict(self._values)
        real(self, *args)
        self._values.update(kept)
    monkeypatch.setattr(serve.GraphServer, "_swap", swap)


SERVE_FAULTS = {"lost_window": _lost_window, "altered_reply": _altered_reply,
                "no_exchange": _no_exchange,
                "unchanged_state": _unchanged_state,
                "early_stop": _early_stop, "stale_cache": _stale_cache,
                "bf16_control": _bf16_control_serve}


@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
def test_serve_fault_is_not_correct(fault, monkeypatch):
    SERVE_FAULTS[fault](monkeypatch)
    line = run_tiny("serve-tiny", 19, seconds=2.0)
    assert not line["correct"], line["checks"]
    assert line["failed"] > 0


# ------------------------------------------------------------ the gate

def _bench_cmd(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "web-batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(proc) -> bool:
    for line in proc.stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_run_refuses_without_a_tpu():
    proc = _bench_cmd(ROOT)
    assert proc.returncode != 0 and _no_result(proc)
    assert "TPU" in proc.stderr


def test_run_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _bench_cmd(tmp_path)
    assert proc.returncode != 0 and _no_result(proc)
