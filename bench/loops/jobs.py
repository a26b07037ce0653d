"""Closed-loop batch jobs: each job streams the seed's edge list through
``GraphSession.partition → layout → run("pagerank") → run("cc")``, back
to back on the same stream, until the next job would not fit the window.

Set-up generates the stream and runs one whole job, which compiles every
program the window's jobs run (the stream, and so every shape, is the
same each time).  After the window every job's outputs are held to the
plain references (``reference.py``)."""
from __future__ import annotations

import time

import numpy as np

from harness import graphs, reference, system


class Loop:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.graph = self.cfg["graph"]
        self.part = self.cfg["partition"]
        self.jobs: list = []
        self.window = (0.0, 0.0)

    def limits(self) -> dict:
        """What must match exactly (0), and what the configuration states:
        the balance cap and PageRank's stopping tolerance."""
        part = self.part
        out = dict.fromkeys(("edges_unassigned", "rf_gap", "layout_faults",
                             "wcc_mismatch"), 0)
        out["balance"] = (part["tau"] + part["nodes"] * part["k"]
                          / self.graph["num_edges"])
        out["pagerank_residual"] = self.cfg["analytics"]["pagerank"]["tol"]
        return out

    # ------------------------------------------------------------ set-up

    def setup(self) -> None:
        with self.ctx.rec.span("generate"):
            self.src, self.dst = graphs.generate(self.graph, self.ctx.seed)
        self.stream_mesh, self.gas_mesh = system.meshes(self.cfg)
        self.job(-1)                          # warm-up: compiles

    def job(self, idx: int) -> dict:
        """One whole batch job; its outputs, as host arrays."""
        rec, ana = self.ctx.rec, self.cfg["analytics"]
        n = self.graph["num_vertices"]
        t0 = time.perf_counter()
        sess = system.session(self.cfg)
        with rec.span("partition", job=idx):
            sess.partition(self.src, self.dst, n, mesh=self.stream_mesh)
        with rec.span("layout", job=idx):
            sess.layout()
        pr_cfg = ana["pagerank"]
        with rec.span("pagerank", job=idx):
            pr, pr_iters = sess.run("pagerank", iters=pr_cfg["max_iters"],
                                    tol=pr_cfg["tol"], mesh=self.gas_mesh,
                                    return_iters=True)
        with rec.span("cc", job=idx):
            cc, cc_iters = sess.run("cc", iters=ana["wcc"]["max_iters"],
                                    tol=0.0, mesh=self.gas_mesh,
                                    return_iters=True)
        return {"start": t0, "end": time.perf_counter(),
                "assign": np.asarray(sess.assign), "stats": sess.stats,
                "layout": sess.partition_layout, "pagerank": pr,
                "pagerank_iters": pr_iters, "cc": cc, "cc_iters": cc_iters}

    # ------------------------------------------------------------ window

    def run(self, seconds: float) -> None:
        tracer = self.ctx.tracer
        t0 = time.perf_counter()
        last = 0.0
        with self.ctx.rec.span("window"):
            while True:
                now = time.perf_counter() - t0
                if self.jobs and now + last > seconds:
                    break
                if tracer is not None and not self.jobs:
                    tracer.start()
                out = self.job(len(self.jobs))
                if tracer is not None:
                    tracer.stop()         # the first job is traced
                last = out["end"] - out["start"]
                self.jobs.append(out)
        self.window = (t0, self.jobs[-1]["end"])

    def drain(self) -> None:
        """Every job is synchronous: nothing is left in flight."""

    def results(self) -> dict:
        return {"jobs": [{k: j[k] for k in ("start", "end", "stats",
                                            "pagerank_iters", "cc_iters")}
                         for j in self.jobs],
                "window": self.window}

    def summary(self) -> dict:
        """Plain facts about the window, for the reader of the log."""
        out = {"jobs": len(self.jobs),
               "job_seconds": [j["end"] - j["start"] for j in self.jobs]}
        if self.jobs:
            last = self.jobs[-1]
            out.update(rf=last["stats"]["rf"],
                       game_rounds=last["stats"].get("game_rounds"),
                       clusters=last["stats"].get("num_clusters"),
                       pagerank_iters=last["pagerank_iters"],
                       cc_iters=last["cc_iters"])
        return out

    # ------------------------------------------------------------- check

    def control(self) -> dict:
        """The control's reading: the plain PageRank computed in bfloat16
        in the program's place, at each job's iteration count."""
        n = self.graph["num_vertices"]
        d = self.cfg["analytics"]["pagerank"]["damping"]
        gap = 0.0
        for it in sorted({int(j["pagerank_iters"]) for j in self.jobs}):
            ref = reference.pagerank(self.src, self.dst, n, it, d)
            low = reference.pagerank(self.src, self.dst, n, it, d,
                                     precision="bf16")
            gap = max(gap, float(np.max(np.abs(low - ref) / ref)))
        return {"pagerank_gap": gap}

    def check(self) -> tuple:
        """(readings, attempted, failed).  ``readings`` maps each number
        compared to its worst value over the window's jobs."""
        n = self.graph["num_vertices"]
        k = self.part["k"]
        e = self.src.shape[0]
        pr_cfg = self.cfg["analytics"]["pagerank"]
        op = reference.PageRank(self.src, self.dst, n, pr_cfg["damping"])
        wcc = reference.wcc(self.src, self.dst, n)
        pr_refs: dict = {}
        worst: dict = {}
        failed = 0
        for job in self.jobs:
            it = int(job["pagerank_iters"])
            if it not in pr_refs:
                # the reference after ``it`` steps, and its last change:
                # a run that stopped early, or never stepped, shows here
                prev = op.run(max(it - 1, 0))
                nxt = op.step(prev)
                pr_refs[it] = (nxt if it else prev,
                               float(np.abs(nxt - prev).max()))
            ref, residual = pr_refs[it]
            a = job["assign"]
            r = {
                "edges_unassigned": int(a.shape[0] != e)
                + int(((a < 0) | (a >= k)).sum()),
                "balance": reference.balance(a, k),
                "rf_gap": abs(job["stats"]["rf"]
                              - reference.replication_factor(
                                  self.src, self.dst, a, n, k)),
                "layout_faults": reference.layout_faults(
                    job["layout"], self.src, self.dst, a, n, k),
                "pagerank_gap": float(np.max(
                    np.abs(job["pagerank"] - ref) / ref)),
                "pagerank_residual": residual,
                "wcc_mismatch": int((job["cc"] != wcc).sum()),
            }
            bad = [name for name, v in r.items()
                   if not v <= self.ctx.limits[name]]
            failed += bool(bad)
            for name, v in r.items():
                worst[name] = max(worst.get(name, v), v)
        return worst, len(self.jobs), failed
