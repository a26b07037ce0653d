"""Open-loop service traffic: queries and live edges arrive on a schedule
drawn from the seed, whatever the server is doing, and one thread drives
``GraphServer.submit/step/ingest`` the way a single-process server would.

The schedule has a fixed count of queries and of edges for the window's
length (``rate × seconds``), spread as a Poisson process conditioned on
its count, so every seed gives the server the same amount of work.  A
query is timed from when it was due; an edge is acknowledged when the
window holding it has been flushed, and from then on queries see it.

Set-up partitions the seed's graph, starts the server, answers one round
of every query kind (which compiles each score program) and flushes one
warm-up window of edges.  After the window every reply is held to a plain
computation on the graph as it stood when the reply was served.
"""
from __future__ import annotations

import time

import numpy as np

from harness import graphs, reference, system

SCORES = ("pagerank", "cc", "labelprop", "degree")


def schedule(traffic: dict, graph: dict, seconds: float, seed: int) -> dict:
    """The window's queries and edge arrivals, from the seed."""
    n = graph["num_vertices"]
    rng = np.random.default_rng([seed, 2])
    nq = int(round(traffic["query_rate"] * seconds))
    kinds = []
    for kind, share in traffic["mix"].items():
        kinds += [kind] * int(round(share * nq))
    kinds = (kinds + [kinds[-1]] * nq)[:nq]
    rng.shuffle(kinds)
    perm = rng.permutation(n)
    ranks = (rng.zipf(traffic["popularity_zipf"], nq) - 1) % n
    ne = int(round(traffic["edge_rate"] * seconds))
    esrc, edst = graphs.arrivals(graph, ne + graph_window(traffic), seed)
    return {"q_due": np.sort(rng.uniform(0, seconds, nq)),
            "q_kind": kinds, "q_vertex": perm[ranks],
            "e_due": np.sort(rng.uniform(0, seconds, ne)),
            "e_src": esrc[:ne], "e_dst": edst[:ne],
            "warm_src": esrc[ne:], "warm_dst": edst[ne:]}


def graph_window(traffic: dict) -> int:
    return int(traffic["warmup_edges"])


class Loop:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.graph = self.cfg["graph"]
        self.traffic = ctx.traffic
        self.replies: list = []     # one dict per query of the window
        self.epochs: list = []      # graph state after each flush
        self.computed: dict = {}    # (epoch, program) -> iterations run
        self.acks: list = []        # (edges acknowledged so far, time)
        self.window = (0.0, 0.0)
        self.lag: list = []

    def limits(self) -> dict:
        """What must match exactly (0), and the server's stopping
        tolerance: the change of the iteration it stopped at."""
        out = dict.fromkeys(("replies_missing", "label_mismatch",
                             "degree_mismatch", "neighbor_mismatch",
                             "owner_mismatch", "label_residual",
                             "acked_edges_lost"), 0)
        out["pagerank_residual"] = self.cfg["serve"]["tol"]
        return out

    # ------------------------------------------------------------ set-up

    def setup(self) -> None:
        rec = self.ctx.rec
        n = self.graph["num_vertices"]
        with rec.span("generate"):
            self.src, self.dst = graphs.generate(self.graph, self.ctx.seed)
            self.plan = schedule(self.traffic, self.graph, self.ctx.seconds,
                                 self.ctx.seed)
        sess = system.session(self.cfg)
        with rec.span("partition"):
            sess.partition(self.src, self.dst, n)
        with rec.span("layout"):
            sess.layout()
        self.srv = system.server(sess, self.cfg)
        self.all_src = [self.src]
        self.all_dst = [self.dst]
        self._new_epoch()
        rng = np.random.default_rng([self.ctx.seed, 3])
        warm = [("score:" + p, int(rng.integers(n))) for p in SCORES]
        warm += [("neighbors", int(rng.integers(n))),
                 ("owner", int(rng.integers(n)))]
        self._serve_now(warm)
        self._ingest(self.plan["warm_src"], self.plan["warm_dst"])
        self._serve_now(warm)

    def _new_epoch(self) -> None:
        self.epochs.append({
            "edges": sum(s.shape[0] for s in self.all_src),
            "assign": np.asarray(self.srv.sess.assign).copy(),
            "restreams": self.srv.stats["restreams"]})

    def _submit(self, kind: str, v: int) -> int:
        if kind.startswith("score:"):
            return self.srv.submit("score", program=kind[6:], vertices=[v])
        return self.srv.submit(kind, vertices=[v])

    def _serve_now(self, queries) -> None:
        """Answer ``queries`` at once (set-up only): compiles each score
        program on the current layout."""
        pending = [{"kind": kind, "vertex": v, "due": None,
                    "ticket": self._submit(kind, v)} for kind, v in queries]
        self._collect(self.srv.serve_pending(), pending)

    def _collect(self, served: int, pending: list) -> list:
        """Pop the replies of the ``served`` oldest pending queries."""
        done, now = pending[:served], time.perf_counter()
        epoch = len(self.epochs) - 1
        for q in done:
            rep = self.srv.result(q["ticket"])
            q.update(done_t=now, epoch=epoch,
                     value=None if rep is None else rep.value,
                     error="no reply" if rep is None else rep.error)
            if q["kind"].startswith("score:"):
                key = (epoch, q["kind"][6:])
                if key not in self.computed:
                    self.computed[key] = self._iters_of(q["kind"][6:])
        return pending[served:]

    def _iters_of(self, program: str) -> int:
        from repro.session import resolve_program
        prog = resolve_program(program, self.graph["num_vertices"])
        cell = (prog.combine, np.dtype(prog.dtype).name,
                self.cfg["analytics"]["exchange"])
        return int(self.srv.last_iters_run[cell])

    def _ingest(self, src, dst) -> None:
        """Hand ``src, dst`` to the server; a flush it triggers opens a
        new epoch and acknowledges every buffered edge."""
        before = self.srv.stats["windows"]
        name = ("flush" if self._buffered() + len(src)
                >= self.cfg["serve"]["window"] else "ingest")
        with self.ctx.rec.span(name):
            self.srv.ingest(src, dst)
        self.all_src.append(np.asarray(src))
        self.all_dst.append(np.asarray(dst))
        if self.srv.stats["windows"] > before:
            self._acked()

    def _acked(self) -> None:
        self._new_epoch()
        self.acks.append((self.epochs[-1]["edges"], time.perf_counter()))

    def _buffered(self) -> int:
        acked = self.acks[-1][0] if self.acks else self.src.shape[0]
        return sum(s.shape[0] for s in self.all_src) - acked

    # ------------------------------------------------------------ window

    def run(self, seconds: float) -> None:
        plan, rec = self.plan, self.ctx.rec
        q_due, e_due = plan["q_due"], plan["e_due"]
        self.e0 = sum(s.shape[0] for s in self.all_src)
        qi = ei = 0
        pending: list = []
        t0 = time.perf_counter()
        if self.ctx.tracer is not None:
            self.ctx.tracer.start()       # the whole window is traced
        with rec.span("window"):
            while True:
                now = time.perf_counter() - t0
                if now >= seconds:
                    break
                busy = False
                while qi < len(q_due) and q_due[qi] <= now:
                    pending.append(self._submit_due(qi, t0))
                    qi += 1
                j = int(np.searchsorted(e_due, now, side="right"))
                if j > ei:
                    self._ingest(plan["e_src"][ei:j], plan["e_dst"][ei:j])
                    ei, busy = j, True
                if pending:
                    with rec.span("step"):
                        served = self.srv.step()
                    pending = self._collect(served, pending)
                    busy = True
                if not busy:
                    nxt = min(q_due[qi] if qi < len(q_due) else seconds,
                              e_due[ei] if ei < len(e_due) else seconds)
                    time.sleep(max(0.0, min(nxt - now, 0.002)))
        self.window = (t0, time.perf_counter())
        self.t0 = t0
        self.pending = pending
        self.edges_due = ei

    def _submit_due(self, qi: int, t0: float) -> dict:
        """Submit the schedule's query ``qi``, due at ``t0`` + its time,
        and note how late the generator is."""
        plan = self.plan
        kind, v = plan["q_kind"][qi], int(plan["q_vertex"][qi])
        q = {"kind": kind, "vertex": v, "due": t0 + plan["q_due"][qi],
             "ticket": self._submit(kind, v)}
        self.lag.append(time.perf_counter() - q["due"])
        self.replies.append(q)
        return q

    def drain(self) -> None:
        """Answer every query that came due in the window and flush the
        edges still buffered, so each is acknowledged; both count against
        the latency and staleness of what was due.  What fell due after
        the loop's last look at the clock is handed over first."""
        plan = self.plan
        for qi in range(len(self.replies), len(plan["q_due"])):
            self.pending.append(self._submit_due(qi, self.t0))
        if self.edges_due < len(plan["e_due"]):
            self._ingest(plan["e_src"][self.edges_due:],
                         plan["e_dst"][self.edges_due:])
            self.edges_due = len(plan["e_due"])
        while self.pending:
            with self.ctx.rec.span("step"):
                served = self.srv.step()
            if not served:
                break
            self.pending = self._collect(served, self.pending)
        with self.ctx.rec.span("flush"):
            flushed = self.srv.flush_window()
        if flushed:
            self._acked()

    def results(self) -> dict:
        lat = [q["done_t"] - q["due"] for q in self.replies if "done_t" in q]
        e_due = self.plan["e_due"][:self.edges_due] + self.t0
        counts = np.array([c for c, _ in self.acks], np.int64)
        times = np.array([t for _, t in self.acks] + [np.nan])
        first = np.searchsorted(counts, self.e0 + np.arange(e_due.shape[0]),
                                side="right")
        ack_at = times[first]
        return {"latency_s": np.asarray(lat),
                "staleness_s": ack_at - e_due,
                "generator_lag_s": np.asarray(self.lag),
                "window": self.window,
                "flushes": len(self.acks),
                "restreams": self.srv.stats["restreams"]}

    def summary(self) -> dict:
        """Plain facts about the window for the reader of the log: how late
        the open-loop generator ran, and whether the edge backlog grew."""
        res = self.results()
        out = {"flushes": res["flushes"], "restreams": res["restreams"],
               "queries": int(res["latency_s"].size)}
        lag = res["generator_lag_s"]
        if lag.size:
            out["generator_lag_ms"] = {
                "p50": float(np.percentile(lag, 50)) * 1e3,
                "p99": float(np.percentile(lag, 99)) * 1e3,
                "max": float(lag.max()) * 1e3}
        st = res["staleness_s"]
        half = st.size // 2
        if half:
            # a backlog that grows shows as a later half staler than the
            # first: the rate is past what the server sustains
            out["staleness_halves_s"] = [float(st[:half].mean()),
                                         float(st[half:].mean())]
        return out

    # ------------------------------------------------------------- check

    def check(self) -> tuple:
        """(readings, attempted, failed).  Score replies are held to the
        plain programs run on the graph of the epoch they were served in,
        warm-started as the server does: from the program's result in the
        latest earlier epoch that computed it, for as many iterations as
        the server reported.  The last of those iterations has to have
        met the server's stop rule (PageRank: a change of at most tol;
        labels: none changed), so a server that stops early, or hands
        back its warm start, fails however well its values match.
        Owners and neighbours are held to that epoch's edge list and
        partition, and every acknowledged edge must be in the served
        graph, in arrival order."""
        n = self.graph["num_vertices"]
        k = self.cfg["partition"]["k"]
        src = np.concatenate(self.all_src)
        dst = np.concatenate(self.all_dst)
        damping = self.cfg["analytics"]["pagerank"]["damping"]
        values, change = self._chains(src, dst, damping)
        lim = self.ctx.limits
        r = dict.fromkeys(("replies_missing", "label_mismatch",
                           "degree_mismatch", "neighbor_mismatch",
                           "owner_mismatch"), 0)
        r["pagerank_gap"] = 0.0
        r["pagerank_residual"] = max(
            [c for (_, p), c in change.items() if p == "pagerank"],
            default=0.0)
        r["label_residual"] = sum(
            c for (_, p), c in change.items() if p != "pagerank")
        wrong = np.zeros(len(self.replies), bool)
        by_epoch: dict = {}
        for i, q in enumerate(self.replies):
            if q.get("error") is not None or "done_t" not in q:
                r["replies_missing"] += 1
                wrong[i] = True
            else:
                by_epoch.setdefault(q["epoch"], []).append(i)
        for ep, idx in by_epoch.items():
            e = self.epochs[ep]["edges"]
            es, ed = src[:e], dst[:e]
            verts = np.unique([self.replies[i]["vertex"] for i in idx])
            near = np.isin(es, verts) | np.isin(ed, verts)
            ns_, nd_ = es[near], ed[near]
            assign = self.epochs[ep]["assign"]
            # a partition that lost acknowledged edges has no master to
            # hold an owner reply to: every owner reply of it is wrong
            own = (reference.masters(ns_, nd_, assign[near], n, k)
                   if assign.shape[0] == e else np.full(n, -1))
            deg = None
            for i in idx:
                q = self.replies[i]
                v, got = q["vertex"], np.asarray(q["value"])
                kind = q["kind"]
                if kind == "score:pagerank":
                    ref = values[(ep, "pagerank")][v]
                    gap = float(abs(got.astype(np.float64)[0] - ref) / ref)
                    r["pagerank_gap"] = max(r["pagerank_gap"], gap)
                    bad = not (gap <= lim["pagerank_gap"]
                               and change[(ep, "pagerank")]
                               <= lim["pagerank_residual"])
                elif kind in ("score:cc", "score:labelprop"):
                    miss = int(got[0]) != int(values[(ep, kind[6:])][v])
                    r["label_mismatch"] += miss
                    bad = miss or change[(ep, kind[6:])] > 0
                elif kind == "score:degree":
                    if deg is None:
                        deg = reference.degree(es, ed, n)
                    bad = int(got[0]) != int(deg[v])
                    r["degree_mismatch"] += bad
                elif kind == "neighbors":
                    want = reference.neighbors(ns_, nd_, v)
                    bad = not np.array_equal(np.asarray(q["value"][0]), want)
                    r["neighbor_mismatch"] += bad
                else:
                    bad = int(got[0]) != int(own[v])
                    r["owner_mismatch"] += bad
                wrong[i] = bad
        # every acknowledged edge is in the served graph, in order
        fsrc, fdst = self.srv.sess.edges
        acked = self.acks[-1][0] if self.acks else 0
        fa = np.asarray(self.srv.sess.assign)
        r["acked_edges_lost"] = int(
            fsrc.shape[0] < acked
            or not np.array_equal(fsrc[:acked], src[:acked])
            or not np.array_equal(fdst[:acked], dst[:acked])
            or fa.shape[0] < acked) + int(((fa < 0) | (fa >= k)).sum())
        attempted = len(self.replies) + len(self.acks)
        failed = int(wrong.sum()) + (r["acked_edges_lost"] > 0)
        return r, attempted, failed

    def control(self) -> dict:
        """The control's reading: the PageRank chain computed in bfloat16
        in the program's place, held to the float64 chain at the replies
        the window served."""
        src = np.concatenate(self.all_src)
        dst = np.concatenate(self.all_dst)
        d = self.cfg["analytics"]["pagerank"]["damping"]
        ref, _ = self._chains(src, dst, d)
        low, _ = self._chains(src, dst, d, precision="bf16")
        gap = 0.0
        for q in self.replies:
            if q["kind"] == "score:pagerank" and "epoch" in q:
                key, v = (q["epoch"], "pagerank"), q["vertex"]
                gap = max(gap, float(abs(low[key][v] - ref[key][v])
                                     / ref[key][v]))
        return {"pagerank_gap": gap}

    def _chains(self, src, dst, damping: float,
                precision: str = "f64") -> tuple:
        """Reference value vector per (epoch, program) the server
        computed, following its warm starts and iteration counts, and the
        change of the last of those iterations (max-norm for PageRank,
        labels changed for cc and labelprop); for a count of 0, the
        change of one step from the value handed back."""
        n = self.graph["num_vertices"]
        out: dict = {}
        change: dict = {}
        last: dict = {}
        for ep, program in sorted(self.computed):
            if program not in ("pagerank", "cc", "labelprop"):
                continue
            iters = self.computed[(ep, program)]
            e = self.epochs[ep]["edges"]
            start = last.get(program)
            if program == "pagerank":
                op = reference.PageRank(src[:e], dst[:e], n, damping,
                                        precision)
                prev = op.run(max(iters - 1, 0),
                              op.cold() if start is None else start)
                nxt = op.step(prev)
                change[(ep, program)] = float(np.abs(nxt - prev).max())
            else:
                op = reference.label_op(program, src[:e], dst[:e], n)
                if start is None:
                    start = reference.labels_cold(n, program)
                prev = op.run(start, max(iters - 1, 0))
                nxt = op.step(prev)
                change[(ep, program)] = int((nxt != prev).sum())
            out[(ep, program)] = last[program] = nxt if iters else prev
        return out, change
