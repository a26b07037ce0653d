"""GraphSession — one object from edge stream to distributed analytics.

The repo's workload is a three-hop chain: CLUGP partition the stream
(`repro.core.partition`), build the vertex-cut device tables
(`repro.graph.build_layout`), run GAS programs over a mesh with a chosen
mirror wire format (`repro.graph.engine` × `repro.dist.halo`).  Before
this module every launcher/benchmark/example hand-wired the chain; the
session makes it one fluent object with a **serializable config**, so a
run is reproducible from a JSON blob:

    from repro.session import GraphSession, SessionConfig
    from repro.core import CLUGPConfig

    sess = GraphSession(SessionConfig(clugp=CLUGPConfig.optimized(8),
                                      backend="jit", exchange="quantized"))
    sess = GraphSession.from_json(sess.to_json())     # round-trips
    pr = sess.partition(src, dst, V).layout().run("pagerank")
    cc = sess.run("cc", mesh=make_graph_mesh(8))      # same layout, any mesh
    sess.comm_bytes()        # modelled wire bytes/iter per exchange

``partition`` accepts any backend (`np`/`jit`/`sharded`, `nodes` for the
§III-C stream split); ``with_partition`` adopts an external edge→partition
assignment (baselines) so the layout/engine/accounting half of the session
works on it; ``run`` takes a program name (any of ``PROGRAMS`` — the
``repro.graph.engine`` library: pagerank/cc/labelprop/sssp/bfs/degree/
centrality/ppr) or any ``GASProgram`` and simulates on one device
(``mesh=None``) or shard_maps k/D partitions onto each of D devices;
``run_many``
executes N homogeneous programs as one fused loop with a single mirror
exchange per phase; ``dryrun_step`` hands the compile-only cell (single
or fused) to ``launch.dryrun --graph``; ``comm_bytes(programs=...,
exchange=..., fused=...)`` is the one keyword-routed comm accounting
entry point (per-exchange table, per-program rows, fused bundles).
"""
from __future__ import annotations

import dataclasses
import json
import warnings
from dataclasses import dataclass

import numpy as np

from .core import metrics
from .core.partitioner import BACKENDS, partition, partition_sweep
from .core.pipeline import CLUGPConfig, CLUGPResult
from .dist.halo import EXCHANGE_NAMES, lossy_payload
from .graph import (GASProgram, PROGRAM_NAMES, PartitionLayout,
                    build_layout, fuse_programs, gas_step_for_dryrun,
                    get_program, shard_map_gas, shard_map_gas_many,
                    simulate_gas, simulate_gas_many)

# the session validates/enumerates wire formats through the ONE registry
EXCHANGES = EXCHANGE_NAMES
PROGRAMS = PROGRAM_NAMES


def resolve_program(program, num_vertices: int) -> GASProgram:
    """Name → library GASProgram (a GASProgram passes through)."""
    if isinstance(program, GASProgram):
        return program
    if program in PROGRAMS:
        return get_program(program, num_vertices)
    raise ValueError(f"unknown program {program!r}; expected a GASProgram "
                     f"or one of {PROGRAMS}")


@dataclass(frozen=True)
class SessionConfig:
    """Everything a reproducible partition→layout→GAS run needs.  Frozen
    and JSON-round-trippable (``to_json``/``from_json``): two sessions
    built from the same blob produce identical partitions and compile
    identical GAS cells (tested)."""
    clugp: CLUGPConfig
    backend: str = "np"        # partitioner strategy: np | jit | sharded
    nodes: int = 1             # §III-C stream-split width
    exchange: str = "halo"     # default mirror wire format for run()
    iters: int = 30            # default GAS iterations
    pad_multiple: int = 8      # layout table padding

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; expected "
                             f"one of {BACKENDS}")
        if self.exchange not in EXCHANGES:
            raise ValueError(f"unknown exchange {self.exchange!r}; "
                             f"expected one of {EXCHANGES}")
        if self.nodes < 1:
            raise ValueError(f"nodes must be >= 1, got {self.nodes}")
        if not isinstance(self.clugp, CLUGPConfig):
            raise TypeError("SessionConfig.clugp must be a CLUGPConfig")

    def to_json(self) -> str:
        # asdict recurses into the nested CLUGPConfig
        return json.dumps(dataclasses.asdict(self), indent=1)

    @classmethod
    def from_json(cls, text: str) -> "SessionConfig":
        d = json.loads(text)
        clugp = CLUGPConfig(**d.pop("clugp"))
        return cls(clugp=clugp, **d)


class GraphSession:
    """Fluent façade: ``GraphSession(cfg).partition(...).layout().run(...)``.

    ``partition``/``with_partition``/``layout`` return ``self`` for
    chaining; ``run`` returns the program's dense (V,) master values.
    The layout is built lazily by ``run``/``comm_bytes`` if ``layout()``
    was not called explicitly."""

    def __init__(self, cfg: SessionConfig | CLUGPConfig, **overrides):
        if isinstance(cfg, CLUGPConfig):
            cfg = SessionConfig(clugp=cfg, **overrides)
        elif overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        if not isinstance(cfg, SessionConfig):
            raise TypeError("GraphSession takes a SessionConfig or a "
                            "CLUGPConfig (+ SessionConfig overrides)")
        self.cfg = cfg
        self.result: CLUGPResult | None = None
        self._layout: PartitionLayout | None = None
        self._src = self._dst = None
        self._num_vertices: int | None = None

    # ----------------------------------------------------------- config

    @property
    def k(self) -> int:
        return self.cfg.clugp.k

    def to_json(self) -> str:
        return self.cfg.to_json()

    @classmethod
    def from_json(cls, text: str) -> "GraphSession":
        return cls(SessionConfig.from_json(text))

    # -------------------------------------------------------- partition

    def partition(self, src, dst, num_vertices: int, *,
                  mesh=None) -> "GraphSession":
        """Run the configured CLUGP backend on the edge stream."""
        self._adopt_graph(src, dst, num_vertices)
        self.result = partition(self._src, self._dst, self._num_vertices,
                                self.cfg.clugp, backend=self.cfg.backend,
                                nodes=self.cfg.nodes, mesh=mesh)
        return self

    def run_sweep(self, src, dst, num_vertices: int, ks) -> dict:
        """Partition the stream at every ``k`` in ``ks`` under ONE
        compiled stacked body (``repro.core.partition_sweep`` — jit
        semantics, k_max-padded lanes, traced per-step k).  Returns
        ``{k: CLUGPResult}`` in input order and leaves the session on the
        LAST k's partition, ready for ``layout()``/``run()``; re-run
        ``partition`` or adopt another sweep entry via ``with_partition``
        to work on a different k."""
        self._adopt_graph(src, dst, num_vertices)
        results = partition_sweep(self._src, self._dst,
                                  self._num_vertices, self.cfg.clugp, ks)
        table = dict(zip((int(k) for k in ks), results))
        last_k = int(tuple(ks)[-1])
        self.cfg = dataclasses.replace(
            self.cfg, clugp=dataclasses.replace(self.cfg.clugp, k=last_k))
        self.result = table[last_k]
        return table

    def with_partition(self, src, dst, num_vertices: int,
                       assign) -> "GraphSession":
        """Adopt an externally computed edge→partition assignment (e.g. a
        baseline partitioner) so layout/run/comm accounting work on it."""
        self._adopt_graph(src, dst, num_vertices)
        assign = np.asarray(assign)
        if assign.shape[0] != self._src.shape[0]:
            raise ValueError(
                f"assignment covers {assign.shape[0]} edges but the "
                f"stream has {self._src.shape[0]}")
        res = CLUGPResult(assign, None, None, None, 0)
        res.stats = metrics.summarize(self._src, self._dst, assign,
                                      self._num_vertices, self.k)
        res.stats["backend"] = "external"
        self.result = res
        return self

    def _adopt_graph(self, src, dst, num_vertices: int) -> None:
        self._src = np.asarray(src)
        self._dst = np.asarray(dst)
        self._num_vertices = int(num_vertices)
        self._layout = None
        self.result = None

    def _require_partition(self) -> None:
        if self.result is None:
            raise RuntimeError(
                "GraphSession: no partition yet — call partition(src, dst, "
                "V) or with_partition(...) first")

    @property
    def assign(self) -> np.ndarray:
        self._require_partition()
        return self.result.assign

    @property
    def stats(self) -> dict:
        self._require_partition()
        return self.result.stats

    @property
    def num_vertices(self) -> int:
        if self._num_vertices is None:
            raise RuntimeError("GraphSession: no graph yet — call "
                               "partition(...) or with_partition(...)")
        return self._num_vertices

    @property
    def edges(self) -> tuple:
        """(src, dst) of the adopted edge stream."""
        if self._src is None:
            raise RuntimeError("GraphSession: no graph yet — call "
                               "partition(...) or with_partition(...)")
        return self._src, self._dst

    # --------------------------------------------------------- snapshots

    def snapshot(self) -> dict:
        """Host-side array tree of the session's graph + partition — what
        ``dist.ft.ServiceFT`` checkpoints for a serving process.  Pair it
        with ``to_json()`` (the config half) and ``num_vertices``;
        ``from_snapshot`` rebuilds an equivalent session."""
        self._require_partition()
        return {"src": np.asarray(self._src).copy(),
                "dst": np.asarray(self._dst).copy(),
                "assign": np.asarray(self.result.assign).copy()}

    @classmethod
    def from_snapshot(cls, config_json: str, tree: dict,
                      num_vertices: int) -> "GraphSession":
        """Rebuild a session from ``to_json()`` + ``snapshot()`` output:
        same config blob, same edges, same edge→partition assignment (no
        re-partitioning — the snapshot IS the partition)."""
        sess = cls.from_json(config_json)
        return sess.with_partition(tree["src"], tree["dst"], num_vertices,
                                   tree["assign"])

    # ----------------------------------------------------------- layout

    def layout(self, pad_multiple: int | None = None) -> "GraphSession":
        """Build the vertex-cut device tables for the current partition."""
        self._require_partition()
        self._layout = build_layout(
            self._src, self._dst, self.result.assign, self._num_vertices,
            self.k, pad_multiple or self.cfg.pad_multiple)
        return self

    @property
    def partition_layout(self) -> PartitionLayout:
        if self._layout is None:
            self.layout()
        return self._layout

    def comm_bytes(self, programs=None, exchange: str | None = None,
                   fused: bool = False):
        """Modelled mirror-sync wire bytes per GAS iteration — the one
        keyword-routed comm accounting entry point:

        - ``comm_bytes()`` — the per-exchange table dict (the Fig. 8
          accounting: every wire format plus the ragged ideal and the
          dense psum baseline).
        - ``comm_bytes(exchange="halo")`` — one model's bytes (int).
        - ``comm_bytes(programs=[...])`` — per-program rows
          ``{program: {exchange: bytes}}`` with per-program lossy-ness
          (int/min programs ship exact on the quantized wires — the
          rows the dry-run gate asserts); narrow to ``{program: bytes}``
          with ``exchange=``.
        - ``comm_bytes(programs=[...], fused=True)`` — one fused step's
          bytes (single collective per phase; int4 fused wire when
          lossy).  ``exchange`` defaults to the session exchange.
        """
        lay = self.partition_layout
        if programs is None:
            if fused:
                raise ValueError(
                    "comm_bytes(fused=True) needs programs=[...]")
            return lay.comm_bytes(exchange)
        if fused:
            bundle = fuse_programs(
                [resolve_program(p, self._num_vertices) for p in programs])
            lossy = lossy_payload(bundle.combine, bundle.dtype)
            return lay.comm_bytes(exchange or self.cfg.exchange,
                                  programs=len(bundle.programs),
                                  fused=True, lossy=lossy)
        table = {}
        for p in programs:
            prog = resolve_program(p, self._num_vertices)
            lossy = lossy_payload(prog.combine, prog.dtype)
            if exchange is None:
                table[prog.name] = {ex: lay.comm_bytes(ex, lossy=lossy)
                                    for ex in EXCHANGE_NAMES}
            else:
                table[prog.name] = lay.comm_bytes(exchange, lossy=lossy)
        return table

    def comm_bytes_programs(self, programs=PROGRAMS) -> dict:
        """Deprecated — use ``comm_bytes(programs=[...])``."""
        warnings.warn(
            "GraphSession.comm_bytes_programs is deprecated; use "
            "GraphSession.comm_bytes(programs=[...])",
            DeprecationWarning, stacklevel=2)
        return self.comm_bytes(programs=programs)

    def comm_bytes_fused(self, programs, exchange: str | None = None) -> int:
        """Deprecated — use ``comm_bytes(programs=[...], fused=True)``."""
        warnings.warn(
            "GraphSession.comm_bytes_fused is deprecated; use "
            "GraphSession.comm_bytes(programs=[...], fused=True)",
            DeprecationWarning, stacklevel=2)
        return self.comm_bytes(programs=programs, exchange=exchange,
                               fused=True)

    # ------------------------------------------------------------- GAS

    def run(self, program="pagerank", *, iters: int | None = None,
            exchange: str | None = None, mesh=None, axis: str = "parts",
            tol: float | None = None, overlap: bool = False,
            init_values=None, return_iters: bool = False):
        """Run a GAS program on the session's layout and return the dense
        (V,) master values.  ``mesh=None`` simulates the stacked k-device
        engine on one device; with a mesh the program shard_maps the k
        partitions over the axis's D devices, k/D to a device (D must
        divide k; ``make_graph_mesh(k)`` picks it), compiled once per
        shape — the same results by construction (shared ``_gas_body``),
        up to the float32 summation order of a global aux.

        ``tol`` turns ``iters`` into a cap: the loop exits once the
        master residual max-norm drops to ``tol`` (``return_iters=True``
        additionally returns the executed count).  ``overlap`` runs the
        interleaved interior/frontier body (ragged exchanges only);
        ``init_values`` warm-starts from a dense (V_old,) vector."""
        lay = self.partition_layout
        prog = resolve_program(program, self._num_vertices)
        iters = self.cfg.iters if iters is None else iters
        exchange = exchange or self.cfg.exchange
        kw = dict(tol=tol, overlap=overlap, init_values=init_values,
                  return_iters=return_iters)
        if mesh is None:
            out = simulate_gas(prog, lay, iters=iters, exchange=exchange,
                               **kw)
        else:
            out = shard_map_gas(prog, lay, mesh, iters=iters, axis=axis,
                                exchange=exchange, **kw)
        out, iters_run = out if return_iters else (out, iters)
        if np.issubdtype(out.dtype, np.integer):
            out = out.astype(np.int64)     # label/distance programs
        return (out, iters_run) if return_iters else out

    def run_many(self, programs, *, iters: int | None = None,
                 exchange: str | None = None, mesh=None,
                 axis: str = "parts", tol: float | None = None,
                 overlap: bool = False, init_values=None,
                 return_iters: bool = False):
        """Run N homogeneous programs as one fused GAS loop — a single
        mirror-sync collective per phase carries every program's lanes
        (``repro.graph.engine.FusedGAS``).  Returns one dense (V,) array
        per program, in input order.  ``tol`` / ``overlap`` /
        ``init_values`` (one dense vector or None per program) /
        ``return_iters`` as in ``run``; a mesh must hold one partition
        per device."""
        lay = self.partition_layout
        progs = [resolve_program(p, self._num_vertices) for p in programs]
        iters = self.cfg.iters if iters is None else iters
        exchange = exchange or self.cfg.exchange
        kw = dict(tol=tol, overlap=overlap, init_values=init_values,
                  return_iters=return_iters)
        if mesh is None:
            outs = simulate_gas_many(progs, lay, iters=iters,
                                     exchange=exchange, **kw)
        else:
            outs = shard_map_gas_many(progs, lay, mesh, iters=iters,
                                      axis=axis, exchange=exchange, **kw)
        outs, iters_run = outs if return_iters else (outs, iters)
        outs = [o.astype(np.int64)
                if np.issubdtype(o.dtype, np.integer) else o
                for o in outs]
        return (outs, iters_run) if return_iters else outs

    def dryrun_step(self, program="pagerank", *, mesh, iters: int = 1,
                    exchange: str | None = None, axis: str = "parts",
                    overlap: bool = False):
        """(jitted_fn, example_args) for one shard_map GAS step — what
        ``launch.dryrun --graph`` lowers to parse collective bytes.
        ``program`` may be a name/GASProgram or a sequence of them; a
        sequence compiles the fused multi-program step.  ``overlap``
        compiles the interleaved ragged body."""
        lay = self.partition_layout
        if isinstance(program, (list, tuple)):
            prog = [resolve_program(p, self._num_vertices)
                    for p in program]
        else:
            prog = resolve_program(program, self._num_vertices)
        return gas_step_for_dryrun(prog, lay, mesh, axis=axis, iters=iters,
                                   exchange=exchange or self.cfg.exchange,
                                   overlap=overlap)
