"""The system under test, built from a configuration: the program's own
``GraphSession``/``GraphServer`` with the configuration's settings.  This
is the only module of the benchmark that imports the program."""
from __future__ import annotations

import sys

from .device import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def session(config: dict):
    """A fresh ``GraphSession`` as the configuration states it."""
    from repro.core import CLUGPConfig
    from repro.session import GraphSession, SessionConfig
    part = config["partition"]
    profile = {"optimized": CLUGPConfig.optimized,
               "paper": CLUGPConfig.paper}[part["profile"]]
    clugp = profile(part["k"], tau=part["tau"], kernel=part["game_kernel"],
                    cluster_kernel=part["cluster_kernel"],
                    restream=part["restream"])
    return GraphSession(SessionConfig(
        clugp=clugp, backend=part["backend"], nodes=part["nodes"],
        exchange=config["analytics"]["exchange"]))


def meshes(config: dict) -> tuple:
    """(stream mesh for the partitioner or None, GAS mesh or None)."""
    from repro.launch.mesh import make_graph_mesh, make_stream_mesh
    part = config["partition"]
    stream = (make_stream_mesh(part["nodes"])
              if part["backend"] == "sharded" else None)
    gas = make_graph_mesh(part["k"]) if config["analytics"]["mesh"] else None
    return stream, gas


def server(sess, config: dict):
    from repro.serve import GraphServer
    s = config["serve"]
    return GraphServer(sess, max_batch=s["max_batch"], window=s["window"],
                       rf_watermark=s["rf_watermark"],
                       restream_passes=s["restream_passes"],
                       iters=s["max_iters"], tol=s["tol"])
