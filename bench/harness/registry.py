"""Everything of a cell is found by name: ``BENCHMARK.json`` names the
cells, configurations and metrics; a configuration is the JSON file its
entry names, a traffic mix is ``bench/traffic/<name>.json``, the loop
that drives it is ``bench/loops/<loop>.py`` (the mix's ``loop``), a
metric is read by ``bench/metrics/<name>.py`` and a cell's measured
limits are ``bench/limits/<cell>.json``.  Adding one is adding a file
and an entry; no code here changes."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{sorted(e['name'] for e in entries)}")


def workload(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = _named(bench["configs"], name, "configuration")
    return json.loads((root / entry["file"]).read_text())


def traffic(name: str, bench_dir: Path = BENCH) -> dict:
    path = bench_dir / "traffic" / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no traffic mix named {name!r} ({path} is missing)")
    return json.loads(path.read_text())


def measured_limits(cell: str, bench_dir: Path = BENCH) -> dict:
    """The measured limit of each number ``bench/limits/<cell>.json``
    names; a cell without the file has none."""
    path = bench_dir / "limits" / f"{cell}.json"
    if not path.is_file():
        return {}
    return {name: entry["limit"]
            for name, entry in json.loads(path.read_text()).items()}


def _module(kind: str, name: str, bench_dir: Path):
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} file for {name!r} ({path} is missing)")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def loop(name: str, bench_dir: Path = BENCH):
    """The ``Loop`` class of ``bench/loops/<name>.py``: it builds a cell's
    data in set-up, drives its window, and checks what the window made."""
    return _module("loops", name, bench_dir).Loop


def reader(name: str, bench_dir: Path = BENCH):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return _module("metrics", name, bench_dir).read


def metrics_of(bench: dict, cell: str, traced: bool) -> list:
    """The entries a run of ``cell`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced.  An entry without a
    ``workloads`` list belongs to every cell."""
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]
