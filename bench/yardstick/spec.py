"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration's file
is the one ``BENCHMARK.json`` gives; the traffic mix is
``bench/traffic/<traffic>.json``; a per-layer metric is read by
``bench/metrics/<name>.py``; an LM configuration's architecture
(``"architecture"`` at the file's top level) is ``bench/arch/<name>.py``.
Adding a cell, a mix, a metric or an architecture is adding files and
entries: nothing here names one.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import sys
import types
from pathlib import Path
from typing import Callable

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    chips: int
    end_to_end: tuple        # metric entries this cell reports at --trace 0
    per_layer: tuple         # metric entries this cell reports at --trace 1
    bench: Path
    arch: types.ModuleType | None = None   # the LM architecture module


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def load_traffic(name: str, bench: Path = BENCH) -> dict:
    with open(Path(bench) / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", (cell,))


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` of the benchmark at ``root``, with its
    configuration, traffic and metric entries.  Raises ``KeyError`` for a
    name that is not there."""
    root = Path(root)
    spec = load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    centry = configs[w["config"]]
    with open(root / centry["file"]) as f:
        config = json.load(f)
    bench = (root / centry["file"]).parents[1]
    if config.get("path") == "lm" and "architecture" not in config:
        raise ValueError(f"{centry['file']}: an LM configuration names its "
                         "architecture (\"architecture\": a module of "
                         "bench/arch)")
    arch = architecture(config["architecture"], bench) \
        if "architecture" in config else None
    e2e = tuple(m for m in spec["end_to_end"] if _reports(m, workload))
    moved = {m["name"] for m in e2e}
    per_layer = tuple(m for m in spec["per_layer"]
                      if m["moves"] in moved and _reports(m, workload))
    return Cell(name=workload, config_name=w["config"], config=config,
                traffic_name=w["traffic"],
                traffic=load_traffic(w["traffic"], bench),
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer,
                bench=bench, arch=arch)


_ARCH: dict = {}


def architecture(name: str, bench: Path = BENCH) -> types.ModuleType:
    """The module ``bench/arch/<name>.py``, loaded once a process: an LM
    architecture's ``shapes``, ``evaluate``, ``logits``, ``tokens_flops``,
    ``decode_min_bytes`` and, where it has one, ``window_work``.  Raises
    ``FileNotFoundError`` naming the path where there is no such file."""
    path = (Path(bench) / "arch" / f"{name}.py").resolve()
    if path not in _ARCH:
        if not path.is_file():
            raise FileNotFoundError(f"architecture {name!r}: no module "
                                    f"{path}")
        digest = hashlib.sha1(str(path).encode()).hexdigest()[:12]
        mod_name = f"bench_arch_{digest}"
        mod_spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[mod_name] = mod
        mod_spec.loader.exec_module(mod)
        _ARCH[path] = mod
    return _ARCH[path]


def metric_reader(name: str, bench: Path = BENCH) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<name>.py``: the metric's value
    from a traced run, or ``None`` where the run has nothing to read."""
    path = Path(bench) / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
