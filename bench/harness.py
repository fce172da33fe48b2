"""Find a cell's parts by name and assemble its result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* a configuration: ``file`` of its entry (under ``bench/configs/``); its
  ``driver`` key names the module ``bench/drivers/<driver>.py``;
* a traffic mix: ``bench/traffic/<traffic>.json``, read by the general
  generator in ``bench/workgen.py``;
* a metric: ``bench/metrics/<name>.py``, whose ``read(rec, ctx)`` returns
  the metric's value, or None when the run holds nothing to read.

A later cell adds files and entries; nothing here needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def find_cell(bench: dict, name: str, root: str = ROOT) -> Cell:
    """The workload ``name`` with its configuration and traffic loaded,
    and the metrics it reports."""
    wl = {w["name"]: w for w in bench["workloads"]}.get(name)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    return Cell(workload=wl, config=config,
                traffic=load_traffic(wl["traffic"], root),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_traffic(name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def _load(module: str, path: str):
    """The module at ``path`` under the name ``module`` (loaded once)."""
    mod = sys.modules.get(module)
    if mod is not None and getattr(mod, "__file__", None) == path:
        return mod
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[module] = mod
    spec.loader.exec_module(mod)
    return mod


def driver(config: dict, root: str = ROOT):
    """The driver module a configuration names,
    ``bench/drivers/<driver>.py``."""
    name = config["driver"]
    return _load(f"bench.drivers.{name}",
                 os.path.join(root, "bench", "drivers", f"{name}.py"))


def metric_reader(name: str, root: str = ROOT):
    """``read(rec, ctx)`` of ``bench/metrics/<name>.py``."""
    module = "bench.metrics." + name.replace(".", "_").replace("-", "_")
    return _load(module, os.path.join(root, "bench", "metrics",
                                      f"{name}.py")).read


def read_metrics(metrics: List[dict], rec, ctx: dict,
                 root: str = ROOT) -> Dict[str, dict]:
    out: Dict[str, dict] = {}
    for m in metrics:
        value = metric_reader(m["name"], root)(rec, ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell: Cell, rec, ctx: dict, device: dict,
                root: str = ROOT) -> dict:
    """The JSON object a run prints last. Traced runs report the cell's
    per-layer metrics, untraced runs its end-to-end metrics."""
    metrics = cell.per_layer if rec.trace else cell.end_to_end
    line: Dict[str, Any] = {
        "correct": rec.correct,
        "attempted": int(rec.attempted),
        "failed": int(rec.failed),
        "metrics": read_metrics(metrics, rec, ctx, root),
        "device": device,
    }
    if rec.trace_summary is not None:
        line["breakdown"] = rec.trace_summary.breakdown()
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in rec.checks}
    return line


def enable_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment names: two checkouts measured
    side by side share no compiled program."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def percentile(values: List[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) by the nearest-rank method, or None
    for no values."""
    if not values:
        return None
    xs = sorted(values)
    k = max(0, min(len(xs) - 1, -(-len(xs) * q // 100) - 1))
    return xs[int(k)]
