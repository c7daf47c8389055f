"""Finds cells, configurations, traffic mixes, metric readers and peaks.

Everything is looked up by the name ``BENCHMARK.json`` gives it:

  configuration  ``bench/configs/<name>.json``
  traffic mix    ``bench/traffic/<name>.json``
  metric         ``bench/metrics/<name>.py`` (``launch_ms.steady.py``)
  peaks          ``bench/peaks.json``, keyed by ``device_kind``

A cell reports the end-to-end metrics that list it under ``workloads``
or have no such list, and the per-layer metrics that list it; every
per-layer metric lists its cells.  An unknown name raises ``KeyError``:
a later change adds a cell or a metric by adding files and entries,
never by editing these lookups.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

__all__ = ["BENCH_DIR", "Cell", "ROOT", "load_benchmark", "load_cell",
           "load_peaks", "metric_reader", "use_compilation_cache"]


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict  # the configuration file, as run
    traffic: dict  # the traffic mix's parameters
    end_to_end: tuple[dict, ...]  # metrics this cell reports, trace 0
    per_layer: tuple[dict, ...]  # metrics this cell reports, trace 1


def load_benchmark() -> dict:
    """The parsed ``BENCHMARK.json`` at the root of the checkout."""
    with open(ROOT / "BENCHMARK.json") as fh:
        return json.load(fh)


def _read_json(path: pathlib.Path, kind: str, name: str) -> dict:
    if not path.is_file():
        raise KeyError(f"no {kind} named {name!r} ({path} is missing)")
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str, bench: dict | None = None) -> Cell:
    """Resolve workload ``name`` to its configuration, traffic and metrics."""
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload named {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise KeyError(f"workload {name!r} names unknown configuration "
                       f"{w['config']!r}")
    config = _read_json(ROOT / configs[w["config"]]["file"],
                        "configuration", w["config"])
    traffic = _read_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json",
                         "traffic mix", w["traffic"])
    e2e = tuple(m for m in bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    for m in bench["per_layer"]:
        if "workloads" not in m:
            raise KeyError(f"per-layer metric {m['name']!r} lists no "
                           f"workloads")
    per_layer = tuple(m for m in bench["per_layer"]
                      if name in m["workloads"])
    for m in e2e + per_layer:
        metric_reader(m["name"])  # refuse a metric with no reader up front
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str):
    """The ``read(run)`` function of metric ``name``.

    ``read`` takes a ``harness.RunRecord`` and returns the metric's value,
    or None where the run holds nothing to read it from.
    """
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reader for metric {name!r} under "
                       f"{BENCH_DIR / 'metrics'}")
    mod_name = "bench_metric_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown device raises."""
    table = _read_json(BENCH_DIR / "peaks.json", "peak table", "peaks")
    if device_kind not in table["devices"]:
        raise KeyError(f"device {device_kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(table['devices'])})")
    return table["devices"][device_kind]


def use_compilation_cache() -> pathlib.Path:
    """Keep JAX's persistent compilation cache in ``.jax_cache`` at the root.

    The directory is made if missing (JAX writes no entry into a directory
    that does not exist), and every program is cached, so that only a
    checkout's first run compiles.
    """
    import jax

    path = ROOT / ".jax_cache"
    path.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
