"""Find a cell's pieces by the names BENCHMARK.json gives them.

Everything that belongs to one configuration, one traffic mix, one
driver, one adapter or one per-layer metric sits in a file of its own.
A later PR adds files and ``BENCHMARK.json`` entries and edits nothing
that exists; nothing here lists names.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any

BENCH_DIR = Path(__file__).resolve().parents[1]


def load_benchmark(bench_dir: Path = BENCH_DIR) -> dict[str, Any]:
    return json.loads((bench_dir.parent / "BENCHMARK.json").read_text())


def find_cell(benchmark: dict[str, Any], name: str) -> dict[str, Any]:
    for cell in benchmark["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(
        f"no workload {name!r} in BENCHMARK.json "
        f"(has: {[c['name'] for c in benchmark['workloads']]})")


def load_config(benchmark: dict[str, Any], name: str, bench_dir: Path = BENCH_DIR) -> dict[str, Any]:
    for entry in benchmark["configs"]:
        if entry["name"] == name:
            cfg = json.loads((bench_dir.parent / entry["file"]).read_text())
            cfg["name"] = name
            return cfg
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict[str, Any]:
    path = bench_dir / "traffic" / f"{name}.json"
    if not path.exists():
        raise FileNotFoundError(f"traffic mix {name!r}: no {path}")
    traffic = json.loads(path.read_text())
    if "same_as" in traffic:  # another mix's parameters under a name of its own
        traffic = {**load_traffic(traffic.pop("same_as"), bench_dir), **traffic}
    traffic["name"] = name
    return traffic


def load_module(kind: str, name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """Import ``<bench_dir>/<kind>/<name>.py`` by path (``kind`` is
    drivers, adapters, layer_metrics, kernels or reference)."""
    path = bench_dir / kind / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"{kind[:-1] if kind.endswith('s') else kind} {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}".replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_metric_readers(bench_dir: Path = BENCH_DIR) -> dict[str, ModuleType]:
    """Every reader under ``layer_metrics/``, keyed by metric name (the
    file's stem)."""
    return {
        p.stem: load_module("layer_metrics", p.stem, bench_dir)
        for p in sorted((bench_dir / "layer_metrics").glob("*.py"))
    }


def metrics_for_cell(benchmark: dict[str, Any], section: str, cell: str) -> list[dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries that apply to ``cell``
    (an entry without ``workloads`` applies to every cell)."""
    return [m for m in benchmark[section] if cell in m.get("workloads", [cell])]
