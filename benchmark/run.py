#!/usr/bin/env python3
"""One run of one cell: ``python benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

A new process that finds the cell's configuration, traffic mix, driver,
adapter and reference by name, refuses to run without the chips the cell
asks for, sets up (weights from the seed on the device, compile cache,
warm-up of the cell's shapes), measures for ``--seconds``, checks the
program's outputs against the plain reference outside the window, and
prints one JSON object as the last line of its standard output. With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Everything else worth keeping goes
on earlier lines and into ``benchmark/out/<workload>-<seed>.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from process start

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def build_context(benchmark: dict[str, Any], workload: str, *, seed: int, seconds: float, trace: bool,
                  devices: list[Any], bench_dir: Path = BENCH_DIR, t_start: float = T_START):
    """The cell's pieces, found by name, and the driver that runs them."""
    from benchmark.harness import loader
    from benchmark.harness.compile_events import CompileCounter
    from benchmark.harness.context import RunContext

    cell = loader.find_cell(benchmark, workload)
    config = loader.load_config(benchmark, cell["config"], bench_dir)
    traffic = loader.load_traffic(cell["traffic"], bench_dir)
    ctx = RunContext(
        cell=cell, config=config, traffic=traffic,
        adapter=loader.load_module("adapters", config["adapter"], bench_dir),
        reference=loader.load_module("reference", config["reference"], bench_dir),
        devices=devices, seed=seed, seconds=seconds, trace=trace,
        cache_dir=bench_dir / ".cache", t_start=t_start, counter=CompileCounter().install())
    ctx.cache_dir.mkdir(exist_ok=True)
    return ctx, loader.load_module("drivers", traffic["driver"], bench_dir)


def run_cell(benchmark: dict[str, Any], workload: str, *, bench_dir: Path = BENCH_DIR,
             **run: Any) -> dict[str, Any]:
    """Run one cell and return its record (every metric the run produced,
    not yet cut to the result line). ``main`` gives it TPU chips; the CPU
    rehearsals in ``benchmark/tests`` give it CPU devices and never print
    what comes back as a result."""
    from benchmark.harness import loader

    ctx, driver = build_context(benchmark, workload, bench_dir=bench_dir, **run)
    record = driver.run(ctx)
    record.update(workload=workload, seed=ctx.seed, seconds=ctx.seconds, traced=ctx.trace,
                  config=ctx.config["name"], traffic=ctx.traffic["name"])
    record["per_layer"] = {}
    for name, reader in loader.layer_metric_readers(bench_dir).items():
        value = reader.read(record)
        if value is not None:
            record["per_layer"][name] = value
    return record


def result_line(benchmark: dict[str, Any], record: dict[str, Any]) -> dict[str, Any]:
    """The contract's object: the cell's end-to-end metrics without a
    trace, its per-layer metrics with one."""
    from benchmark.harness import loader

    section = "per_layer" if record["traced"] else "end_to_end"
    values = dict(record["per_layer"]) if record["traced"] else dict(record["end_to_end"], setup_s=record["setup_s"])
    metrics = {}
    for m in loader.metrics_for_cell(benchmark, section, record["workload"]):
        if m["name"] in values:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    device = dict(record["device"])
    line: dict[str, Any] = {
        "correct": bool(record["correct"]), "attempted": int(record["attempted"]),
        "failed": int(record["failed"]), "metrics": metrics, "device": device,
    }
    reduced = record.get("trace")
    if record["traced"] and reduced is not None:
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
    return line


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    try:
        import hops_tpu  # noqa: F401 — the system under test
    except ImportError:
        print(f"benchmark: the program (hops_tpu) is not in {ROOT}; nothing to measure",
              file=sys.stderr)
        return 3
    from benchmark.harness import device, loader
    from hops_tpu.runtime import compile_cache

    benchmark = loader.load_benchmark()
    cell = loader.find_cell(benchmark, args.workload)
    cache = compile_cache.enable()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    devices = device.require_tpu(int(cell["chips"]))
    print(f"[bench] {args.workload} seed={args.seed} seconds={args.seconds} trace={args.trace} "
          f"devices={len(devices)}x{devices[0].device_kind!r} compile_cache={cache}", flush=True)

    record = run_cell(benchmark, args.workload, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices)
    record["compile_cache"] = compile_cache.stats()
    line = result_line(benchmark, record)
    if record["traced"] and "busy_s" not in line["device"]:
        print("benchmark: the traced run holds no device operation", file=sys.stderr)
        return 4
    out_dir = BENCH_DIR / "out"
    out_dir.mkdir(exist_ok=True)
    kept = {k: v for k, v in record.items() if k != "trace"}
    if record.get("trace") is not None:
        kept["trace"] = {k: v for k, v in record["trace"].items() if k != "ops"}
    suffix = "-trace" if record["traced"] else ""
    (out_dir / f"{args.workload}-{args.seed}{suffix}.json").write_text(json.dumps(kept, indent=1, default=str))
    print(f"[bench] end_to_end={json.dumps(record['end_to_end'])} setup_s={record['setup_s']:.2f} "
          f"counters={json.dumps(record['counters'], default=str)}", flush=True)
    print(f"[bench] per_layer={json.dumps(record['per_layer'])}", flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
