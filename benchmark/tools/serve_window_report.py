#!/usr/bin/env python3
"""One run of a serving cell that ``BENCHMARK.json`` does not list yet, and
what its engine says of the window: the cell is made here, in memory, from
a configuration file and a traffic mix that are in the repository
(``--config phi3-mini --traffic chat-steady`` is ``phi3-mini.serve-chat``),
run exactly as ``benchmark/run.py`` runs a listed cell, and written out with
the window's iterations by ``kind``, their phase means and, for a traced
run, the chip's idle seconds by engine phase
(``harness/engine_spans.py``). Two runs on one seed put side by side say
what differs between two runs of one schedule. Not part of a run of the
benchmark: ``python benchmark/tools/serve_window_report.py --config
phi3-mini --traffic chat-steady --seed 1 --seconds 51 --out
chiprun_out/serve/a.json``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--traffic", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()

    from benchmark import run as bench_run
    from benchmark.harness import device, engine_spans, loader
    from hops_tpu.runtime import compile_cache

    workload = f"{args.config}.{args.traffic}"
    benchmark = {
        "configs": [{"name": args.config, "file": f"benchmark/configs/{args.config}.json"}],
        "workloads": [{"name": workload, "config": args.config, "traffic": args.traffic, "chips": 1}],
    }
    compile_cache.enable()
    devices = device.require_tpu(1)
    record = bench_run.run_cell(benchmark, workload, seed=args.seed, seconds=args.seconds,
                                trace=bool(args.trace), devices=devices, t_start=T_START)
    report = {
        "workload": workload, "seed": args.seed, "seconds": args.seconds, "traced": bool(args.trace),
        "correct": record["correct"], "attempted": record["attempted"], "failed": record["failed"],
        "setup_s": record["setup_s"], "device": record["device"],
        "end_to_end": record["end_to_end"], "per_layer": record["per_layer"],
        "engine_delta": record["counters"]["engine_delta"],
        "window": engine_spans.summary(record),
        "idle_by_phase_s": engine_spans.idle_by_phase(record, loader.BENCH_DIR),
        # every span of the window, for what the summary cannot say
        # (start_s: the ring's clock, time.time())
        **{kind: [dict(s.attrs, start_s=s.start, duration_ms=1e3 * s.duration_s) for s in spans]
           for kind, spans in (engine_spans.window(record) or {"requests": [], "iterations": []}).items()},
    }
    if record.get("trace"):
        report["trace"] = {k: record["trace"][k] for k in ("busy_s", "window_s", "idle_pct", "idle_gaps", "device_ops")}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(report, indent=1, default=str))
    print(json.dumps({k: report[k] for k in ("correct", "attempted", "failed", "end_to_end", "per_layer",
                                             "engine_delta", "window", "idle_by_phase_s", "device")},
                     default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
