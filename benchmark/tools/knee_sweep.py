#!/usr/bin/env python3
"""Find the knee of a serving cell once, on the chip: one set-up, then
the cell's mix at rates rising by 1.25x, each offered for ``--seconds``
and then drained.

A rate is sustained when no backlog builds: the median latency per
output token stays within 1.3x of the lowest swept rate's, and the
drain (last answer after the offering stops) within 1.25x of it. The
knee is the highest sustained rate. Start the sweep below the knee. The
cell's traffic file then gets 0.8 x the knee as ``rate_rps`` (written by
hand, with this sweep's rows in PERF.md). Judged at the client: the
engine's counters over ``GET /v1/models/<name>`` wait for the engine
loop's lock and arrive seconds late under load. Not part of a run of the
benchmark: ``python benchmark/tools/knee_sweep.py --workload <cell>``.
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
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--start", type=float, default=1.0)
    parser.add_argument("--factor", type=float, default=1.25)
    parser.add_argument("--max-rates", type=int, default=8)
    parser.add_argument("--out", default="chiprun_out/knee_sweep.json")
    args = parser.parse_args()

    from benchmark import run as bench_run
    from benchmark.harness import device, loader, stats
    from hops_tpu.runtime import compile_cache

    benchmark = loader.load_benchmark()
    compile_cache.enable()
    devices = device.require_tpu(int(loader.find_cell(benchmark, args.workload)["chips"]))
    ctx, driver = bench_run.build_context(
        benchmark, args.workload, seed=args.seed, seconds=args.seconds, trace=False,
        devices=devices, t_start=T_START)
    traffic = ctx.traffic
    endpoint = driver.Endpoint(ctx)
    endpoint.setup()
    ctx.mark_setup_done()
    rows, rate = [], args.start
    for k in range(args.max_rates):
        endpoint.traffic = dict(traffic, rate_rps=rate)  # the cell's mix at the swept rate
        offered = endpoint.offer(args.seconds, args.seed + k)
        summary = driver.summarize(offered, traffic)
        e2e = driver.end_to_end(summary)
        row = {"rate_rps": rate, "requests": summary["attempted"], "failed": summary["failed"],
               "offered_tokens_per_s": summary["offered_tokens_per_s"],
               "makespan_s": summary["makespan_s"], "drain_s": summary["makespan_s"] - args.seconds,
               "window_compiles": offered["window_compiles"],
               "late_p90_ms": stats.percentile(summary["late_ms"], 0.9),
               "engine": {k2: offered["engine_after"][k2] - offered["engine_before"][k2]
                          for k2 in ("dispatches", "tokens_emitted", "prefill_chunks", "preemptions")},
               **e2e}
        rows.append(row)
        row["sustained"] = bool(
            row["failed"] == 0
            and row["req_ms_per_token_p50"] <= 1.3 * rows[0]["req_ms_per_token_p50"]
            and row["drain_s"] <= 1.25 * rows[0]["drain_s"])
        ctx.note(json.dumps(row))
        if not row["sustained"]:  # past the knee: a higher rate only grows the backlog
            break
        rate *= args.factor
    endpoint.stop()
    good = [r["rate_rps"] for r in rows if r["sustained"]]
    result = {"workload": args.workload, "seconds": args.seconds, "rows": rows,
              "knee_rps": max(good) if good else None, "device": ctx.device_info()}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps({"knee_rps": result["knee_rps"], "device": result["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
