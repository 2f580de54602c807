"""Per-host launcher CLI — ``python -m hops_tpu.launch [opts] script.py``.

The reference's launcher was a Spark driver scheduling wrapper functions
onto executors (SURVEY.md §3.1-3.2); on TPU every host must run the
same SPMD program, so the launcher becomes this thin per-host agent
(SURVEY.md §7 build stage 3 "launcher-owns-the-mesh"): it joins the
multi-host runtime (coordination service on host 0), pins the shared
run-session id, then hands the host to the user's script/module, whose
``experiment.*`` calls now see the full slice.

Usage (one invocation per host, e.g. via your pod scheduler):

    python -m hops_tpu.launch \
        --coordinator 10.0.0.2:1234 --num-processes 4 --process-id $IDX \
        train.py --epochs 10

Single-host runs need no flags: ``python -m hops_tpu.launch train.py``.
Flags may also come from JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
JAX_PROCESS_ID env vars (the GKE path auto-discovers and needs none).
"""

from __future__ import annotations

import argparse
import os
import runpy
import sys


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(
        prog="python -m hops_tpu.launch", description=__doc__.split("\n")[0]
    )
    parser.add_argument("--coordinator", default=os.environ.get("JAX_COORDINATOR_ADDRESS"))
    parser.add_argument(
        "--num-processes",
        type=int,
        default=int(os.environ["JAX_NUM_PROCESSES"]) if "JAX_NUM_PROCESSES" in os.environ else None,
    )
    parser.add_argument(
        "--process-id",
        type=int,
        default=int(os.environ["JAX_PROCESS_ID"]) if "JAX_PROCESS_ID" in os.environ else None,
    )
    parser.add_argument("-m", "--module", help="run a module instead of a script file")
    parser.add_argument("script", nargs="?", help="Python file to run on this host")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    if not args.module and not args.script:
        parser.error("provide a script file or -m module")

    # Both before the user code can touch the XLA backend: place the
    # persistent compile cache, then join the slice.
    from hops_tpu.parallel import multihost
    from hops_tpu.runtime import compile_cache

    compile_cache.enable()

    multihost.initialize(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )

    if args.module:
        sys.argv = [args.module, *([args.script] if args.script else []), *args.script_args]
        runpy.run_module(args.module, run_name="__main__", alter_sys=True)
    else:
        sys.argv = [args.script, *args.script_args]
        runpy.run_path(args.script, run_name="__main__")


if __name__ == "__main__":
    main()
