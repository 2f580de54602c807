#!/usr/bin/env python3
"""Record the small trace kept in ``benchmark/tests/recorded/`` (run on a chip).

Two steps of flash attention forward+backward and a matmul, with a host
sleep between them so that the trace holds an idle gap under a
``bench:sleep`` span. Writes ``<out>/small.xplane.pb`` and the
description of it a person reads first.
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmark.harness import trace_reduce
    from hops_tpu.ops.attention import flash_attention

    if jax.default_backend() != "tpu":
        print("record_small_trace: no TPU", file=sys.stderr)
        return 3
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (1, 2, 2048, 96), jnp.bfloat16) for kk in jax.random.split(key, 3))
    w = jax.random.normal(key, (1024, 1024), jnp.bfloat16)

    @jax.jit
    def step(q, k, v, w):
        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True, window=1024).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v), w @ w

    jax.block_until_ready(step(q, k, v, w))  # compile outside the trace
    tmp = Path(out) / "small_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench:dispatch"):
            r = step(q, k, v, w)
        jax.block_until_ready(r)
        with jax.profiler.TraceAnnotation("bench:sleep"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(str(tmp))
    dst = Path(out) / "small.xplane.pb"
    shutil.copy(src, dst)
    (Path(out) / "small.describe.txt").write_text(trace_reduce.describe(str(dst)))
    print(f"recorded {dst} ({dst.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out"))
