#!/usr/bin/env python3
"""Record the trace of a small LM training step kept in
``benchmark/tests/recorded/train_step.xplane.pb`` (run on a chip).

One transformer block at d_head 96 (2 heads, d_model 192, vocab 1,024,
one sequence of 2,048 tokens, window 1,024, chunked loss, Adam) trained
through ``experiment.mirrored`` -> ``Strategy.step`` ->
``make_lm_train_step``, so the trace holds what a training cell's does
at a size a test can keep: the program's scopes (``attn``, ``mlp``,
``embed``, ``final_norm``, ``lm_head_loss``, ``optimizer``) in the
``tf_op`` of its device operations, the three flash kernels under their
names, and the launcher's ``hops_tpu_train_input_put`` /
``hops_tpu_train_dispatch`` spans on the host. Two steps are traced
after two of warm-up. Writes ``<out>/train_step.xplane.pb`` (the planes
the readers read: the device's and the host's; ``/host:metadata``, two
thirds of the file, holds the program's HLO and is left out) and, beside
it, the listing by scope a person checks the test's values against.
"""

from __future__ import annotations

import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

STEPS = 2
KEPT_PLANES = ("/device:TPU:0", "/host:CPU")


def keep_planes(src: Path, dst: Path) -> None:
    """Copy the XSpace at ``src`` with only ``KEPT_PLANES``: top-level
    fields are copied byte for byte or dropped whole."""
    from benchmark.harness import trace_scopes as ts

    data = src.read_bytes()
    out, pos = bytearray(), 0
    for field, wire, a, end in ts.fields(data, 0, len(data)):  # end: the position after the field
        name = None
        if field == 1 and wire == 2:
            name = next((ts.text_of(data, a2, b2) for f2, w2, a2, b2 in ts.fields(data, a, end)
                         if f2 == 2 and w2 == 2), "")
        if name is None or name in KEPT_PLANES:
            out += data[pos:end]
        pos = end
    dst.write_bytes(bytes(out))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import trace_reduce, trace_scopes
    from hops_tpu import experiment
    from hops_tpu.models import common
    from hops_tpu.models.transformer import TransformerLM, make_lm_train_step
    from hops_tpu.parallel import get_strategy
    from hops_tpu.parallel import mesh as mesh_lib
    from hops_tpu.runtime import config as rt_config

    if jax.default_backend() != "tpu":
        print("record_train_step_trace: no TPU", file=sys.stderr)
        return 3
    out_dir = Path(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    tmp = out_dir / "train_step_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    model = TransformerLM(vocab_size=1024, d_model=192, num_heads=2, num_layers=1, window=1024,
                          dtype=jnp.bfloat16, attention_impl="flash", max_decode_len=2048)
    tokens = np.random.RandomState(0).randint(0, 1024, (1, 2049)).astype(np.int32)

    def train_fn():
        strategy = get_strategy()
        state = strategy.replicate(common.create_train_state(
            model, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32))
        step = strategy.step(make_lm_train_step(loss_chunk=512))

        def run(n, state):
            for _ in range(n):
                state, metrics = step(state, strategy.distribute_batch({"tokens": tokens}))
            return state, float(metrics["loss"])

        state, _ = run(2, state)  # compile and settle outside the trace
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp), profiler_options=options)
        state, loss = run(STEPS, state)
        jax.profiler.stop_trace()
        return {"loss": loss}

    rt_config.configure(workspace=str(out_dir / "ws-record"), project="record")
    with mesh_lib.device_scope(jax.devices()[:1]):
        experiment.mirrored(train_fn, name="record_train_step", metric_key="loss")
    dst = out_dir / "train_step.xplane.pb"
    keep_planes(Path(trace_reduce.find_xplane(str(tmp))), dst)
    listing = trace_scopes.describe(str(dst), limit=400)
    (out_dir / "train_step.scopes.txt").write_text(listing)
    print(listing[:6000])
    print(f"recorded {dst} ({dst.stat().st_size} bytes), {STEPS} steps")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out"))
