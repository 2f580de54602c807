#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one pass over the main path with a small dense LM
(d_model 1024, 8 heads so d_head 128, vocab 32,000, bf16, 4 layers,
weights random from a seed). It is go/no-go, not a yardstick: the
benchmark's cells (``benchmark/run.py``) are what is measured.

1. *kernels*: every ``pallas_call`` family in ``ops/attention.py`` and
   the gated delta rule's five kernels (``ops/gated_delta.py``) are
   compiled by Mosaic (``interpret=False``) at the main path's shapes
   and compared with its reference twin run in fp32 at highest matmul
   precision;
2. *train*: ``experiment.mirrored`` -> ``Strategy.step`` ->
   ``make_lm_train_step`` (flash attention + chunked cross-entropy), a
   few optimizer steps at sequence length 2048 over every chip JAX
   sees; the loss must be finite and fall;
3. *serve*: the trained module saved with ``registry.save_flax``,
   ``serving.create_or_update(model_server="LM", lm_config={"kv_page_size": 64, ...})``,
   ``serving.start`` hosted in this process, concurrent
   ``make_inference_request`` calls with ragged prompts (some longer
   than ``prefill_chunk``) -> ``LMEnginePredictor`` -> paged
   ``LMEngine`` -> ``paged_decode_attention`` on one chip.

It exits non-zero, printing no result line, when JAX's default backend
is not ``tpu``, and when any phase fails. It never sets
``jax_platforms``. The last line of its standard output is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``; the
``[phase]``/``[kernel]``/``[report]`` lines before it carry the
per-phase wall times, compile-cache counts, compile seconds by phase
(trace, lower, backend: the program's ``hops_tpu_compile_seconds``) and
readings.
"""

from __future__ import annotations

import functools
import json
import sys
import tempfile
import threading
import time
import traceback

SEED = 0
# A small dense LM at d_head 128.
VOCAB, D_MODEL, NUM_HEADS, NUM_LAYERS = 32000, 1024, 8, 4
D_HEAD = D_MODEL // NUM_HEADS
# Train: seq 2048 so the Pallas flash kernels run, not the sub-1536 XLA route.
SEQ_LEN, PER_CHIP_BATCH, TRAIN_STEPS, LOSS_CHUNK = 2048, 4, 6, 512
# Serve: paged engine, 64-token pages, prompts on both sides of the chunk.
SERVE_CAP, PAGE, PREFILL_CHUNK, SLOTS = 512, 64, 64, 4
PROMPT_LENS, MAX_NEW_TOKENS = (9, 40, 100, 170), 12
# Kernel checks: dense decode capacity, and the tolerance every family
# must meet — max |kernel - reference| over max |reference|. bf16 keeps
# 8 mantissa bits (2^-8 = 0.4 %); 2 % leaves room for the rounding of
# the probabilities and of the output, not for a wrong mask or scale.
KERNEL_CAP, KERNEL_TOL = 2048, 2e-2
INTERPRET = False  # Mosaic compiles every kernel here


def _rel_err(got, ref) -> float:
    import jax.numpy as jnp

    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-6))


def check_kernels() -> list[dict]:
    """Compile each pallas_call family once and compare with its
    reference twin. Returns one row per family; ``ok`` is False when it
    did not compile, was not finite, or missed :data:`KERNEL_TOL`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hops_tpu.ops import attention as A

    rs = np.random.RandomState(SEED)
    f32 = jnp.float32

    def rand(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(rs.randn(*shape), dtype)

    def exact(fn, *args, **kw):
        """The reference twin on fp32 inputs at highest matmul precision
        (the TPU default would round its matmuls through bf16 too)."""
        with jax.default_matmul_precision("highest"):
            return fn(*[a.astype(f32) if a.dtype == jnp.bfloat16 else a for a in args], **kw)

    rows: list[dict] = []

    def family(name, run, ref):
        t0 = time.perf_counter()
        row = {"family": name, "ok": False}
        try:
            got = jax.block_until_ready(jax.jit(run)())
            row["compile_and_run_s"] = round(time.perf_counter() - t0, 2)
            want = ref()
            errs = [_rel_err(g, w) for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
            row["max_rel_err"] = round(max(errs), 5)
            row["ok"] = bool(np.isfinite(errs).all() and max(errs) <= KERNEL_TOL)
        except Exception as e:  # noqa: BLE001 — a Mosaic refusal is this row's result
            row["error"] = f"{type(e).__name__}: {str(e)[:600]}"
        rows.append(row)
        print(f"[kernel] {json.dumps(row)}", flush=True)

    # -- flash attention: forward + the backward kernel ----------------------
    b = 2
    q, k, v = (rand(b, NUM_HEADS, SEQ_LEN, D_HEAD) for _ in range(3))
    ct = rand(b, NUM_HEADS, SEQ_LEN, D_HEAD, dtype=f32)  # output cotangent

    def flash_loss(attn, window):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v, causal=True, window=window).astype(f32) * ct)
        return loss

    for window, name in ((None, f"flash fwd+bwd causal seq {SEQ_LEN}"),
                         (512, f"flash fwd+bwd causal seq {SEQ_LEN} window 512")):
        kernel = functools.partial(A.flash_attention, interpret=INTERPRET)
        family(
            name,
            lambda kernel=kernel, window=window: (
                kernel(q, k, v, causal=True, window=window),
                jax.grad(flash_loss(kernel, window), argnums=(0, 1, 2))(q, k, v),
            ),
            lambda window=window: (
                exact(A.attention_reference, q, k, v, causal=True, window=window),
                exact(jax.grad(flash_loss(A.attention_reference, window), argnums=(0, 1, 2)), q, k, v),
            ),
        )

    # -- dense decode: bf16 and int8 caches, ragged valid_len ---------------
    bd = 4
    vl = jnp.asarray([KERNEL_CAP, KERNEL_CAP // 2 - 24, 129, 0], jnp.int32)
    qd = rand(bd, NUM_HEADS, 1, D_HEAD)
    kc, vc = (rand(bd, NUM_HEADS, KERNEL_CAP, D_HEAD) for _ in range(2))
    family(
        f"decode bf16 cap {KERNEL_CAP}",
        lambda: A.decode_attention(qd, kc, vc, vl, interpret=INTERPRET),
        lambda: exact(A.decode_attention_reference, qd, kc, vc, vl),
    )
    (k8, ks), (v8, vs) = A.quantize_kv(kc), A.quantize_kv(vc)
    family(
        f"decode int8 cap {KERNEL_CAP}",
        lambda: A.decode_attention(qd, k8, v8, vl, k_scale=ks, v_scale=vs, interpret=INTERPRET),
        lambda: exact(A.decode_attention_reference, qd,
                      A.dequantize_kv(k8, ks), A.dequantize_kv(v8, vs), vl),
    )

    # -- paged decode: page 64, single token and a prefill chunk ------------
    max_blocks = KERNEL_CAP // PAGE
    nblocks = 1 + bd * max_blocks
    pages = jnp.asarray(
        1 + rs.permutation(bd * max_blocks).reshape(bd, max_blocks), jnp.int32
    )
    kp, vp = (rand(NUM_HEADS, nblocks, PAGE, D_HEAD) for _ in range(2))
    (kp8, kps), (vp8, vps) = A.quantize_kv(kp), A.quantize_kv(vp)
    for s in (1, PREFILL_CHUNK):
        qp = rand(bd, NUM_HEADS, s, D_HEAD)
        # valid_len counts the chunk itself; the last row is a free slot.
        vlp = jnp.asarray([KERNEL_CAP, KERNEL_CAP // 2 - 24, 129 + s, 0], jnp.int32)
        family(
            f"paged decode bf16 page {PAGE} s={s}",
            lambda qp=qp, vlp=vlp: A.paged_decode_attention(
                qp, kp, vp, vlp, pages, interpret=INTERPRET),
            lambda qp=qp, vlp=vlp: exact(
                A.paged_decode_attention_reference, qp, kp, vp, vlp, pages),
        )
        family(
            f"paged decode int8 page {PAGE} s={s}",
            lambda qp=qp, vlp=vlp: A.paged_decode_attention(
                qp, kp8, vp8, vlp, pages, k_scale=kps, v_scale=vps,
                interpret=INTERPRET),
            lambda qp=qp, vlp=vlp: exact(
                A.paged_decode_attention_reference, qp,
                A.dequantize_kv(kp8, kps), A.dequantize_kv(vp8, vps), vlp, pages),
        )
    # -- gated delta rule: the five kernels, forward and all five gradients --
    from hops_tpu.ops.gated_delta import gated_delta_rule

    gh, gs, gk, gv = 10, 2048, 96, 192  # a head group of the hybrid cell's linear layers, 32 chunks
    gq, gkey = (rand(1, gh, gs, gk, dtype=f32) for _ in range(2))
    gq = gq / jnp.linalg.norm(gq, axis=-1, keepdims=True) / np.sqrt(gk)
    gkey = gkey / jnp.linalg.norm(gkey, axis=-1, keepdims=True)
    gval, gct = (rand(1, gh, gs, gv, dtype=f32) for _ in range(2))
    log_alpha = -jnp.exp(jnp.asarray(rs.uniform(np.log(1e-3), np.log(1e-1), (1, gh, gs)), f32))
    gbeta = jnp.asarray(rs.uniform(0, 2, (1, gh, gs)), f32)

    def rule_and_grads(**route):
        def loss(*args):
            return jnp.sum(gated_delta_rule(*args, **route) * gct)
        args = (gq, gkey, gval, log_alpha, gbeta)
        return gated_delta_rule(*args, **route), jax.grad(loss, argnums=range(5))(*args)

    family(
        f"gated delta rule fwd+bwd seq {gs} float32",
        lambda: rule_and_grads(interpret=INTERPRET),
        # the XLA twin: batched matmuls at highest precision round a lax.scan, differentiated by jax.grad
        lambda: rule_and_grads(custom_backward=False),
    )
    return rows


def _mosaic_calls(jitted, *args, **kwargs) -> int:
    """Mosaic custom calls in the program XLA compiles for ``jitted`` —
    read from the compiled HLO text, not from configuration (an
    interpreted kernel lowers to plain HLO ops and counts zero)."""
    text = jitted.lower(*args, **kwargs).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def _abstract(tree):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), tree)


def _lm_model():
    import jax.numpy as jnp

    from hops_tpu.models.transformer import TransformerLM

    return TransformerLM(
        vocab_size=VOCAB, d_model=D_MODEL, num_heads=NUM_HEADS,
        num_layers=NUM_LAYERS, dtype=jnp.bfloat16, attention_impl="flash",
        max_decode_len=SERVE_CAP,
    )


def train() -> dict:
    """A few optimizer steps of the LM on every chip, through the
    launcher a user calls. Returns the trained params (host arrays)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hops_tpu import experiment
    from hops_tpu.models import common
    from hops_tpu.models.transformer import make_lm_train_step
    from hops_tpu.parallel import get_strategy

    out: dict = {}

    def train_fn():
        strategy = get_strategy()
        n_chips = strategy.num_replicas_in_sync
        model = _lm_model()
        init = jax.jit(functools.partial(
            common.create_train_state, model, input_shape=(1, 8),
            input_dtype=jnp.int32, learning_rate=3e-4,
        ))
        state = strategy.replicate(init(jax.random.PRNGKey(SEED)))
        step = strategy.step(make_lm_train_step(loss_chunk=LOSS_CHUNK))
        tokens = np.random.RandomState(SEED).randint(
            0, VOCAB, (PER_CHIP_BATCH * n_chips, SEQ_LEN + 1)).astype(np.int32)
        batch = strategy.distribute_batch({"tokens": tokens})
        shapes = _abstract((state, batch))
        losses, times = [], []
        for _ in range(TRAIN_STEPS):
            t0 = time.perf_counter()
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))  # waits for the step
            times.append(time.perf_counter() - t0)
        out.update(
            n_chips=n_chips,
            global_batch=int(tokens.shape[0]),
            losses=[round(x, 4) for x in losses],
            first_step_s=round(times[0], 2),
            step_ms=round(1e3 * min(times[1:]), 1),
            mosaic_calls=_mosaic_calls(step, *shapes),
            params=jax.device_get(state.params),
        )
        return {"loss": losses[-1]}

    experiment.mirrored(train_fn, name="chip_smoke_lm", metric_key="loss")
    losses = out["losses"]
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"train loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"train loss did not fall: {losses}")
    # 3 flash kernels (fwd, dq, dk/dv) per layer.
    if out["mosaic_calls"] < 3 * NUM_LAYERS:
        raise RuntimeError(
            f"train step holds {out['mosaic_calls']} Mosaic custom calls, "
            f"expected {3 * NUM_LAYERS}")
    return out


def serve(params) -> dict:
    """Serve the trained module through the LM endpoint and check every
    response; failures the serving path would catch and log (per-ticket
    engine errors, a failed start) are checked here explicitly."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hops_tpu.modelrepo import registry, serving
    from hops_tpu.models.generation import generate
    from hops_tpu.telemetry.metrics import REGISTRY

    name = "chip_smoke_lm"
    model = _lm_model()
    registry.save_flax(model, params, name)
    serving.create_or_update(
        name, model_name=name, model_server="LM",
        lm_config={"kv_page_size": PAGE, "prefill_chunk": PREFILL_CHUNK,
                   "slots": SLOTS},
    )
    t0 = time.perf_counter()
    serving.start(name)
    out: dict = {"start_s": round(time.perf_counter() - t0, 2)}
    try:
        if serving.get_status(name) != "Running":
            raise RuntimeError(f"serving status {serving.get_status(name)!r}")
        rs = np.random.RandomState(SEED + 1)
        prompts = [rs.randint(0, VOCAB, (n,)).tolist() for n in PROMPT_LENS]
        results: list = [None] * len(prompts)

        def client(i: int) -> None:
            t = time.perf_counter()
            try:
                # make_inference_request raises on any status but 200.
                resp = serving.make_inference_request(name, {"instances": [
                    {"prompt": prompts[i], "max_new_tokens": MAX_NEW_TOKENS}]})
                results[i] = (resp["predictions"][0], time.perf_counter() - t)
            except Exception as e:  # noqa: BLE001 — re-raised below, by prompt
                results[i] = e

        threads = [threading.Thread(target=client, args=(i,)) for i in range(len(prompts))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        out["requests_s"] = round(time.perf_counter() - t0, 2)
        streams = []
        for n, res in zip(PROMPT_LENS, results):
            if not isinstance(res, tuple):
                raise RuntimeError(f"request with a {n}-token prompt failed: {res!r}")
            toks = res[0]
            if len(toks) != MAX_NEW_TOKENS or not all(
                    isinstance(t, int) and 0 <= t < VOCAB for t in toks):
                raise RuntimeError(f"bad response for the {n}-token prompt: {toks}")
            streams.append(toks)
        out["request_s"] = [round(r[1], 2) for r in results]

        # A second, warm request: nothing left to compile.
        t0 = time.perf_counter()
        again = serving.make_inference_request(name, {"instances": [
            {"prompt": prompts[0], "max_new_tokens": MAX_NEW_TOKENS}]})
        out["warm_request_s"] = round(time.perf_counter() - t0, 3)
        out["repeat_identical"] = again["predictions"][0] == streams[0]

        # The engine catches a failed dispatch per ticket and keeps
        # serving; its counter must not have moved.
        stats = serving.get_model_status(name)["engine"]
        failures = REGISTRY.get("hops_tpu_lm_dispatch_failures_total").value()
        out["engine"] = {"dispatch_failures": failures, **{k: stats[k] for k in (
            "dispatches", "tokens_emitted", "prefill_chunks", "preemptions",
            "blocks_peak_used", "blocks_total", "cache_layout")}}
        if failures:
            raise RuntimeError(f"engine recorded {failures} failed dispatches")
        if stats["cache_layout"] != "paged" or not stats["blocks_peak_used"]:
            raise RuntimeError(f"block pool never used: {stats}")
        if stats["prefill_chunks"] <= len(prompts):
            raise RuntimeError(
                f"no prompt was prefilled in more than one chunk: {stats}")

        # The engine's decode program (the mixed program at width 1),
        # lowered for the live engine's own params and cache.
        engine = serving._servers[name].predictor._engine
        vec = lambda dt: jax.ShapeDtypeStruct((SLOTS,), dt)  # noqa: E731
        out["decode_mosaic_calls"] = _mosaic_calls(
            engine._paged_mixed, _abstract(engine.params), engine._cache_tmpl,
            jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32), vec(jnp.int32),
            vec(jnp.int32), vec(jnp.float32), vec(jnp.int32), vec(jnp.float32),
            vec(jnp.int32), vec(jnp.int32), sampled=False, nucleus=False)
        # One paged_decode_attention call per layer.
        if out["decode_mosaic_calls"] < NUM_LAYERS:
            raise RuntimeError(
                f"decode program holds {out['decode_mosaic_calls']} Mosaic "
                f"custom calls, expected {NUM_LAYERS}")

        # Reported, not asserted: greedy agreement with generate().
        matches = []
        for prompt, toks in zip(prompts, streams):
            ref = generate(
                model, params, jnp.asarray([prompt], jnp.int32),
                jax.random.PRNGKey(0), max_new_tokens=MAX_NEW_TOKENS,
                temperature=0.0)
            ref = np.asarray(ref)[0, len(prompt):].tolist()
            matches.append(sum(int(a == b) for a, b in zip(toks, ref)))
        out["generate_match"] = [f"{m}/{MAX_NEW_TOKENS}" for m in matches]
    finally:
        serving.stop(name)
    return out


def main() -> int:
    t_start = time.perf_counter()
    import jax

    from hops_tpu.runtime import compile_cache, config

    cache_dir = compile_cache.enable()  # before first use of the backend
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} count={device['count']} "
          f"compile_cache={cache_dir}", flush=True)
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (jax.default_backend() == "
              f"{jax.default_backend()!r}); this script only runs on a chip",
              file=sys.stderr)
        return 1

    phases: dict = {}
    failed: list[str] = []

    def phase(name, fn, *args):
        before, t0 = compile_cache.stats(), time.perf_counter()
        compiling = compile_cache.compile_seconds()
        try:
            result = fn(*args)
        except Exception:  # noqa: BLE001 — every phase runs; any failure fails the run
            traceback.print_exc()
            failed.append(name)
            result = None
        after = compile_cache.stats()
        phases[name] = {
            "wall_s": round(time.perf_counter() - t0, 2),
            "compile_cache": {k: after[k] - before[k] for k in after},
            # the program's own histogram (hops_tpu_compile_seconds{phase}), which the
            # benchmark's set-up metrics read as spans: trace, lower, backend seconds
            "compile_s": {k: round(v - compiling[k], 2) for k, v in compile_cache.compile_seconds().items()},
        }
        print(f"[phase] {name}: {json.dumps(phases[name])}", flush=True)
        return result

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ws_") as ws:
        config.configure(workspace=ws, project="chip_smoke")
        kernels = phase("kernels", check_kernels)
        if kernels is not None:
            phases["kernels"]["families"] = kernels
            if not all(r["ok"] for r in kernels):
                failed.append("kernels")
        trained = phase("train", train)
        if trained is not None:
            params = trained.pop("params")
            phases["train"].update(trained)
            served = phase("serve", serve, params)
            if served is not None:
                phases["serve"].update(served)
        else:
            failed.append("serve")  # nothing to serve

    total = compile_cache.stats()
    print(f"[total] wall {time.perf_counter() - t_start:.1f}s compile cache "
          f"{json.dumps(total)} ({'warm' if total['hits'] and not total['writes'] else 'cold'})",
          flush=True)
    print(f"[report] {json.dumps(phases)}", flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases: {sorted(set(failed))}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
