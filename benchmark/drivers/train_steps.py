"""Driver ``train_steps``: optimizer steps through the launcher, one call each.

The wrapper function runs inside ``experiment.mirrored`` over exactly the
cell's chips; every step is one ``strategy.step`` call on a fresh batch
taken round-robin from a seeded host pool and placed with
``strategy.distribute_batch``. The host syncs one loss every
``sync_every`` steps, one block behind the dispatch, so the device never
waits for the host to read a number. The clock runs from the first
dispatch after warm-up to ``block_until_ready`` on the last step.
"""

from __future__ import annotations

import json
import math
import time
from typing import Any


def run(ctx) -> dict[str, Any]:
    import jax

    from hops_tpu import experiment
    from hops_tpu.parallel import get_strategy
    from hops_tpu.parallel import mesh as mesh_lib
    from hops_tpu.runtime import config as rt_config

    cfg, traffic, adapter = ctx.config, ctx.traffic, ctx.adapter
    span = jax.profiler.TraceAnnotation
    out: dict[str, Any] = {}

    def train_fn():
        strategy = get_strategy()
        n_chips = strategy.num_replicas_in_sync
        if n_chips != len(ctx.devices):
            raise RuntimeError(f"strategy spans {n_chips} chips, cell asks for {len(ctx.devices)}")
        global_batch = int(traffic["per_chip_batch"]) * n_chips
        model = adapter.build_module(cfg)
        state = strategy.replicate(adapter.init_train_state(cfg, model, ctx.seed))
        step = strategy.step(adapter.make_step(cfg, traffic))
        pool = adapter.make_batches(cfg, traffic, global_batch, ctx.seed, int(traffic["batch_pool"]))
        items = adapter.items_per_step(traffic, global_batch)
        flops = adapter.flops_per_item(cfg, traffic, state.params)

        # correctness, outside the window and before the first (donating) step
        check = adapter.check_step0(cfg, traffic, model, state, ctx.seed, ctx.reference)
        ctx.note(f"step-0 check: {json.dumps(check)}")

        synced: list[float] = []
        n_dispatched = 0

        def dispatch(state):
            nonlocal n_dispatched
            with span("bench:dispatch"):
                batch = strategy.distribute_batch(pool[n_dispatched % len(pool)])
                state, metrics = step(state, batch)
            n_dispatched += 1
            return state, metrics["loss"]

        def sync(loss) -> None:
            with span("bench:wait_loss"):
                synced.append(float(loss))

        # warm-up: the cell's one program, every pool slot's placement path
        for _ in range(int(traffic["warmup_steps"])):
            state, loss = dispatch(state)
            sync(loss)
        warm_steps = n_dispatched
        setup_s = ctx.mark_setup_done()

        sync_every = int(traffic["sync_every"])
        block_ends: list[float] = []
        pending = None

        def run_blocks(until_s: float, state):
            """Blocks of ``sync_every`` steps until ``until_s`` has passed
            since the call; returns (state, seconds, steps)."""
            nonlocal pending
            t0 = time.perf_counter()
            start_steps = n_dispatched
            while time.perf_counter() - t0 < until_s:
                for _ in range(sync_every):
                    state, loss = dispatch(state)
                if pending is not None:
                    sync(pending)  # the block before: done, or nearly
                    block_ends.append(time.perf_counter())
                pending = loss
            sync(pending)
            pending = None
            jax.block_until_ready(state)
            block_ends.append(time.perf_counter())
            return state, time.perf_counter() - t0, n_dispatched - start_steps

        compiles_before = ctx.compiles()
        state, elapsed, steps = run_blocks(ctx.seconds, state)
        window_compiles = ctx.compiles() - compiles_before
        # between consecutive lagged syncs lie sync_every steps of device
        # time; the first and the last interval of the window are partial
        block_ms = [1e3 * (b - a) / sync_every for a, b in zip(block_ends, block_ends[1:-1])]
        # the step program's temporaries (activations, gradients), which
        # memory_stats() leaves out: asked of the compiler, outside the window
        temp_bytes = step.lower(state, strategy.distribute_batch(pool[0])).compile() \
            .memory_analysis().temp_size_in_bytes
        memory = ctx.device_info(temp_bytes)

        reduced = None
        if ctx.trace:
            block_ends.clear()
            ctx.start_trace()
            state, traced_s, traced_steps = run_blocks(float(traffic["trace_seconds"]), state)
            reduced = ctx.stop_trace()
            if reduced is not None:
                reduced["steps"] = traced_steps
                reduced["wall_s"] = traced_s

        first = synced[:5]
        out.update(
            setup_s=setup_s, window_s=elapsed, attempted=steps,
            failed=sum(1 for x in synced[warm_steps:] if not math.isfinite(x)),
            correct=bool(check["ok"] and all(math.isfinite(x) for x in synced)
                         and ctx.same_as_before("first_losses", first, rel_tol=1e-5)),
            device=memory,
            end_to_end={"train_items_per_s_chip": items * steps / elapsed / n_chips},
            counters={
                "n_chips": n_chips, "global_batch": global_batch, "items_per_step": items,
                "steps": steps, "warmup_steps": warm_steps, "window_compiles": window_compiles,
                "flops_per_item": flops, "item": adapter.ITEM,
                "attention_shapes": adapter.attention_shapes(cfg, traffic),
                "program_temp_bytes": int(temp_bytes),
            },
            client={"step_ms_blocks": block_ms, "first_losses": first,
                    "last_loss": synced[-1], "check": check},
            trace=reduced,
        )
        return {"loss": synced[-1]}

    # the launcher's run directories stay inside the checkout
    rt_config.configure(workspace=str(ctx.cache_dir / "ws-train"), project="bench")
    with mesh_lib.device_scope(ctx.devices):
        experiment.mirrored(train_fn, name=f"bench_{ctx.cell['name']}", metric_key="loss")
    return out
