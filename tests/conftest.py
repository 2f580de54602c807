"""Test fixtures: fake 8-chip mesh on CPU + isolated workspace.

Per SURVEY.md §4 the reference had no test suite; multi-worker paths
were only exercised on a live YARN cluster. We close that gap with the
fake-mesh fixture: 8 virtual CPU devices emulate an 8-chip slice
in-process, so every distributed code path (pjit shardings, collectives,
multi-chip launchers) runs in CI without TPU hardware.

JAX reads ``JAX_PLATFORMS`` when it is imported and ``XLA_FLAGS`` when
the backend starts, so both are set here at module scope, before
anything imports jax.
"""

import os
import subprocess
import warnings

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

from hops_tpu import native as _native  # noqa: E402

# Build the native engines up front: the .so is gitignored, so a fresh
# checkout starts without it, and tests that import native-backed modules
# (featurestore.online) run before test_native's own fixture would build it.
# A failed build is survivable (every native-backed module keeps a
# pure-Python fallback) but must be visible, not swallowed.
if not _native.lib_path().exists():
    _build = subprocess.run(
        ["make", "-C", str(_native.lib_path().parent)],
        capture_output=True, text=True,
    )
    if _build.returncode != 0:
        warnings.warn(
            f"native library build failed (rc={_build.returncode}); tests run "
            f"on the pure-Python fallbacks:\n{_build.stderr[-2000:]}",
            RuntimeWarning,
        )

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def workspace(tmp_path, monkeypatch):
    """Point the framework workspace at a per-test temp dir."""
    monkeypatch.setenv("HOPS_TPU_WORKSPACE", str(tmp_path / "workspace"))
    from hops_tpu.runtime import config

    config.configure(workspace=str(tmp_path / "workspace"), project="testproj")
    yield tmp_path / "workspace"


#: memory maps a worker may hold when a test file ends before its compiled
#: programs are dropped: a third of what Linux allows a process (65,530)
MAPS_BETWEEN_FILES = 20_000


@pytest.fixture(autouse=True, scope="module")
def compiled_programs_stay_under_the_map_limit():
    """XLA:CPU maps memory for every executable the process holds and a
    process may hold ``vm.max_map_count`` maps: past it a compile segfaults
    or aborts and the worker is lost with the rest of its file (PR 50 saw
    both in one run, in files that compile little). Files are dealt to the
    workers as they come free, so two that leave 40,000 maps each
    (``test_kda.py``, ``test_ling_flash.py``) can meet in one process."""
    yield
    try:
        with open("/proc/self/maps") as maps:
            held = sum(1 for _ in maps)
    except OSError:
        return
    if held > MAPS_BETWEEN_FILES:
        import jax

        jax.clear_caches()


# Steering the kernels from a test (the program has no option for either):


@pytest.fixture
def flash_kernel_at_any_length(monkeypatch):
    """Flash attention takes the Pallas kernel (interpreted on the CPU) at
    any key length a 128-multiple divides, not only from 1,536 keys up."""
    from hops_tpu.ops import attention

    monkeypatch.setattr(attention, "_XLA_FASTER_BELOW", 0)


def _interpret(monkeypatch, module, name):
    whole = getattr(module, name)
    monkeypatch.setattr(module, name, lambda *a, **kw: whole(*a, **{**kw, "interpret": True}))


@pytest.fixture
def scan_kernels_interpreted(monkeypatch):
    """The selective scan and the state-space-dual scan take their two
    Pallas kernels each through the interpreter, not their XLA twins."""
    from hops_tpu.ops import selective_scan, ssd

    _interpret(monkeypatch, selective_scan, "selective_scan")
    _interpret(monkeypatch, ssd, "ssd_scan")


@pytest.fixture
def delta_kernels_interpreted(monkeypatch):
    """The Kimi delta rule and the gated delta rule take their Pallas
    kernels through the interpreter, not their XLA twins."""
    from hops_tpu.ops import gated_delta, kda

    _interpret(monkeypatch, kda, "kda_rule")
    _interpret(monkeypatch, gated_delta, "gated_delta_rule")

