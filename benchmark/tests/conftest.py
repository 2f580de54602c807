"""Tests of the benchmark's own code: run with
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` from the repo root."""

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the four-chip driver runs once on four virtual CPU devices
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TESTS = Path(__file__).resolve().parent

TINY_BENCHMARK = {
    "configs": [
        {"name": "tiny-lm", "file": "benchmark/tests/configs/tiny-lm.json"},
        {"name": "tiny-resnet", "file": "benchmark/tests/configs/tiny-resnet.json"},
    ],
    "workloads": [
        {"name": "tiny-lm.train", "config": "tiny-lm", "traffic": "tiny-train-lm", "chips": 1},
        {"name": "tiny-lm.train-dp4", "config": "tiny-lm", "traffic": "tiny-train-lm-dp4", "chips": 4},
        {"name": "tiny-lm.chat", "config": "tiny-lm", "traffic": "tiny-chat", "chips": 1},
        {"name": "tiny-resnet.train", "config": "tiny-resnet", "traffic": "tiny-train-images", "chips": 1},
    ],
}


@pytest.fixture()
def bench_copy(tmp_path):
    """A copy of ``benchmark/`` (what git would commit of it) under a temp
    root, with the tiny traffic mixes added as NEW files and a tiny
    BENCHMARK.json beside it. No file of the copy is edited."""
    dst = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark", dst,
                    ignore=shutil.ignore_patterns(".cache", "out", "__pycache__"))
    for mix in (TESTS / "traffic").glob("*.json"):
        shutil.copy(mix, dst / "traffic" / mix.name)
    shutil.copy(TESTS / "traffic" / "tiny-train-lm.json", dst / "traffic" / "tiny-train-lm-dp4.json")
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    benchmark["configs"] = benchmark["configs"] + TINY_BENCHMARK["configs"]
    benchmark["workloads"] = benchmark["workloads"] + TINY_BENCHMARK["workloads"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return dst, benchmark
