"""The four-chip training cell on four virtual CPU devices: a sound run
reads correct and reports every metric of its cell that a CPU run can
read, each fault planted underneath its timed path reads not correct. The
runs happen in one child process (`dp4_runs.py`), since JAX fixes its
device count when it starts."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import tiny

pytestmark = pytest.mark.timeout(900)
FAULTS = ["keys-wrong-device", "one-shard-grads", "one-shard-batch",
          "half-batch", "state-unchanged", "altered-loss"]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = tmp_path_factory.mktemp("root")
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.HERE, "dp4_runs.py"), str(root),
         "sound", *FAULTS],
        env=env, capture_output=True, text=True, timeout=840)
    assert p.returncode == 0, p.stderr[-4000:]
    return {r["fault"]: r for r in map(json.loads, p.stdout.splitlines())}


def test_a_sound_four_shard_run_is_correct(runs):
    out = runs["sound"]
    assert out["correct"], out["checks"]
    c = out["counters"]
    # every graph of all four shards counts: 4 x 2 a step in the tiny mix
    assert c["steps"] > 0 and c["graphs"] == 4 * 2 * c["sampler_steps"]
    assert c["executables"] == 0, c["window_executables"]


def test_a_sound_four_shard_run_reads_its_span_metrics(runs):
    metrics = runs["sound"]["metrics"]
    for name in ("batch_ms_per_step.train", "loop_ms_per_step.train",
                 "dispatch_ms_per_step.train", "sampler_ms_per_step.train"):
        assert metrics[name]["value"] > 0, name
    # no device plane on the CPU: the trace's device metrics stay silent
    for name in ("allreduce_ms_per_step.train", "step_device_ms.train",
                 "device_idle.train"):
        assert name not in metrics


@pytest.mark.parametrize("fault", FAULTS)
def test_a_four_shard_fault_is_not_correct(runs, fault):
    out = runs[fault]
    assert not out["correct"], out["checks"]
