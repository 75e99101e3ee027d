"""Tiny runs of the four-chip training cell on four virtual CPU devices,
each sound or with one fault planted underneath its timed path; one JSON
line per run on standard output.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 tests/bench/dp4_runs.py ROOT sound keys-wrong-device ...

ROOT is an empty directory that becomes the tiny checkout (`tiny.py`).
JAX fixes its device count when it starts, so the tests run this in a
child process of their own."""
from __future__ import annotations

import json
import sys
import time

import faults
import tiny

import run

WORKLOAD = "train-fusion-dp4"


# fault name -> (patch of the program's code, fault argument of run_cell)
FAULTS = {
    "sound": (None, None),
    "keys-wrong-device": (faults.wrong_device_keys, None),
    "one-shard-grads": (faults.one_shard_grads, None),
    "one-shard-batch": (None, {"batch": faults.one_shard_batch}),
    "half-batch": (None, {"batch": faults.half_batch}),
    "state-unchanged": (None, {"step": faults.unchanged}),
    "altered-loss": (None, {"step": faults.altered_loss}),
}


def main(argv) -> int:
    import common
    root = tiny.make_root(argv[0])
    # before JAX starts: the runs that share a program share its compiles
    common.enable_compile_cache(root)
    for name in argv[1:]:
        patch, fault = FAULTS[name]
        owner = None
        if patch is not None:
            owner, attr, fn = patch()
            real = getattr(owner, attr)
            setattr(owner, attr, fn)
        try:
            out = run.run_cell(root, WORKLOAD, 5, 1.5, int(name == "sound"),
                               require_tpu=False, fault=fault,
                               t0=time.monotonic())
        finally:
            if owner is not None:
                setattr(owner, attr, real)
        print(json.dumps({"fault": name, "correct": out["correct"],
                          "checks": out["checks"], "metrics": out["metrics"],
                          "counters": out["counters"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
