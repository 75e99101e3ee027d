"""Data-parallel mesh training: scaling efficiency, sharded-stream
identity, cross-layout checkpoint restore (DESIGN.md §13).

Three properties of the mesh train step, each a machine-readable gate in
BENCH_scaling.json:

  1. scaling efficiency   — train-step throughput at dp=2 over dp=1, both
                            on forced host CPU devices
                            (XLA_FLAGS=--xla_force_host_platform_device_
                            count). dp=2 consumes two per-device batches
                            per step, so perfect scaling is 2.0x and the
                            gate is 1.7x (>= 85% per-device efficiency).
  2. stream identity      — the union of `StreamingCorpus.shard(i, W)`
                            worker views, position-interleaved, is
                            byte-identical to the unsharded stream (same
                            record keys, same runtime arrays, disjoint,
                            exhaustive) for several W.
  3. checkpoint elasticity — a checkpoint written while training under
                            dp=2 restores under dp=1 with bit-exact params
                            at the saved step.

Like bench_corpus, the scaling threshold is calibrated, not assumed:
`cpu_count` lies on quota'd containers, so the bench first measures the
host's parallel capacity with fork-pool spin workers (before jax loads)
and gates at min(1.7, max(1.0, 0.85 * capacity)) — multi-core CI runners
(capacity ~3-4) get the full 1.7x gate; a 1-core dev box degrades to
"two devices must not be slower than their work serialized". Step times
are interleaved best-of-2 trials. The measured capacity and threshold are
recorded in BENCH_scaling.json.

Every training/restore measurement runs in a subprocess (this file
re-invokes itself with --worker) because the forced device count is fixed
at jax import; the parent stays jax-free until the corpus pools and spin
workers are done.

`BENCH_SCALE` scales the number of timed steps (model and batch shapes
are fixed — scaling efficiency at a smaller model would measure dispatch
overhead, not the data path).

  PYTHONPATH=src python benchmarks/bench_scaling.py
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

SCALE = float(os.environ.get("BENCH_SCALE", "1.0"))
WARM_STEPS = 3
TIMED_STEPS = max(int(16 * SCALE), 6)
STREAM_PROGRAMS = max(int(12 * SCALE), 8)
STREAM_WORKER_COUNTS = (2, 3, 5)
EFF_CAP = 1.7             # >= 85% of perfect 2.0x
KERNEL_NODES = (24, 30, 28, 22, 26, 32, 20, 34)


# ---------------------------------------------------------------- capacity
def _spin(seconds: float) -> int:
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < seconds:
        n += 1
    return n


def parallel_capacity(workers: int, window: float = 0.5) -> float:
    """Measured speedup ceiling of this host (see bench_corpus): total
    spin throughput of `workers` fork-pool processes over one process's."""
    import multiprocessing
    one = _spin(window)
    with multiprocessing.get_context("fork").Pool(workers) as pool:
        many = sum(pool.map(_spin, [window] * workers))
    return many / max(one, 1)


# ---------------------------------------------------------------- worker
def _build_sampler():
    """Deterministic training set shared by every worker invocation."""
    from repro.core.simulator import TPUSimulator
    from repro.data.synthetic import random_kernel
    from repro.data.tile_dataset import build_tile_records, \
        fit_tile_normalizer
    from repro.data.sampler import TileBatchSampler

    sim = TPUSimulator()
    kernels = [random_kernel(n, seed=i)
               for i, n in enumerate(KERNEL_NODES)]
    recs = build_tile_records(kernels, sim, max_configs_per_kernel=16)
    norm = fit_tile_normalizer(recs)
    return TileBatchSampler(recs, norm, seed=3, adjacency="sparse",
                            kernels_per_batch=4, configs_per_kernel=8)


def _params_sha(params) -> str:
    import jax
    import numpy as np
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _worker_train(args) -> dict:
    from repro.core.model import CostModelConfig
    from repro.training.trainer import CostModelTrainer, TrainerConfig

    mcfg = CostModelConfig(hidden_dim=96, gnn_layers=3, adjacency="sparse")
    cfg = TrainerConfig(task="tile", steps=WARM_STEPS, log_every=10 ** 6,
                        ckpt_every=args.steps if args.ckpt_dir else 0,
                        ckpt_dir=args.ckpt_dir, seed=0, dp=args.dp,
                        prefetch=2)
    trainer = CostModelTrainer(mcfg, cfg, _build_sampler())
    trainer.run(resume=False)                    # warmup incl. compile
    t0 = time.perf_counter()
    trainer.cfg.steps = args.steps
    res = trainer.run(resume=False)
    dt = time.perf_counter() - t0
    return {"step": res["step"], "loss": res["loss"],
            "step_s": dt / max(args.steps - WARM_STEPS, 1),
            "params_sha": _params_sha(trainer.params)}


def _worker_restore(args) -> dict:
    from repro.core.model import CostModelConfig
    from repro.training.trainer import CostModelTrainer, TrainerConfig

    mcfg = CostModelConfig(hidden_dim=96, gnn_layers=3, adjacency="sparse")
    cfg = TrainerConfig(task="tile", steps=args.steps, log_every=10 ** 6,
                        ckpt_dir=args.ckpt_dir, seed=0, dp=args.dp)
    trainer = CostModelTrainer(mcfg, cfg, _build_sampler())
    resumed = trainer.maybe_resume()
    return {"resumed": resumed, "step": trainer.step,
            "params_sha": _params_sha(trainer.params)}


def _run_worker(mode: str, *, dp: int, devices: int, steps: int,
                ckpt_dir: str = "") -> dict:
    """Re-invoke this file with a forced device count; last stdout line is
    the worker's JSON result."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.abspath(__file__), "--worker", mode,
           "--dp", str(dp), "--steps", str(steps),
           "--ckpt-dir", ckpt_dir]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          cwd=os.path.dirname(os.path.abspath(__file__)))
    if proc.returncode != 0:
        raise RuntimeError(f"worker {mode} dp={dp} failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------- streams
def stream_identity(root: str) -> bool:
    """Shard views are disjoint, exhaustive, and their position interleave
    is byte-identical to the unsharded stream."""
    import numpy as np
    from repro.data.store import StreamingCorpus, record_key

    corpus = StreamingCorpus.open(os.path.join(root, "tile"))
    full = list(corpus)
    ok = len(full) == len(corpus)
    for w in STREAM_WORKER_COUNTS:
        shards = [corpus.shard(i, w) for i in range(w)]
        ok &= sum(len(s) for s in shards) == len(full)
        keys = [record_key(r) for sh in shards for r in sh]
        ok &= len(set(keys)) == len(keys)                    # disjoint
        for k, rec in enumerate(full):                       # interleave
            got = shards[k % w][k // w]
            ok &= (record_key(got) == record_key(rec)
                   and np.array_equal(got.runtimes, rec.runtimes)
                   and got.program == rec.program)
    # shard(0, 1) is the identity view over the same parent cache
    s01 = corpus.shard(0, 1)
    ok &= len(s01) == len(corpus) and all(
        record_key(a) == record_key(b) for a, b in zip(s01, corpus))
    return ok


def main() -> int:
    t_start = time.perf_counter()
    assert "jax" not in sys.modules, \
        "bench_scaling must measure capacity and fork corpus pools " \
        "before jax loads"
    capacity = parallel_capacity(2)
    eff_gate = min(EFF_CAP, max(1.0, 0.85 * capacity))
    print(f"bench_scaling: timed_steps={TIMED_STEPS}, "
          f"{os.cpu_count()} cpus, measured parallel capacity "
          f"{capacity:.2f}x -> efficiency gate >= {eff_gate:.2f}x")

    root = tempfile.mkdtemp(prefix="bench_scaling_")
    try:
        # --- 2. sharded-stream identity (store pools fork: jax-free) ------
        from repro.launch.build_corpus import DEFAULT_TILE, build_corpus
        build_corpus(os.path.join(root, "corpus"), kinds=("tile",),
                     programs=STREAM_PROGRAMS, seed=0, workers=2,
                     tile_opts=dict(DEFAULT_TILE, max_configs_per_kernel=8),
                     quiet=True)
        stream_ok = stream_identity(os.path.join(root, "corpus"))
        print(f"  shard union byte-identical to unsharded stream "
              f"(W={STREAM_WORKER_COUNTS}): {stream_ok}")

        # --- 1. throughput scaling dp=1 -> dp=2 (interleaved best-of-2) ---
        ckpt_dir = os.path.join(root, "ckpt_dp2")
        t1 = t2 = float("inf")
        for trial in range(2):
            r2 = _run_worker("train", dp=2, devices=2, steps=TIMED_STEPS,
                             ckpt_dir=ckpt_dir if trial == 0 else "")
            r1 = _run_worker("train", dp=1, devices=1, steps=TIMED_STEPS)
            t1, t2 = min(t1, r1["step_s"]), min(t2, r2["step_s"])
            if trial == 0:
                dp2_sha, dp2_step = r2["params_sha"], r2["step"]
        efficiency = 2.0 * t1 / t2       # dp=2 consumes 2 batches/step
        print(f"  step time dp=1 {t1 * 1e3:.0f}ms, dp=2 {t2 * 1e3:.0f}ms "
              f"-> {efficiency:.2f}x throughput (best of 2, perfect = 2.0)")

        # --- 3. dp=2 checkpoint restores under dp=1, params bit-exact -----
        rr = _run_worker("restore", dp=1, devices=1, steps=TIMED_STEPS,
                         ckpt_dir=ckpt_dir)
        ckpt_ok = (rr["resumed"] and rr["step"] == dp2_step
                   and rr["params_sha"] == dp2_sha)
        print(f"  dp=2 checkpoint -> dp=1 restore bit-exact at step "
              f"{rr['step']}: {ckpt_ok}")

        from common import Gate, emit_json
        ok = emit_json(
            "scaling",
            [Gate("scaling_efficiency_dp2", efficiency, eff_gate),
             Gate("shard_union_identity", stream_ok, True, "=="),
             Gate("ckpt_dp2_to_dp1", ckpt_ok, True, "==")],
            wall_s=time.perf_counter() - t_start,
            extra={"parallel_capacity": round(capacity, 2),
                   "efficiency_gate": round(eff_gate, 2),
                   "step_s_dp1": round(t1, 4),
                   "step_s_dp2": round(t2, 4),
                   "timed_steps": TIMED_STEPS,
                   "stream_worker_counts": list(STREAM_WORKER_COUNTS)})
        print(f"bench_scaling: {'PASS' if ok else 'FAIL'} "
              f"(need >={eff_gate:.2f}x, stream identity, elastic ckpt; "
              f"got {efficiency:.2f}x / {stream_ok} / {ckpt_ok})")
        return 0 if ok else 1
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", choices=("train", "restore"), default="")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--steps", type=int, default=TIMED_STEPS)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()
    if args.worker == "train":
        print(json.dumps(_worker_train(args)))
    elif args.worker == "restore":
        print(json.dumps(_worker_restore(args)))
    else:
        raise SystemExit(main())
