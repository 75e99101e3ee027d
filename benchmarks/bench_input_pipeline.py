"""Encode-once feature cache + async prefetch vs the old synchronous
per-config encoder (DESIGN.md §9; acceptance gate for the input pipeline).

The tile task re-scores every kernel under many tile configurations; before
this pipeline the sampler re-ran full feature extraction per config with a
per-node Python loop. This bench replays the same deterministic batch
stream two ways:

  * old — the per-node oracles (`features._build_encoded_reference`) +
    `EncodeCache(0)` (every draw encodes fresh) + synchronous encode in the
    train loop: the pre-cache behavior of every call site.
  * new — the columnar encode + the shared structural `EncodeCache`
    (tile variants rewrite only `TILE_SLICE`) + `TrainerConfig.prefetch`
    encode-ahead.

Gates (all must hold):
  1. sampler encode throughput (dense tile batches)   >= 3.0x
  2. end-to-end `CostModelTrainer` steps/s on CPU     >= 1.5x
  3. cached-path predictions vs old encoder           max delta < 1e-6
  4. prefetched batch stream vs synchronous           byte-identical

  PYTHONPATH=src python benchmarks/bench_input_pipeline.py

`BENCH_SCALE` scales kernel/step *counts*, never kernel *sizes* (see
benchmarks/common.py) — the encode-vs-step cost ratio the gates measure is
scale-independent. Margins are wide (measured ~24x encode / ~2.3x
steps/s at scale 1.0, ~5x steps/s at 0.5), so the scaled-down CI run
keeps headroom.
"""
from __future__ import annotations

import os
import sys
import time
from contextlib import contextmanager

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.core import features as F
from repro.core.evaluate import make_predict_fn
from repro.core.model import CostModelConfig, cost_model_init
from repro.core.simulator import TPUSimulator
from repro.data.prefetch import Prefetcher
from repro.data.sampler import TileBatchSampler
from repro.data.synthetic import random_kernel
from repro.data.tile_dataset import build_tile_dataset, fit_tile_normalizer
from repro.training.trainer import CostModelTrainer, TrainerConfig

SCALE = float(os.environ.get("BENCH_SCALE", "1.0"))
MAX_NODES = 48
# BENCH_SCALE scales how MANY kernels/steps run, never how BIG the kernels
# are — the encode-vs-step cost ratio (what the gates measure) must not
# depend on the scale knob (see benchmarks/common.py).
NUM_KERNELS = max(int(24 * SCALE), 12)
KERNEL_NODES = (28, 34, 40, 48)            # cycled; sizes fixed at any scale
ENCODE_STEPS = max(int(40 * SCALE), 15)
TRAIN_WARM = 3
TRAIN_STEPS = max(int(30 * SCALE), 12)

_COLUMNAR_ENCODE = F._build_encoded


@contextmanager
def encoder(mode: str):
    """'old' = the per-node oracles, caching disabled;
    'new' = the columnar encoder + a fresh EncodeCache."""
    F._build_encoded = (F._build_encoded_reference if mode == "old"
                        else _COLUMNAR_ENCODE)
    prev = F.set_encode_cache(F.EncodeCache(0 if mode == "old" else 4096))
    try:
        yield
    finally:
        F._build_encoded = _COLUMNAR_ENCODE
        F.set_encode_cache(prev)


def make_sampler(records, norm, adjacency: str) -> TileBatchSampler:
    return TileBatchSampler(records, norm, kernels_per_batch=4,
                            configs_per_kernel=16, max_nodes=MAX_NODES,
                            seed=0, adjacency=adjacency)


def time_stream(sampler, steps: int, warm: int = 5) -> float:
    """Steady-state batch-encode time: `warm` untimed steps first, so the
    cached path is measured with the structural cache populated (the
    training regime — every kernel recurs across thousands of steps) and
    the uncached path amortizes nothing either way."""
    for s in range(warm):
        sampler.batch(s)
    t0 = time.perf_counter()
    for s in range(warm, warm + steps):
        sampler.batch(s)
    return time.perf_counter() - t0


def batches_equal(a, b) -> bool:
    """Byte-identical TileBatch comparison (targets/groups/valid + every
    array leaf of the encoded graphs)."""
    fields = [(a.targets, b.targets), (a.group_ids, b.group_ids),
              (a.valid, b.valid)]
    fields += list(zip(jax.tree_util.tree_leaves(a.graphs),
                       jax.tree_util.tree_leaves(b.graphs)))
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in fields)


def train_steps_per_sec(mc, records, norm, *, prefetch: int) -> float:
    sampler = make_sampler(records, norm, mc.adjacency)
    tc = TrainerConfig(task="tile", steps=TRAIN_WARM + TRAIN_STEPS,
                       ckpt_every=0, log_every=10 ** 9, prefetch=prefetch)
    tr = CostModelTrainer(mc, tc, sampler)
    tr.run(TRAIN_WARM, resume=False)            # compile + warm the caches
    t0 = time.perf_counter()
    tr.run(TRAIN_WARM + TRAIN_STEPS, resume=False)
    jax.block_until_ready(tr.params)
    return TRAIN_STEPS / (time.perf_counter() - t0)


def main() -> int:
    t_start = time.perf_counter()
    sim = TPUSimulator()
    kernels = [random_kernel(KERNEL_NODES[i % len(KERNEL_NODES)], seed=i)
               for i in range(NUM_KERNELS)]
    ds = build_tile_dataset([], sim, extra_kernels=kernels,
                            max_configs_per_kernel=16,
                            max_kernel_nodes=MAX_NODES)
    records = ds.records
    norm = fit_tile_normalizer(records)
    bs = 4 * 16
    print(f"bench_input_pipeline: {len(records)} kernels, "
          f"{ds.num_samples} (kernel, tile) samples, batch={bs}")

    # --- 1. sampler encode throughput -------------------------------------
    enc = {}
    for adjacency in ("dense", "sparse"):
        for mode in ("old", "new"):
            with encoder(mode):
                dt = time_stream(make_sampler(records, norm, adjacency),
                                 ENCODE_STEPS)
            enc[mode, adjacency] = ENCODE_STEPS * bs / dt
            print(f"  encode {adjacency:6s} {mode}: "
                  f"{enc[mode, adjacency]:8.0f} graphs/s")
    enc_speedup = enc["new", "dense"] / enc["old", "dense"]
    sparse_speedup = enc["new", "sparse"] / enc["old", "sparse"]
    print(f"  encode speedup: dense {enc_speedup:.2f}x, "
          f"sparse {sparse_speedup:.2f}x")

    # --- 2. end-to-end trainer steps/s ------------------------------------
    mc = CostModelConfig(gnn="graphsage", reduction="column_wise",
                         hidden_dim=16, opcode_embed_dim=16, gnn_layers=2,
                         dropout=0.1, max_nodes=MAX_NODES, adjacency="dense")
    with encoder("old"):
        sps_old = train_steps_per_sec(mc, records, norm, prefetch=0)
    with encoder("new"):
        sps_new = train_steps_per_sec(mc, records, norm, prefetch=3)
    e2e_speedup = sps_new / sps_old
    print(f"  train old: {sps_old:6.1f} steps/s   "
          f"new(+cache+prefetch): {sps_new:6.1f} steps/s   "
          f"-> {e2e_speedup:.2f}x")

    # --- 3. prediction delta: cached path vs the old encoder --------------
    params = cost_model_init(jax.random.key(0), mc)
    predict = make_predict_fn(mc)
    deltas = []
    for step in range(3):
        with encoder("old"):
            b_old = make_sampler(records, norm, "dense").batch(step)
        with encoder("new"):
            b_new = make_sampler(records, norm, "dense").batch(step)
        p_old = np.asarray(predict(params, b_old.graphs))
        p_new = np.asarray(predict(params, b_new.graphs))
        deltas.append(float(np.max(np.abs(p_old - p_new))))
        if not batches_equal(b_old, b_new):
            deltas.append(float("inf"))       # encoders diverged
    delta = max(deltas)
    print(f"  max prediction delta cached-vs-old-encoder: {delta:.2e}")

    # --- 4. prefetched stream == synchronous stream -----------------------
    sync = make_sampler(records, norm, "dense")
    with Prefetcher(make_sampler(records, norm, "dense"), depth=3) as pre:
        stream_ok = all(batches_equal(sync.batch(s), pre.batch(s))
                        for s in range(6))
        # simulated restart mid-stream: a fresh prefetcher seeked to step 3
        with Prefetcher(make_sampler(records, norm, "dense"), depth=3,
                        start_step=3) as pre2:
            stream_ok &= batches_equal(sync.batch(3), pre2.batch(3))
    print(f"  prefetched stream byte-identical: {stream_ok}")

    from common import Gate, emit_json
    ok = emit_json(
        "input_pipeline",
        [Gate("encode_speedup", enc_speedup, 3.0),
         Gate("train_steps_speedup", e2e_speedup, 1.5),
         Gate("prediction_delta", delta, 1e-6, "<"),
         Gate("prefetch_stream_identical", bool(stream_ok), True, "==")],
        wall_s=time.perf_counter() - t_start,
        extra={"sparse_encode_speedup": sparse_speedup,
               "steps_per_sec_old": sps_old, "steps_per_sec_new": sps_new})
    print(f"bench_input_pipeline: {'PASS' if ok else 'FAIL'} "
          f"(need >=3x encode, >=1.5x steps/s, delta <1e-6, identical "
          f"stream; got {enc_speedup:.2f}x / {e2e_speedup:.2f}x / "
          f"{delta:.1e} / {stream_ok})")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
