#!/usr/bin/env python3
"""Chip smoke test: the cost model's train → serve path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # data-parallel training, four chips

One chip: builds a small tile corpus from --seed with the simulator,
trains a few steps of the full-width model (`CostModelConfig()` with the
paper's 256-wide opcode embedding, sparse batches) through
`CostModelTrainer`, restores its checkpoint into a `CostModelService`
behind a `CostModelServer` and answers tile-replay requests from a
`CostModelClient`, then scores the same requests through the fused
`segment_aggregate` kernel with f32 and with int8 weights. Every chip
score is compared with the same parameters scored on the host CPU by the
plain jnp path.

Four chips: trains dp=4 through the mesh train step and compares its loss
trajectory with the same global batches reduced on one chip (dp=1).

It needs a TPU and exits non-zero without one, before any work. It runs
in one process, writes only under chip_smoke_out/ next to this file, and
keeps JAX's compile cache where `repro.launch.enable_compile_cache` puts
it. The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chip_smoke_out")

SEED_PROGRAMS = 16           # synthetic programs in the training corpus
TRAIN_STEPS = 12
REPLAY_PROGRAMS = 4          # the served tile-search stream's programs
REQUESTS = 48
# chip scores against the host CPU's, same parameters. f32 matmuls at
# default precision on a TPU round their operands to bfloat16 (8-bit
# significand), so scores agree to ~1e-3 of their scale, not to f32's
# 1e-7, and near-tied tiles of one kernel may swap places. On a TPU v5e
# the served, Pallas f32 and Pallas int8 paths differ from the host by at
# most 1.5e-3 to 1.6e-3, with mean per-kernel Kendall tau 0.996 to 0.999.
MAX_REL_DELTA = 1e-2
MIN_MEAN_KENDALL = 0.95
# dp=4 against dp=1 on the same global batches: per-step loss, relative,
# both with f32 matmuls ("highest" precision). The two programs round
# differently, and AdamW amplifies the difference: on four TPU v5e chips
# the losses agree to six digits over the first three steps, then part by
# 1.5e-5, 1.4e-3 and 2.6e-3 over the next three (2.5e-2 at the sixth with
# bfloat16 operands). Three steps check the data-parallel step before
# rounding is amplified.
DP_STEPS = 3
DP_LOSS_RTOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def model_config():
    """The repository's model at its own widths (Table 5: GraphSAGE,
    Transformer reduction, hidden 192, 3 GNN + 3 node-final layers) with
    the paper's 256-wide opcode embedding, on packed sparse batches."""
    from repro.core.model import CostModelConfig
    return CostModelConfig(opcode_embed_dim=256, adjacency="sparse")


def tile_sampler(seed: int, cfg):
    """A tile corpus generated from `seed` and measured by the simulator,
    split by program, drawn by the sampler settings of
    `python -m repro.launch.train cost-model --task tile`."""
    from repro.core.simulator import TPUSimulator
    from repro.data.corpus import filter_by_programs, split_programs
    from repro.data.sampler import TileBatchSampler
    from repro.data.synthetic import generate_corpus
    from repro.data.tile_dataset import build_tile_dataset, \
        fit_tile_normalizer

    programs = generate_corpus(SEED_PROGRAMS, seed=seed)
    split = split_programs([p.program for p in programs], method="random",
                           seed=seed)
    ds = build_tile_dataset(programs, TPUSimulator(),
                            max_configs_per_kernel=24)
    recs = filter_by_programs(ds.records, split["train"])
    norm = fit_tile_normalizer(recs)
    log(f"corpus: {len(programs)} programs, {len(recs)} train kernels "
        f"with tile sweeps")
    return TileBatchSampler(recs, norm, kernels_per_batch=4,
                            configs_per_kernel=8, max_nodes=cfg.max_nodes,
                            seed=seed, adjacency=cfg.adjacency)


def read_losses(path: str) -> list[float]:
    with open(path) as f:
        return [json.loads(line)["loss"] for line in f]


class CompileClock:
    """Seconds JAX spends compiling or fetching executables from the
    persistent cache, the number of such compiles, and cache hits."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ----------------------------------------------------------------------------
# one chip: corpus → trainer → checkpoint → server → client, and the kernels
# ----------------------------------------------------------------------------
def score_requests(params, cfg, norm, requests, *, device=None,
                   predict_fn=None):
    """Score every request through a fresh `CostModelService` (on
    `device`, default the chip)."""
    import contextlib

    import jax
    from repro.serving import CostModelService

    with (jax.default_device(device) if device is not None
          else contextlib.nullcontext()):
        if device is not None:
            params = jax.device_put(params, device)
        svc = CostModelService(params, cfg, norm, predict_fn=predict_fn)
        return [svc.predict_many(r) for r in requests]


def compare(name: str, chip, host) -> None:
    """Max delta relative to the host scores' scale, and Kendall τ of the
    chip's tile ranking against the host's, per kernel request."""
    from repro.core.metrics import kendall_tau

    a, b = np.concatenate(chip), np.concatenate(host)
    if not np.all(np.isfinite(a)):
        fail(f"{name}: non-finite chip scores")
    rel = float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))
    taus = np.array([kendall_tau(x, y) for x, y in zip(chip, host)
                     if len(x) >= 2])
    log(f"{name} vs host CPU: {a.size} scores, max rel delta {rel:.3e} "
        f"(limit {MAX_REL_DELTA:g}), per-kernel Kendall tau mean "
        f"{taus.mean():.4f} min {taus.min():.4f} over {taus.size} kernels "
        f"(mean limit {MIN_MEAN_KENDALL:g})")
    if rel > MAX_REL_DELTA or taus.mean() < MIN_MEAN_KENDALL:
        fail(f"{name} disagrees with the host CPU reference")


def smoke_one_chip(seed: int) -> None:
    import jax

    from repro.core.evaluate import make_predict_fn
    from repro.core.model import CostModelConfig, cost_model_init, \
        param_count
    from repro.data.batching import encode_packed
    from repro.kernels import interpret_mode
    from repro.quant import quantize_params
    from repro.serving import CostModelService
    from repro.serving.client import CostModelClient
    from repro.serving.replay import build_tile_replay
    from repro.serving.server import CostModelServer
    from repro.training.checkpoint import restore_checkpoint
    from repro.training.optim import AdamWConfig
    from repro.training.trainer import CostModelTrainer, TrainerConfig

    cfg = model_config()
    sampler = tile_sampler(seed, cfg)
    norm = sampler.normalizer

    # -- train ---------------------------------------------------------------
    ckpt_dir = os.path.join(OUT, "ckpt")
    metrics = os.path.join(OUT, "train.jsonl")
    tc = TrainerConfig(task="tile", steps=TRAIN_STEPS, ckpt_every=0,
                       log_every=1, ckpt_dir=ckpt_dir, metrics_path=metrics,
                       seed=seed, optim=AdamWConfig(lr=2e-3))
    trainer = CostModelTrainer(cfg, tc, sampler)
    t0 = time.perf_counter()
    res = trainer.run(resume=False)
    losses = read_losses(metrics)
    log(f"train: {res['step']} steps of {cfg}")
    log(f"train: {param_count(trainer.params)} parameters, losses "
        f"{[round(x, 5) for x in losses]} "
        f"({time.perf_counter() - t0:.1f}s with compiles)")
    if len(losses) != TRAIN_STEPS or not all(map(math.isfinite, losses)):
        fail(f"training losses {losses}")

    # -- checkpoint → service → server → client -----------------------------
    like = {"params": jax.eval_shape(
        lambda: cost_model_init(jax.random.key(0), cfg))}
    state, step, meta = restore_checkpoint(ckpt_dir, like)
    params = state["params"]
    if step != TRAIN_STEPS or meta.get("model_cfg") != cfg.to_dict():
        fail(f"checkpoint holds step {step}, config {meta.get('model_cfg')}")
    if not all(np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(trainer.params))):
        fail("restored parameters differ from the trained ones")
    log(f"checkpoint: restored step {step} from {ckpt_dir}, bit-identical")

    replay = build_tile_replay(REPLAY_PROGRAMS, max_configs=16, rounds=2,
                               seed=seed)
    requests = replay.requests[:REQUESTS]
    service = CostModelService(params, cfg, norm)
    t0 = time.perf_counter()
    with CostModelServer(service, host="127.0.0.1", port=0) as server, \
            CostModelClient(*server.address, timeout_s=600.0) as client:
        served = [client.predict_many(r) for r in requests]
        stats = client.stats()
        host, port = server.address
    done = stats["server"]["completed"]
    log(f"serve: {done} requests ({sum(map(len, requests))} tile queries) "
        f"answered by CostModelServer on {host}:{port}, hit rate "
        f"{stats['service']['hit_rate']:.1%}, "
        f"{stats['service']['flushes']} flushes "
        f"({time.perf_counter() - t0:.1f}s with compiles)")
    if done != len(requests):
        fail(f"server completed {done} of {len(requests)} requests")

    cpu = jax.devices("cpu")[0]
    compare("served f32", served,
            score_requests(params, cfg, norm, requests, device=cpu))

    # -- the fused segment_aggregate kernel, f32 then int8 weights ----------
    if interpret_mode():
        fail("Pallas kernels would run in interpret mode on this backend")
    pal_cfg = CostModelConfig.from_dict(
        dict(cfg.to_dict(), use_pallas_aggregate=True))
    probe = encode_packed(requests[0], norm)
    qm = quantize_params(params)
    # (name, model, its weight tree, kernel config, host reference config)
    for name, model, weights, run_cfg, ref_cfg in (
            ("pallas f32", params, params, pal_cfg, cfg),
            ("pallas int8", qm, qm.params, qm.serving_config(pal_cfg),
             qm.serving_config(cfg))):
        predict = make_predict_fn(run_cfg)
        hlo = predict.lower(weights, probe).compile().as_text()
        if "tpu_custom_call" not in hlo:
            fail(f"{name}: compiled predict function has no tpu_custom_call")
        t0 = time.perf_counter()
        got = score_requests(model, run_cfg, norm, requests,
                             predict_fn=predict)
        log(f"{name}: segment_aggregate compiled natively (tpu_custom_call "
            f"in the predict HLO), {len(got)} requests scored "
            f"({time.perf_counter() - t0:.1f}s with compiles)")
        compare(name, got, score_requests(model, ref_cfg, norm, requests,
                                          device=cpu))


# ----------------------------------------------------------------------------
# four chips: the dp=4 mesh train step against dp=1 on the same batches
# ----------------------------------------------------------------------------
def smoke_data_parallel(seed: int) -> None:
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.losses import pairwise_rank_loss
    from repro.core.model import cost_model_apply, cost_model_init
    from repro.sharding.mesh import DATA_AXIS
    from repro.training.optim import AdamWConfig, adamw_init, adamw_update
    from repro.training.trainer import CostModelTrainer, TrainerConfig

    dp = 4
    cfg = model_config()
    metrics = os.path.join(OUT, "train_dp4.jsonl")
    tc = TrainerConfig(task="tile", steps=DP_STEPS, ckpt_every=0,
                       log_every=1, metrics_path=metrics, seed=seed, dp=dp,
                       optim=AdamWConfig(lr=2e-3))
    trainer = CostModelTrainer(cfg, tc, tile_sampler(seed, cfg))
    stream = trainer.sampler                  # the GlobalBatchSampler

    placed = jax.device_put(stream.batch(0).graphs,
                            NamedSharding(trainer.mesh, P(DATA_AXIS)))
    for leaf in jax.tree_util.tree_leaves(placed):
        if (len(leaf.sharding.device_set) != dp
                or leaf.sharding.shard_shape(leaf.shape)[0] != 1):
            fail(f"batch leaf {leaf.shape} is not split over {dp} devices")
    log(f"dp={dp}: the stacked batch's leading axis spans "
        f"{len(placed.node_mask.sharding.device_set)} devices "
        f"{sorted(d.id for d in placed.node_mask.sharding.device_set)}")

    t0 = time.perf_counter()
    trainer.run(resume=False)
    dp_losses = read_losses(metrics)
    log(f"dp={dp}: {len(dp_losses)} mesh steps (f32 matmuls), losses "
        f"{[round(x, 6) for x in dp_losses]} "
        f"({time.perf_counter() - t0:.1f}s with compiles)")

    # dp=1: each step's global batch on one chip, the dp sub-batches' losses
    # averaged as the mesh step's pmean does, with the same per-shard keys
    @partial(jax.jit, donate_argnums=(0,))
    def step_one_chip(params, opt, batch, targets, groups, valid, rngs):
        def loss_fn(p):
            def one(b, t, g, v, r):
                preds = cost_model_apply(p, cfg, b, rng=r,
                                         deterministic=False)
                return pairwise_rank_loss(preds, t, g, v, phi=tc.rank_phi)
            return jnp.mean(jax.vmap(one)(batch, targets, groups, valid,
                                          rngs))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt, _ = adamw_update(params, grads, opt, tc.optim)
        return params, opt, loss

    params = cost_model_init(jax.random.key(seed), cfg)
    opt = adamw_init(params)
    base = jax.random.key(seed + 1)
    one_losses = []
    for step in range(DP_STEPS):
        b = stream.batch(step)
        rngs = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            base, step * dp + jnp.arange(dp))
        params, opt, loss = step_one_chip(params, opt, b.graphs, b.targets,
                                          b.group_ids, b.valid, rngs)
        one_losses.append(float(loss))
    rels = [abs(a - b) / max(abs(b), 1e-12)
            for a, b in zip(dp_losses, one_losses)]
    log(f"dp=1: losses {[round(x, 6) for x in one_losses]}; relative "
        f"difference from dp={dp} per step {[f'{r:.3e}' for r in rels]} "
        f"(limit {DP_LOSS_RTOL:g})")
    if len(dp_losses) != DP_STEPS or not max(rels) <= DP_LOSS_RTOL:
        fail(f"dp={dp} and dp=1 loss trajectories differ")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus, the model and the stream")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel training check")
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {src}; run "
                         "this from a checkout of the repository")
    sys.path.insert(0, src)
    # the host CPU backend scores the plain reference beside the chip
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU visible (JAX found "
                         f"{dev.platform}); this smoke runs on the chip only")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {len(devices)}")

    from repro.launch import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    t0 = time.perf_counter()
    if args.chips == 4:
        with jax.default_matmul_precision("highest"):
            smoke_data_parallel(args.seed)
    else:
        smoke_one_chip(args.seed)
    log(f"compile: {clock.seconds:.1f}s in {clock.compiles} compiles "
        f"({clock.cache_hits} persistent-cache hits); wall "
        f"{time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
