"""Cost-model trainer: one shard_map train step over a data-parallel mesh,
fault tolerance, checkpoint/resume, optional int8-compressed gradients.

The trainer is deliberately framework-grade rather than script-grade:
  * deterministic batch streams (seed, step, host) — restart-reproducible,
  * SIGTERM/SIGINT-safe: a final checkpoint is written on the way out,
  * periodic atomic checkpoints + automatic resume from the latest,
  * metrics streamed to JSONL for the benchmark harness,
  * data parallelism over a named mesh axis; parameters are replicated
    (the model is ~1-10M params — DP is the right parallelism; the LM zoo
    under repro.models exercises TP/FSDP/EP/SP instead).

Batches are whatever the sampler yields: dense `features.GraphBatch`,
packed `features.SparseGraphBatch` (adjacency='sparse'; DESIGN.md §4) or
`features.SegmentedGraphBatch` (adjacency='segmented'; DESIGN.md §12). The
jit step caches one executable per batch shape, so sparse batches must come
from the pow2-bucketed batcher in `repro.data.batching` to bound
recompilation.

With `TrainerConfig.prefetch > 0` the sampler is wrapped in a
`repro.data.prefetch.Prefetcher`: a background thread encodes that many
batches ahead of the jitted step (optionally staging them on device), with
a byte-identical batch stream and restart-safe determinism (DESIGN.md §9).

The sampler's record list may be a `repro.data.store.StreamingCorpus` (or
a split view of one): records then stream shard-by-shard from disk as
batches draw them, with a byte-identical batch stream to in-memory records
— `python -m repro.launch.train cost-model --from-store` is this path
(DESIGN.md §11, docs/DATA.md).

The trainer has one train step, the *mesh train step* (DESIGN.md §13): a
``(dp, 1)`` ``(data, model)`` mesh from `repro.sharding.make_train_mesh`,
the sampler wrapped in a `GlobalBatchSampler` whose batches carry a leading
[dp] device axis (each device trains on its own disjoint record shard),
per-device forward/backward under `shard_map` with pmean'd loss and grads
— int8-compressed when `compress_grads`. At ``dp=1`` the global stream is
the base sampler's stream with a length-1 leading axis. Segmented batches
have no shared shape across shards, so segmented training runs at dp=1
only.

The step takes ``key(seed + 1)``, made once, and the step number as an
``np.int32`` argument, and folds the step's dropout keys from them inside
the jit (`step_keys`): the ladder ``fold_in(key(seed + 1), step*dp + d)``
for device d. Threefry gives the same bits traced as eager, so no eager JAX
op runs between the batch and the launch, and the keys are those a host
fold would give. Checkpoints are written by process 0 only and restore
onto any dp layout (error-feedback buffers, the one per-device-layout
state, restart at zero across layouts).
"""
from __future__ import annotations

import json
import os
import signal
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.losses import log_mse_loss, mse_loss, pairwise_rank_loss
from repro.core.model import CostModelConfig, cost_model_apply, cost_model_init
from repro.data.sampler import GlobalBatchSampler
from repro.sharding.context import (activation_sharding,
                                    constrain_batch_tree, shard_map_nocheck)
from repro.sharding.mesh import DATA_AXIS, MODEL_AXIS, make_train_mesh
from repro.spans import span
from repro.training import checkpoint as ckpt_lib
from repro.training.compression import compressed_allreduce, zeros_like_error
from repro.training.optim import AdamWConfig, adamw_init, adamw_update


@dataclass
class TrainerConfig:
    task: str = "tile"                   # tile | fusion | fusion_mse
    rank_phi: str = "hinge"              # hinge | logistic (tile task)
    steps: int = 2000
    ckpt_every: int = 500
    log_every: int = 100
    keep_ckpts: int = 3
    seed: int = 0
    ckpt_dir: str = ""
    metrics_path: str = ""
    compress_grads: bool = False          # int8 + error feedback over DP axis
    # mesh train step (DESIGN.md §13): a (dp, 1) (data, model) mesh; the
    # sampler is wrapped in a GlobalBatchSampler and each device trains on
    # its own record shard. Segmented batches train at dp=1 only.
    dp: int = 1
    # async input pipeline (DESIGN.md §9): number of batches a background
    # thread encodes ahead of the jitted step (0 = synchronous encode). The
    # delivered batch stream is byte-identical either way; `.run` owns the
    # worker's lifecycle (started per run, stopped on exit/interrupt).
    prefetch: int = 0
    prefetch_device_put: bool = False     # also overlap host->device copies
    optim: AdamWConfig = field(default_factory=AdamWConfig)


def step_keys(base, step, dp: int):
    """The dropout keys of train step `step` from ``base = key(seed + 1)``,
    traceable: the ladder ``fold_in(base, step*dp + d)``, one key for each
    device d of `dp` (a ``[dp]`` key array)."""
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        base, step * dp + jnp.arange(dp))


class CostModelTrainer:
    def __init__(self, model_cfg: CostModelConfig, cfg: TrainerConfig,
                 sampler, mesh: Mesh | None = None):
        self.model_cfg = model_cfg
        self.cfg = cfg
        self.step = 0
        self._stop = False
        self._metrics_f = None

        if cfg.dp < 1:
            raise ValueError(f"TrainerConfig.dp must be >= 1, got {cfg.dp}")

        if model_cfg.precision != "f32":
            raise ValueError(
                f"training runs in f32, got precision="
                f"{model_cfg.precision!r} — train the f32 model and "
                "quantize afterwards (repro.quant.quantize_params)")

        # reject dense-only config combos here rather than as a
        # NotImplementedError buried in the first step's jit trace
        if cfg.dp > 1 and model_cfg.adjacency == "segmented":
            raise ValueError(
                "segmented batches have no shape shared across shards, so "
                "segmented training runs at dp=1 — use adjacency='dense' "
                "or 'sparse' for TrainerConfig.dp > 1")
        if model_cfg.adjacency in ("sparse", "segmented"):
            if model_cfg.use_pallas_aggregate:
                raise ValueError(
                    "use_pallas_aggregate on the sparse layouts routes "
                    "through kernels/segment_aggregate, which has no VJP — "
                    "it is inference-only; train with "
                    "use_pallas_aggregate=False (or adjacency='dense')")
            if model_cfg.gnn == "gat" and not model_cfg.directed:
                raise ValueError(
                    "undirected GAT is dense-only (DESIGN.md §4) — use "
                    "adjacency='dense'")

        self.mesh = mesh or make_train_mesh(cfg.dp)
        if isinstance(sampler, GlobalBatchSampler):
            if sampler.num_shards != cfg.dp:
                raise ValueError(
                    f"GlobalBatchSampler has {sampler.num_shards} "
                    f"shards but dp={cfg.dp}")
            self.sampler = sampler
        else:
            self.sampler = GlobalBatchSampler.for_mesh(sampler, cfg.dp)

        key = jax.random.key(cfg.seed)
        self.params = cost_model_init(key, model_cfg)
        self.opt_state = adamw_init(self.params)
        if cfg.compress_grads:
            # per-DEVICE residuals: leading [dp] axis, sharded P(data)
            self.opt_state["ef"] = jax.tree_util.tree_map(
                lambda x: jnp.zeros((cfg.dp,) + x.shape, x.dtype),
                zeros_like_error(self.params))

        # an argument of every step, not a constant of its trace, so one
        # executable (and one persistent-cache entry) serves every seed
        self._rng_base = jax.random.key(cfg.seed + 1)
        self._train_step = self._build_train_step()

    # ------------------------------------------------------------------
    def _loss_fn(self, params, batch, targets, group_ids, valid, rng):
        preds = cost_model_apply(params, self.model_cfg, batch, rng=rng,
                                 deterministic=False)
        if self.cfg.task == "tile":
            return pairwise_rank_loss(preds, targets, group_ids, valid,
                                      phi=self.cfg.rank_phi)
        if self.cfg.task == "fusion":
            return log_mse_loss(preds, targets, valid)
        if self.cfg.task == "fusion_mse":
            return mse_loss(preds, targets, valid)
        if self.cfg.task == "tile_mse":
            # ablation row 'MSE loss (not rank)': absolute (log) runtimes
            return log_mse_loss(preds, targets, valid)
        raise ValueError(f"unknown task {self.cfg.task!r}")

    def _build_train_step(self):
        """The dp mesh train step (DESIGN.md §13).

        Inputs carry a leading [dp] device axis (GlobalBatchSampler); the
        step shards it over the data axis, runs the per-device
        forward/backward under shard_map, and pmeans loss + grads (int8
        `compressed_allreduce` when `compress_grads` — its error-feedback
        residuals live in `opt_state['ef']` with the same leading [dp]
        axis). The optimizer update runs once on the replicated mean
        gradient outside the shard_map, so params never diverge across
        devices.
        """
        cfg = self.cfg
        mesh = self.mesh
        axis = DATA_AXIS
        compress = cfg.compress_grads

        def repl(tree):
            return jax.tree_util.tree_map(lambda _: P(), tree)

        def lead(tree):
            return jax.tree_util.tree_map(lambda _: P(axis), tree)

        def squeeze(tree):
            return jax.tree_util.tree_map(lambda x: x[0], tree)

        def local(params, batch, targets, group_ids, valid, rngs, ef):
            loss, grads = jax.value_and_grad(self._loss_fn)(
                params, squeeze(batch), targets[0], group_ids[0], valid[0],
                rngs[0])
            if compress:
                grads, new_ef = compressed_allreduce(grads, squeeze(ef),
                                                     axis)
                new_ef = jax.tree_util.tree_map(lambda x: x[None], new_ef)
            else:
                grads = jax.lax.pmean(grads, axis)
                new_ef = ef
            return jax.lax.pmean(loss, axis), grads, new_ef

        @partial(jax.jit, donate_argnums=(0,))
        def mesh_step(params, opt_state, batch, targets, group_ids, valid,
                      base, step):
            rngs = step_keys(base, step, cfg.dp)
            batch = constrain_batch_tree(batch, leading=0)
            targets, group_ids, valid = constrain_batch_tree(
                (targets, group_ids, valid), leading=0)
            ef = opt_state.get("ef") if compress else {}
            loss, grads, new_ef = shard_map_nocheck(
                local, mesh,
                in_specs=(repl(params), lead(batch), P(axis), P(axis),
                          P(axis), P(axis), lead(ef)),
                out_specs=(P(), repl(params), lead(ef)),
            )(params, batch, targets, group_ids, valid, rngs, ef)
            opt_no_ef = {k: v for k, v in opt_state.items() if k != "ef"}
            new_params, new_opt, stats = adamw_update(
                params, grads, opt_no_ef, cfg.optim)
            if compress:
                new_opt["ef"] = new_ef
            stats["loss"] = loss
            return new_params, new_opt, stats

        return mesh_step

    # ------------------------------------------------------------------
    def _install_signal_handlers(self):
        def handler(signum, frame):
            self._stop = True
        try:
            signal.signal(signal.SIGTERM, handler)
            signal.signal(signal.SIGINT, handler)
        except ValueError:
            pass   # not on main thread (e.g. under pytest plugins)

    def _log(self, record: dict):
        if self.cfg.metrics_path:
            if self._metrics_f is None:
                os.makedirs(os.path.dirname(self.cfg.metrics_path) or ".",
                            exist_ok=True)
                self._metrics_f = open(self.cfg.metrics_path, "a")
            self._metrics_f.write(json.dumps(record) + "\n")
            self._metrics_f.flush()

    def save(self):
        if not self.cfg.ckpt_dir:
            return
        state = {"params": self.params, "opt": self.opt_state}
        ckpt_lib.save_checkpoint(
            self.cfg.ckpt_dir, self.step, state,
            meta={"model_cfg": self.model_cfg.to_dict(),
                  "task": self.cfg.task},
            keep=self.cfg.keep_ckpts)

    def _state_shardings(self, like):
        """NamedSharding tree for `like`: everything replicated over the
        mesh except the per-device error-feedback residuals, which shard
        their leading [dp] axis over the data axis."""
        repl = NamedSharding(self.mesh, P())
        sh = jax.tree_util.tree_map(lambda _: repl, like)
        if "ef" in like.get("opt", {}):
            dps = NamedSharding(self.mesh, P(DATA_AXIS))
            sh["opt"]["ef"] = jax.tree_util.tree_map(
                lambda _: dps, like["opt"]["ef"])
        return sh

    def maybe_resume(self) -> bool:
        if not self.cfg.ckpt_dir:
            return False
        latest = ckpt_lib.latest_step(self.cfg.ckpt_dir)
        if latest is None:
            return False
        like = {"params": self.params, "opt": self.opt_state}
        try:
            state, step, _ = ckpt_lib.restore_checkpoint(
                self.cfg.ckpt_dir, like,
                shardings=self._state_shardings(like))
        except ValueError:
            if "ef" not in self.opt_state:
                raise
            # cross-dp-layout restore: error-feedback residuals are
            # per-device [dp, ...] state, so a checkpoint from a different
            # dp layout can't be mapped onto this one — restore everything
            # else bit-exactly and restart the residuals at zero (they are
            # quantization carry, not model state)
            like = {"params": self.params,
                    "opt": {k: v for k, v in self.opt_state.items()
                            if k != "ef"}}
            state, step, _ = ckpt_lib.restore_checkpoint(
                self.cfg.ckpt_dir, like,
                shardings=self._state_shardings(like))
            state["opt"]["ef"] = jax.tree_util.tree_map(
                jnp.zeros_like, self.opt_state["ef"])
        self.params, self.opt_state = state["params"], state["opt"]
        self.step = step
        return True

    def warm_start(self, ckpt_dir: str, *, step: int | None = None,
                   restore_opt: bool = True,
                   reset_opt_step: bool = True) -> int:
        """Initialize from ANOTHER run's checkpoint, keeping this run
        fresh — the flywheel fine-tune path (DESIGN.md §15, TLP-style).

        Unlike `maybe_resume` (which continues the same run: `self.step`
        jumps to the checkpoint step, so a finished run is a no-op),
        `warm_start` copies the checkpoint's params — and, with
        `restore_opt`, the AdamW moments — but leaves ``self.step`` at 0,
        so the full `cfg.steps` of fine-tuning actually run.

        `reset_opt_step=True` (default) also zeroes the *optimizer's*
        step counter, restarting the `AdamWConfig.warmup_steps` LR warmup
        — the short re-warmup that keeps fresh delta gradients from
        blowing away a good checkpoint. `reset_opt_step=False` preserves
        the counter: the schedule continues as if training never stopped.
        Error-feedback residuals (`opt['ef']`) are never imported — they
        are per-device quantization carry, not model state.

        Returns the checkpoint step warm-started from. Note `run`'s
        default ``resume=True`` still prefers a checkpoint in THIS run's
        `cfg.ckpt_dir` if one exists — pass ``resume=False`` (or a fresh
        ckpt_dir) when fine-tuning into a new directory.
        """
        pick = ckpt_lib.latest_step(ckpt_dir) if step is None else step
        if pick is None:
            raise FileNotFoundError(
                f"no checkpoint to warm-start from in {ckpt_dir!r}")
        like = {"params": self.params}
        if restore_opt:
            like["opt"] = {k: v for k, v in self.opt_state.items()
                           if k != "ef"}
        state, ck_step, _ = ckpt_lib.restore_checkpoint(
            ckpt_dir, like, step=pick,
            shardings=self._state_shardings(like))
        self.params = state["params"]
        if restore_opt:
            opt = dict(state["opt"])
            if reset_opt_step:
                opt["step"] = jnp.zeros_like(opt["step"])
            if "ef" in self.opt_state:
                opt["ef"] = self.opt_state["ef"]
            self.opt_state = opt
        self.step = 0
        return ck_step

    # ------------------------------------------------------------------
    def run(self, steps: int | None = None, *, resume: bool = True,
            eval_fn: Callable[[dict, int], dict] | None = None,
            eval_every: int = 0) -> dict:
        cfg = self.cfg
        total = steps if steps is not None else cfg.steps
        if resume:
            self.maybe_resume()
        with span("repro.train.run", steps=max(0, total - self.step)):
            self._install_signal_handlers()
            sampler = self.sampler
            if cfg.prefetch:
                from repro.data.prefetch import Prefetcher
                sampler = Prefetcher(self.sampler, depth=cfg.prefetch,
                                     start_step=self.step,
                                     device_put=cfg.prefetch_device_put)
            try:
                mapping = {"dp": DATA_AXIS,
                           "axis_sizes": {DATA_AXIS: cfg.dp, MODEL_AXIS: 1}}
                with self.mesh, activation_sharding(mapping):
                    return self._run_loop(sampler, total, eval_fn,
                                          eval_every)
            finally:
                if sampler is not self.sampler:
                    sampler.close()

    def _run_loop(self, sampler, total: int, eval_fn, eval_every) -> dict:
        cfg = self.cfg
        t0 = time.time()
        last_loss = float("nan")
        while self.step < total and not self._stop:
            with span("repro.train.step", step=self.step):
                with span("repro.train.batch") as sp:
                    b = sampler.batch(self.step)
                    sp.set_metadata(graphs=int(np.size(b.valid)))
                with span("repro.train.inputs"):
                    group_ids = getattr(b, "group_ids",
                                        np.zeros_like(b.targets, np.int32))
                    # the jitted step folds its dropout keys from this
                    # scalar (`step_keys`); a traced argument, not static
                    step = np.int32(self.step)
                with span("repro.train.dispatch"):
                    self.params, self.opt_state, stats = self._train_step(
                        self.params, self.opt_state, b.graphs, b.targets,
                        group_ids, b.valid, self._rng_base, step)
                self.step += 1
                if self.step % cfg.log_every == 0 or self.step == total:
                    with span("repro.train.sync"):
                        last_loss = float(stats["loss"])
                        self._log({"step": self.step, "loss": last_loss,
                                   "lr": float(stats["lr"]),
                                   "grad_norm": float(stats["grad_norm"]),
                                   "wall": time.time() - t0})
                if cfg.ckpt_every and self.step % cfg.ckpt_every == 0:
                    self.save()
                if eval_fn and eval_every and self.step % eval_every == 0:
                    ev = eval_fn(self.params, self.step)
                    self._log({"step": self.step,
                               **{f"eval/{k}": v for k, v in ev.items()}})
        self.save()
        if self._metrics_f:
            self._metrics_f.close()
            self._metrics_f = None
        return {"step": self.step, "loss": last_loss,
                "wall": time.time() - t0, "interrupted": self._stop}
