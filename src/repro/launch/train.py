"""Training launcher.

Two sub-commands:

  cost-model — train the paper's learned performance model on a generated
    corpus (the production path: deterministic sharded sampling, atomic
    checkpoints, resume, optional int8-compressed DP).

      PYTHONPATH=src python -m repro.launch.train cost-model \
          --task tile --steps 2000 --ckpt-dir ckpts/tile

    With --from-store the corpus is streamed shard-by-shard from an
    on-disk store built by `python -m repro.launch.build_corpus`
    (docs/DATA.md) — no generation or oracle measurement at train time:

      PYTHONPATH=src python -m repro.launch.train cost-model \
          --task tile --from-store experiments/corpora/v1/tile

    The flywheel's incremental-retrain path (DESIGN.md §15) adds
    --deltas (train on the store's base+delta chained view) and
    --warm-start CKPT (fine-tune from another run's checkpoint with a
    short LR re-warmup):

      PYTHONPATH=src python -m repro.launch.train cost-model \
          --task tile --from-store experiments/corpora/v1/tile --deltas \
          --warm-start ckpts/tile --ckpt-dir ckpts/tile_ft \
          --steps 200 --warmup-steps 20

  lm — train one of the 10 assigned architectures (reduced config on CPU;
    full configs are exercised via the dry-run).

      PYTHONPATH=src python -m repro.launch.train lm --arch yi-9b \
          --steps 10 --smoke
"""
from __future__ import annotations

import argparse
import time


def train_cost_model(args) -> None:
    from repro.core.features import fit_normalizer
    from repro.core.model import CostModelConfig
    from repro.core.simulator import TPUSimulator
    from repro.data.corpus import filter_by_programs, split_programs
    from repro.data.fusion_dataset import build_fusion_dataset
    from repro.data.sampler import BalancedSampler, TileBatchSampler
    from repro.data.synthetic import generate_corpus
    from repro.data.tile_dataset import build_tile_dataset
    from repro.training.optim import AdamWConfig
    from repro.training.trainer import CostModelTrainer, TrainerConfig

    if args.num_hosts < 1:
        raise SystemExit(f"--num-hosts must be >= 1, got {args.num_hosts}")
    if not 0 <= args.host_id < args.num_hosts:
        raise SystemExit(f"--host-id must be in [0, {args.num_hosts}), "
                         f"got {args.host_id}")
    if args.dp < 0 or args.mp < 1:
        raise SystemExit(f"--dp must be >= 0 and --mp >= 1, "
                         f"got dp={args.dp} mp={args.mp}")

    if args.deltas and not args.from_store:
        raise SystemExit("--deltas only applies to a stored corpus; "
                         "pass --from-store DIR")
    if args.warm_start:
        from repro.training.checkpoint import latest_step
        if latest_step(args.warm_start) is None:
            raise SystemExit(f"--warm-start: no checkpoint found in "
                             f"{args.warm_start!r}")
        if args.warm_start == args.ckpt_dir:
            raise SystemExit(
                "--warm-start must point at a DIFFERENT run's checkpoint "
                "directory — resuming the same --ckpt-dir is the default "
                "behaviour (drop --warm-start), and fine-tuning in place "
                "would overwrite the checkpoint being fine-tuned from")

    want_kind = "tile" if args.task.startswith("tile") else "fusion"
    if args.from_store:
        from repro.data.store import StreamingCorpus
        corpus = StreamingCorpus.open(args.from_store)
        if corpus.kind != want_kind:
            raise SystemExit(f"--from-store points at a {corpus.kind!r} "
                             f"corpus but --task {args.task} needs "
                             f"{want_kind!r}")
        if args.deltas:
            corpus = corpus.with_deltas()
            print(f"chained {corpus.num_deltas} delta shard set(s) "
                  f"(chain {corpus.chain_hash[:12]}…)")
        split = split_programs(corpus.programs(), method=args.split,
                               seed=args.seed)
        recs = corpus.select_programs(split["train"])
        ident = (corpus.chain_hash if args.deltas
                 else corpus.manifest_hash)
        print(f"streaming {len(recs)}/{len(corpus)} records from "
              f"{args.from_store} (manifest {ident[:12]}…)")
    else:
        sim = TPUSimulator()
        programs = generate_corpus(args.programs, seed=args.seed)
        split = split_programs([p.program for p in programs],
                               method=args.split, seed=args.seed)
        if want_kind == "tile":
            ds = build_tile_dataset(programs, sim, max_configs_per_kernel=24)
        else:
            ds = build_fusion_dataset(programs, sim, configs_per_program=12)
        recs = filter_by_programs(ds.records, split["train"])
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    mc = CostModelConfig(gnn=args.gnn, reduction=args.reduction,
                         hidden_dim=args.hidden, max_nodes=args.max_nodes)
    if want_kind == "tile":
        from repro.data.tile_dataset import fit_tile_normalizer
        norm = fit_tile_normalizer(recs)
        sampler = TileBatchSampler(recs, norm, kernels_per_batch=4,
                                   configs_per_kernel=8,
                                   max_nodes=args.max_nodes,
                                   host_id=args.host_id,
                                   num_hosts=args.num_hosts)
    else:
        norm = fit_normalizer([r.kernel for r in recs])
        sampler = BalancedSampler(recs, norm, batch_size=32,
                                  max_nodes=args.max_nodes,
                                  host_id=args.host_id,
                                  num_hosts=args.num_hosts)
    tc = TrainerConfig(task=args.task, steps=args.steps,
                       ckpt_every=args.ckpt_every, log_every=args.log_every,
                       ckpt_dir=args.ckpt_dir,
                       metrics_path=args.metrics_path,
                       compress_grads=args.compress_grads,
                       dp=args.dp, mp=args.mp,
                       optim=AdamWConfig(lr=args.lr,
                                         warmup_steps=args.warmup_steps))
    trainer = CostModelTrainer(mc, tc, sampler)
    if args.warm_start:
        from_step = trainer.warm_start(args.warm_start,
                                       reset_opt_step=not args.keep_opt_step)
        print(f"warm-started from {args.warm_start} step {from_step} "
              f"(LR warmup {'continues' if args.keep_opt_step else 'restarts'}"
              f", {args.warmup_steps} warmup steps)")
    res = trainer.run(resume=not args.no_resume)
    print(f"done: step={res['step']} loss={res['loss']:.5f} "
          f"wall={res['wall']:.1f}s interrupted={res['interrupted']}")


def train_lm(args) -> None:
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    import jax
    from repro.models import lm, registry
    from repro.models.config import ShapeSpec
    from repro.models.inputs import make_batch

    cfg = registry.get_smoke_config(args.arch) if args.smoke \
        else registry.get_config(args.arch)
    shape = ShapeSpec("cli", args.seq, args.batch, "train")
    params = lm.init_params(jax.random.key(args.seed), cfg)
    opt_init, _ = lm.make_optimizer(cfg)
    opt = opt_init(params)
    step = jax.jit(lm.train_step_fn(cfg))
    print(f"arch={cfg.name} params={lm.param_count(params):,}")
    for i in range(args.steps):
        batch = make_batch(cfg, shape, seed=args.seed + i)
        t0 = time.time()
        params, opt, stats = step(params, opt, batch)
        print(f"step {i}: loss={float(stats['loss']):.4f} "
              f"({time.time()-t0:.2f}s)")


def main() -> None:
    from repro.core.model import CostModelConfig
    model_defaults = CostModelConfig()
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)

    cm = sub.add_parser("cost-model")
    cm.add_argument("--task", default="tile",
                    choices=["tile", "fusion", "tile_mse", "fusion_mse"])
    cm.add_argument("--steps", type=int, default=2000)
    cm.add_argument("--programs", type=int, default=48)
    cm.add_argument("--from-store", default="",
                    help="stream records from an on-disk corpus store "
                         "(one kind's directory, e.g. corpora/v1/tile) "
                         "instead of regenerating + re-measuring")
    cm.add_argument("--deltas", action="store_true",
                    help="with --from-store: train on the base+delta "
                         "chained view (StreamingCorpus.with_deltas) — "
                         "the flywheel's appended measurement shards "
                         "included, chain-verified")
    cm.add_argument("--warm-start", default="",
                    help="checkpoint directory of ANOTHER run to "
                         "fine-tune from: params + AdamW moments are "
                         "restored, this run still starts at step 0 "
                         "(DESIGN.md §15)")
    cm.add_argument("--warmup-steps", type=int, default=0,
                    help="LR warmup steps (AdamWConfig.warmup_steps); "
                         "pair with --warm-start for the short re-warmup "
                         "that protects a fine-tuned checkpoint")
    cm.add_argument("--keep-opt-step", action="store_true",
                    help="with --warm-start: keep the optimizer's step "
                         "counter (LR schedule continues) instead of "
                         "resetting it (warmup restarts)")
    cm.add_argument("--split", default="random",
                    choices=["random", "manual"])
    cm.add_argument("--gnn", default="graphsage")
    cm.add_argument("--reduction", default="transformer")
    cm.add_argument("--hidden", type=int, default=model_defaults.hidden_dim)
    cm.add_argument("--max-nodes", type=int,
                    default=model_defaults.max_nodes)
    cm.add_argument("--lr", type=float, default=2e-3)
    cm.add_argument("--seed", type=int, default=0)
    cm.add_argument("--ckpt-dir", default="ckpts/cost_model")
    cm.add_argument("--ckpt-every", type=int, default=500)
    cm.add_argument("--log-every", type=int, default=100)
    cm.add_argument("--metrics-path", default="")
    cm.add_argument("--compress-grads", action="store_true")
    cm.add_argument("--no-resume", action="store_true")
    cm.add_argument("--dp", type=int, default=0,
                    help="data-parallel mesh size (0 = legacy single-device "
                         "path; >=1 runs the mesh train step, DESIGN.md "
                         "§13)")
    cm.add_argument("--mp", type=int, default=1,
                    help="model mesh axis size (params replicated)")
    cm.add_argument("--num-hosts", type=int, default=1,
                    help="total training hosts; this host's sampler draws "
                         "from its disjoint record shard")
    cm.add_argument("--host-id", type=int, default=0,
                    help="this host's index in [0, --num-hosts)")

    lm_p = sub.add_parser("lm")
    lm_p.add_argument("--arch", required=True)
    lm_p.add_argument("--smoke", action="store_true")
    lm_p.add_argument("--steps", type=int, default=5)
    lm_p.add_argument("--seq", type=int, default=64)
    lm_p.add_argument("--batch", type=int, default=4)
    lm_p.add_argument("--seed", type=int, default=0)

    args = ap.parse_args()
    if args.cmd == "cost-model":
        train_cost_model(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
