"""End-to-end production driver: train the fusion-task cost model for a few
hundred steps with the full substrate — corpus incl. programs imported from
the assigned architectures, train/val/test splits, checkpointing + resume,
JSONL metrics, periodic eval — then hand the model to both autotuners.

  PYTHONPATH=src python examples/train_cost_model.py [--steps 600]
      [--adjacency dense|sparse] [--prefetch 2] [--dp N]
      [--num-hosts H --host-id h]

--adjacency selects the batched-graph representation end-to-end (sampler,
trainer, evaluation, autotuner): 'dense' pads each kernel to a [N, N]
adjacency slot; 'sparse' packs kernels into bucketed flat node/edge buffers
(segment-sum aggregation — same numerics, much higher throughput on
mixed-size corpora; see DESIGN.md §4 and benchmarks/bench_batching.py).

--prefetch encodes that many batches ahead on a background thread
(byte-identical batch stream; DESIGN.md §9, 0 = synchronous).

--store DIR makes the corpus a durable artifact (docs/DATA.md): the first
run fans generation + oracle measurement across worker processes into a
sharded on-disk store under DIR, and every later run streams the records
shard-by-shard from disk instead of rebuilding them (build once, reuse
forever — rebuilding an unchanged spec is a manifest-hash no-op).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.autotuner import model_cost_fn, simulated_annealing_fusion
from repro.core.evaluate import (
    eval_fusion_task,
    learned_runtime_predictor,
    make_predict_fn,
)
from repro.core.features import fit_normalizer
from repro.core.hlo_import import import_arch_program
from repro.core.model import CostModelConfig
from repro.core.simulator import TPUSimulator
from repro.data.corpus import filter_by_programs, split_programs
from repro.data.fusion_dataset import FusionDataset, build_fusion_dataset
from repro.data.sampler import BalancedSampler
from repro.data.synthetic import generate_corpus
from repro.training.optim import AdamWConfig
from repro.training.trainer import CostModelTrainer, TrainerConfig

MAX_NODES = 48


def _rebuild_program(name: str):
    """Regenerate one pre-fusion program graph by its corpus name —
    `arch_<zoo-name>` imports that architecture, `<family>_<idx>` re-runs
    the deterministic synthetic generator."""
    if name.startswith("arch_"):
        return import_arch_program(name[len("arch_"):])
    from repro.data.synthetic import generate_program
    family, idx = name.rsplit("_", 1)
    return generate_program(family, int(idx), seed=0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--ckpt-dir", default="ckpts/fusion_model")
    ap.add_argument("--adjacency", choices=("dense", "sparse"),
                    default="dense")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="batches encoded ahead by a background thread "
                         "(0 = synchronous)")
    ap.add_argument("--store", default="",
                    help="corpus store root: built on the first run, "
                         "streamed from disk on every later run")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel mesh size: the mesh train step runs "
                         "on dp local devices (on CPU, export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=<dp> "
                         "first)")
    ap.add_argument("--num-hosts", type=int, default=1,
                    help="total training hosts (the sampler draws from "
                         "this host's disjoint record shard)")
    ap.add_argument("--host-id", type=int, default=0,
                    help="this host's index in [0, --num-hosts)")
    args = ap.parse_args()
    if args.num_hosts < 1 or not 0 <= args.host_id < args.num_hosts:
        ap.error(f"--host-id must be in [0, --num-hosts={args.num_hosts}), "
                 f"got {args.host_id}")

    # ---- data: synthetic families + imported architectures
    sim = TPUSimulator()
    archs = ("yi-9b", "mamba2-2.7b", "granite-moe-3b-a800m")
    if args.store:
        # build-once-reuse-forever: a no-op when the spec is unchanged.
        # Generation (incl. the jaxpr arch imports) happens in the builder
        # workers on the first run only — warm runs touch no generator.
        from repro.data.store import StreamingCorpus
        from repro.launch.build_corpus import build_corpus
        build_corpus(args.store, kinds=("fusion",), programs=24, seed=0,
                     import_archs=archs, workers=os.cpu_count() or 1,
                     fusion_opts={"configs_per_program": 10})
        corpus = StreamingCorpus.open(os.path.join(args.store, "fusion"))
        names = corpus.programs()
        split = split_programs(names, method="random")
        train_recs = corpus.select_programs(split["train"])
        test_recs = list(corpus.select_programs(split["test"]))
        num_samples = len(corpus)
    else:
        programs = generate_corpus(24, seed=0)
        for arch in archs:
            programs.append(import_arch_program(arch))
        ds = build_fusion_dataset(programs, sim, configs_per_program=10)
        names = [p.program for p in programs]
        split = split_programs(names, method="random")
        train_recs = filter_by_programs(ds.records, split["train"])
        test_recs = filter_by_programs(ds.records, split["test"])
        num_samples = ds.num_samples
    norm = fit_normalizer([r.kernel for r in train_recs])
    print(f"{len(names)} programs -> {num_samples} kernels "
          f"({len(train_recs)} train / {len(test_recs)} test)")

    # ---- model + trainer (checkpointed; rerun to resume)
    mc = CostModelConfig(gnn="graphsage", reduction="transformer",
                         hidden_dim=64, opcode_embed_dim=16,
                         max_nodes=MAX_NODES, adjacency=args.adjacency)
    sampler = BalancedSampler(train_recs, norm, batch_size=24,
                              max_nodes=MAX_NODES, adjacency=mc.adjacency,
                              host_id=args.host_id,
                              num_hosts=args.num_hosts)

    def eval_fn(params, step):
        pred = learned_runtime_predictor(params, mc, norm,
                                         max_nodes=MAX_NODES, chunk=32)
        res = eval_fusion_task(FusionDataset(test_recs), pred)
        return {"test_mape": res["mean_mape"],
                "test_kendall": res["mean_kendall"]}

    trainer = CostModelTrainer(
        mc,
        TrainerConfig(task="fusion", steps=args.steps, ckpt_every=200,
                      log_every=100, ckpt_dir=args.ckpt_dir,
                      metrics_path=os.path.join(args.ckpt_dir,
                                                "metrics.jsonl"),
                      prefetch=args.prefetch, dp=args.dp,
                      optim=AdamWConfig(lr=2e-3)),
        sampler)
    res = trainer.run(eval_fn=eval_fn, eval_every=200)
    print(f"training done at step {res['step']}: loss={res['loss']:.4f}")

    ev = eval_fn(trainer.params, res["step"])
    print(f"held-out programs: MAPE {ev['test_mape']:.1f}%  "
          f"Kendall {ev['test_kendall']:.3f}")

    # ---- hand the model to the fusion autotuner on a held-out program
    # (representation follows mc.adjacency: sparse scores every candidate
    # kernel; dense drops kernels above MAX_NODES)
    model_cost = model_cost_fn(trainer.params, mc, norm,
                               max_nodes=MAX_NODES, chunk=32,
                               predict_fn=make_predict_fn(mc))

    if args.store:
        # rebuild just the one target program (generation is deterministic
        # and cheap; only this name is re-imported/re-generated)
        target = _rebuild_program(split["test"][0])
    else:
        by_name = {p.program: p for p in programs}
        target = by_name[split["test"][0]]
    r = simulated_annealing_fusion(target, sim, model_cost=model_cost,
                                   hardware_budget_s=10, model_steps=200,
                                   seed=0)
    print(f"fusion autotuner on held-out {target.name}: "
          f"{r.speedup:.3f}x speedup over compiler default with only "
          f"{r.hardware_evals} hardware evals")


if __name__ == "__main__":
    main()
