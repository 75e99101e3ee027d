"""Training the fusion model on a simulator-labelled fusion corpus, as
`repro.launch.train cost-model --task fusion` builds it."""
from __future__ import annotations

import common
import reference

LOOP = "train"
TASK = "fusion"


def corpus(cfg: dict, mix: dict, seed: int):
    """The seed's fusion corpus (the kernels of each program's default and
    random fusion decisions, simulator labels, random program split, the
    train split) in the program's balanced sampler, and the feature
    normalizer's min/max over every train kernel, as `repro.launch.train`
    fits it."""
    from repro.core.simulator import TPUSimulator
    from repro.data.corpus import filter_by_programs, split_programs
    from repro.data.fusion_dataset import build_fusion_dataset
    from repro.data.sampler import BalancedSampler
    from repro.data.synthetic import generate_corpus
    mc = common.model_config(cfg)
    programs = generate_corpus(mix["programs"], seed=seed)
    split = split_programs([p.program for p in programs], method="random",
                           seed=seed)
    ds = build_fusion_dataset(programs, TPUSimulator(),
                              configs_per_program=mix["configs_per_program"],
                              max_kernel_nodes=mc.max_nodes, seed=seed)
    recs = filter_by_programs(ds.records, split["train"])
    norm = reference.fit_normalizer([reference.featurize(r.kernel.to_dict())
                                     for r in recs])
    sampler = BalancedSampler(
        recs, common.normalizer(norm), batch_size=mix["kernels_per_device"],
        max_nodes=mc.max_nodes, seed=seed, adjacency=mc.adjacency)
    return sampler, norm


def reference_loss(preds, targets, groups, valid):
    return reference.log_mse_loss(preds, targets, valid)
