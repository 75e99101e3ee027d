"""Training the tile-size model on a simulator-labelled tile corpus, as
`repro.launch.train cost-model --task tile` builds it."""
from __future__ import annotations

import common
import reference

LOOP = "train"
TASK = "tile"


def corpus(cfg: dict, mix: dict, seed: int):
    """The seed's tile corpus (simulator labels, random program split, the
    train split) in the program's sampler, and the feature normalizer's
    min/max over each kernel's smallest, median and largest tile, as
    `repro.data.tile_dataset.fit_tile_normalizer` picks them."""
    from repro.core.simulator import TPUSimulator
    from repro.data.corpus import filter_by_programs, split_programs
    from repro.data.sampler import TileBatchSampler
    from repro.data.synthetic import generate_corpus
    from repro.data.tile_dataset import build_tile_dataset
    mc = common.model_config(cfg)
    programs = generate_corpus(mix["programs"], seed=seed)
    split = split_programs([p.program for p in programs], method="random",
                           seed=seed)
    ds = build_tile_dataset(programs, TPUSimulator(),
                            max_configs_per_kernel=mix["max_configs"])
    recs = filter_by_programs(ds.records, split["train"])
    norm = reference.fit_normalizer([
        reference.featurize(r.kernel.with_tile(r.tiles[i]).to_dict())
        for r in recs for i in {0, len(r.tiles) // 2, len(r.tiles) - 1}])
    sampler = TileBatchSampler(
        recs, common.normalizer(norm),
        kernels_per_batch=mix["kernels_per_step"],
        configs_per_kernel=mix["configs_per_kernel"],
        max_nodes=mc.max_nodes, seed=seed, adjacency=mc.adjacency)
    return sampler, norm


def reference_loss(preds, targets, groups, valid):
    return reference.rank_loss(preds, targets, groups, valid)
