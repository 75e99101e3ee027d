"""Flywheel tests: MeasurementLog cumulative flush semantics, delta
chain tamper detection, variance/LCB acquisition routing, trainer
warm-start (params + moments, step handling), and the train.py CLI
validation around --warm-start/--deltas.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import sys

import numpy as np
import pytest

from repro.core.simulator import TPUSimulator
from repro.data.store import (
    CorpusFormatError,
    CorpusWriter,
    StreamingCorpus,
    load_delta_manifests,
    load_manifest,
    write_corpus,
)
from repro.data.synthetic import random_kernel
from repro.data.tile_dataset import TileKernelRecord
from repro.flywheel import MeasurementLog
from repro.search import HardwareEstimator
from repro.search.acquisition import route_variance


def _sweep_record(seed, tiles, program="p"):
    k = random_kernel(6, seed=seed, program=program)
    rts = np.linspace(1e-4, 2e-4, len(tiles))
    return TileKernelRecord(kernel=k, tiles=list(tiles),
                            runtimes=np.asarray(rts, np.float64),
                            program=program)


# ------------------------------------------------------- MeasurementLog
def test_log_groups_and_dedups_tile_variants():
    log = MeasurementLog("tile")
    hw = HardwareEstimator(TPUSimulator(), log=log)
    g = random_kernel(8, seed=0)
    hw.estimate([g.with_tile((8, 8)), g.with_tile((16, 8))])
    hw.estimate([g.with_tile((8, 8))])          # repeat -> dedup
    assert (len(log), log.duplicates) == (2, 1)
    recs = log.records()
    assert len(recs) == 1 and recs[0].tiles == [(8, 8), (16, 8)]


def test_take_pending_reemits_grown_sweeps_cumulatively():
    """One tile per round still yields multi-config records from the
    second flush on: a flush re-emits a changed group's WHOLE sweep."""
    log = MeasurementLog("tile")
    g = random_kernel(8, seed=1)
    log.record(g.with_tile((8, 8)), 1e-4)
    assert [r.tiles for r in log.take_pending()] == [[(8, 8)]]
    assert log.take_pending() == []             # nothing new
    log.record(g.with_tile((16, 8)), 2e-4)
    assert [r.tiles for r in log.take_pending()] == [[(8, 8), (16, 8)]]
    assert log.take_pending() == []


def test_take_pending_min_configs_holds_back_unmarked():
    """A 1-tile group is held back by min_configs=2 — and NOT marked, so
    it flushes (whole) once it grows past the threshold."""
    log = MeasurementLog("tile")
    g = random_kernel(8, seed=2)
    log.record(g.with_tile((8, 8)), 1e-4)
    assert log.take_pending(min_configs=2) == []
    log.record(g.with_tile((16, 8)), 2e-4)
    assert ([r.tiles for r in log.take_pending(min_configs=2)]
            == [[(8, 8), (16, 8)]])


# ------------------------------------------------ delta chain integrity
@pytest.fixture
def tile_store(tmp_path):
    base = [_sweep_record(s, [(8, 8), (16, 8)], program=f"p{s}")
            for s in range(3)]
    d = str(tmp_path / "store")
    write_corpus(d, "tile", base, dedup=True)
    return d, base


def test_chained_view_matches_scratch_rebuild(tile_store, tmp_path):
    store_dir, base = tile_store
    d0 = [_sweep_record(10, [(4, 4)], program="x")]
    d1 = [_sweep_record(10, [(4, 4), (8, 4)], program="x")]  # grown sweep
    assert CorpusWriter.append_delta(store_dir, d0) is not None
    assert CorpusWriter.append_delta(store_dir, d1) is not None
    chained = StreamingCorpus.open(store_dir).with_deltas()
    rebuild_dir = str(tmp_path / "rebuild")
    write_corpus(rebuild_dir, "tile", base + d0 + d1, dedup=True)
    rebuilt = StreamingCorpus.open(rebuild_dir)
    assert len(chained) == len(rebuilt) == 5
    for a, b in zip(chained, rebuilt):
        assert a.tiles == b.tiles
        assert np.array_equal(a.runtimes, b.runtimes)
        digest = a.kernel.structural_digest(order_sensitive=True)
        assert digest == b.kernel.structural_digest(order_sensitive=True)


def test_append_delta_dedups_against_chain(tile_store):
    store_dir, base = tile_store
    extra = [_sweep_record(20, [(4, 4)], program="y")]
    assert CorpusWriter.append_delta(store_dir, extra) is not None
    # whole batch already in chain -> nothing written, no new manifest
    assert CorpusWriter.append_delta(store_dir, base + extra) is None
    assert len(load_delta_manifests(store_dir)) == 1


def test_delta_manifest_tamper_detected(tile_store):
    store_dir, _ = tile_store
    CorpusWriter.append_delta(store_dir, [_sweep_record(30, [(4, 4)])])
    path = os.path.join(store_dir, "delta-00000.json")
    tampered = open(path).read().replace('"delta_seq": 0',
                                         '"delta_seq": 0, "evil": 1')
    with open(path, "w") as f:
        f.write(tampered)
    with pytest.raises(CorpusFormatError, match="manifest hash mismatch"):
        load_delta_manifests(store_dir)


def test_delta_wrong_base_detected(tile_store, tmp_path):
    """A delta copied onto a different base store must not load."""
    store_dir, _ = tile_store
    CorpusWriter.append_delta(store_dir, [_sweep_record(31, [(4, 4)])])
    other = str(tmp_path / "other")
    write_corpus(other, "tile", [_sweep_record(40, [(8, 8)])], dedup=True)
    for name in os.listdir(store_dir):
        if name.startswith("delta-"):
            with open(os.path.join(store_dir, name), "rb") as src, \
                    open(os.path.join(other, name), "wb") as dst:
                dst.write(src.read())
    with pytest.raises(CorpusFormatError, match="base"):
        load_delta_manifests(other)


def test_delta_shard_corruption_detected(tile_store):
    store_dir, base = tile_store
    CorpusWriter.append_delta(store_dir, [_sweep_record(32, [(4, 4)])])
    shard = os.path.join(store_dir, "delta-00000-00000.npz")
    blob = bytearray(open(shard, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(shard, "wb") as f:
        f.write(bytes(blob))
    chained = StreamingCorpus.open(store_dir).with_deltas()
    with pytest.raises(CorpusFormatError, match="checksum"):
        chained[len(base)]                      # first delta record


# ------------------------------------------------- acquisition routing
def test_route_variance_budget_and_exclude():
    stds = [[0.9, 0.1, 0.5], [0.6, 0.4]]
    plan = route_variance(stds, 3, spread="global")
    assert plan == [(0, 0), (1, 0), (0, 2)]
    assert len(route_variance(stds, 99, spread="kernel")) == 5
    assert route_variance(stds, 0) == []
    plan = route_variance(stds, 5, spread="kernel",
                          exclude={(0, 0), (1, 0)})
    assert (0, 0) not in plan and (1, 0) not in plan and len(plan) == 3


def test_route_variance_lcb_ranks_mean_minus_kappa_std():
    means = [[2.0, 0.0], [1.0, 3.0]]
    stds = [[0.1, 0.1], [2.0, 0.1]]
    # kappa=1: LCB = [1.9, -0.1, -1.0, 2.9] -> (1,0) then (0,1)
    assert route_variance(stds, 2, spread="global", means=means,
                          kappa=1.0) == [(1, 0), (0, 1)]
    # kappa=0 is pure exploitation: lowest mean first
    assert route_variance(stds, 2, spread="global", means=means,
                          kappa=0.0) == [(0, 1), (1, 0)]


def test_route_variance_rejects_unknown_spread():
    with pytest.raises(ValueError, match="spread"):
        route_variance([[1.0]], 1, spread="everywhere")


# ------------------------------------------------- trainer warm start
def _tiny_trainer(tmp_path, name, steps=8, lr=3e-3):
    from repro.core.model import CostModelConfig
    from repro.data.sampler import TileBatchSampler
    from repro.data.tile_dataset import fit_tile_normalizer
    from repro.training.optim import AdamWConfig
    from repro.training.trainer import CostModelTrainer, TrainerConfig

    recs = [_sweep_record(s, [(4, 4), (8, 8), (16, 8)], program=f"p{s}")
            for s in range(4)]
    norm = fit_tile_normalizer(recs)
    sampler = TileBatchSampler(recs, norm, kernels_per_batch=2,
                               configs_per_kernel=3, max_nodes=16)
    mc = CostModelConfig(hidden_dim=16, opcode_embed_dim=4, max_nodes=16,
                         reduction="per_node", gnn_layers=1,
                         node_final_layers=1)
    tc = TrainerConfig(task="tile", steps=steps, ckpt_every=steps,
                       log_every=steps, ckpt_dir=str(tmp_path / name),
                       optim=AdamWConfig(lr=lr))
    return CostModelTrainer(mc, tc, sampler)


def test_warm_start_restores_params_and_step_semantics(tmp_path):
    tr = _tiny_trainer(tmp_path, "a", steps=8)
    tr.run(resume=False)
    src_step = int(tr.opt_state["step"])
    assert src_step == 8

    warm = _tiny_trainer(tmp_path, "b")
    from_step = warm.warm_start(str(tmp_path / "a"))
    assert from_step == 8
    assert warm.step == 0                       # run still trains fully
    assert int(warm.opt_state["step"]) == 0     # LR warmup restarts
    flat_a = np.concatenate([np.ravel(x) for x in
                             _leaves(tr.params)])
    flat_b = np.concatenate([np.ravel(x) for x in
                             _leaves(warm.params)])
    assert np.array_equal(flat_a, flat_b)
    # AdamW moments came along too (non-zero after 8 source steps)
    assert any(float(np.abs(x).sum()) > 0 for x in
               _leaves(warm.opt_state["m"]))


def test_warm_start_keep_opt_step_preserves_schedule(tmp_path):
    tr = _tiny_trainer(tmp_path, "a", steps=8)
    tr.run(resume=False)
    warm = _tiny_trainer(tmp_path, "b")
    warm.warm_start(str(tmp_path / "a"), reset_opt_step=False)
    assert int(warm.opt_state["step"]) == 8     # schedule continues
    assert warm.step == 0


def test_warm_start_missing_checkpoint_raises(tmp_path):
    warm = _tiny_trainer(tmp_path, "b")
    with pytest.raises(FileNotFoundError, match="warm-start"):
        warm.warm_start(str(tmp_path / "nowhere"))


def _leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


# ------------------------------------------------------ train.py CLI
def _run_cli(monkeypatch, *argv):
    from repro.launch.train import main
    monkeypatch.setattr(sys, "argv", ["train.py", *argv])
    main()


def test_cli_deltas_requires_from_store(monkeypatch):
    with pytest.raises(SystemExit, match="--deltas only applies"):
        _run_cli(monkeypatch, "cost-model", "--deltas")


@pytest.mark.parametrize("dp", ["0", "-1"])
def test_cli_rejects_dp_below_one(monkeypatch, dp):
    with pytest.raises(SystemExit, match="--dp must be >= 1"):
        _run_cli(monkeypatch, "cost-model", "--dp", dp)


def test_cli_warm_start_needs_existing_checkpoint(monkeypatch, tmp_path):
    with pytest.raises(SystemExit, match="no checkpoint found"):
        _run_cli(monkeypatch, "cost-model",
                 "--warm-start", str(tmp_path / "empty"))


def test_cli_warm_start_must_differ_from_ckpt_dir(monkeypatch, tmp_path):
    from repro.training.checkpoint import save_checkpoint
    ck = str(tmp_path / "ck")
    save_checkpoint(ck, 3, {"params": {"w": np.zeros(2, np.float32)}})
    with pytest.raises(SystemExit, match="DIFFERENT"):
        _run_cli(monkeypatch, "cost-model",
                 "--warm-start", ck, "--ckpt-dir", ck)


def test_store_kind_mismatch_refused(monkeypatch, tile_store):
    store_dir, _ = tile_store
    with pytest.raises(SystemExit, match="needs 'fusion'"):
        _run_cli(monkeypatch, "cost-model", "--task", "fusion",
                 "--from-store", store_dir)


def test_manifest_present_after_deltas(tile_store):
    """Base manifest is untouched by appends (deltas chain off it)."""
    store_dir, _ = tile_store
    before = load_manifest(store_dir)["manifest_hash"]
    CorpusWriter.append_delta(store_dir, [_sweep_record(33, [(4, 4)])])
    assert load_manifest(store_dir)["manifest_hash"] == before
