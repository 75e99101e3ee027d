"""Mesh train step (DESIGN.md §13): GlobalBatchSampler stacking, dp=1
bit-parity with a plain value_and_grad + AdamW reference, dp>=2 data
parallelism, compress composition, and cross-layout checkpoint restore.

Tests needing two devices skip on a single-device host; CI runs this file
once under XLA_FLAGS=--xla_force_host_platform_device_count=2 (the
tier-1 mesh-parity step) so they execute there, and
benchmarks/bench_scaling.py gates scaling and checkpoint elasticity
end-to-end in subprocesses with forced device counts.
"""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.losses import pairwise_rank_loss
from repro.core.model import CostModelConfig, cost_model_apply, cost_model_init
from repro.core.simulator import TPUSimulator
from repro.data.sampler import (
    BalancedSampler,
    GlobalBatchSampler,
    TileBatchSampler,
)
from repro.data.synthetic import generate_program, random_kernel
from repro.data.tile_dataset import build_tile_records, fit_tile_normalizer
from repro.sharding.context import shard_map_nocheck
from repro.sharding.mesh import DATA_AXIS, make_train_mesh
from repro.training.optim import adamw_init, adamw_update
from repro.training.trainer import CostModelTrainer, TrainerConfig, step_keys

needs_two = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=2")


@pytest.fixture(scope="module")
def tile_records():
    sim = TPUSimulator()
    kernels = [random_kernel(n, seed=i)
               for i, n in enumerate((10, 14, 18, 12, 16, 20))]
    return build_tile_records(kernels, sim, max_configs_per_kernel=8)


@pytest.fixture(scope="module")
def norm(tile_records):
    return fit_tile_normalizer(tile_records)


def _sampler(tile_records, norm, adjacency="sparse", **kw):
    return TileBatchSampler(tile_records, norm, seed=3, adjacency=adjacency,
                            kernels_per_batch=2, configs_per_kernel=4, **kw)


def _trainer(tile_records, norm, dp, adjacency="sparse", **cfg_kw):
    mcfg = CostModelConfig(hidden_dim=16, gnn_layers=1,
                           transformer_layers=1, adjacency=adjacency)
    cfg_kw.setdefault("ckpt_every", 0)
    cfg_kw.setdefault("seed", 0)
    cfg = TrainerConfig(task="tile", steps=3, log_every=100, dp=dp,
                        **cfg_kw)
    return CostModelTrainer(mcfg, cfg, _sampler(tile_records, norm,
                                                adjacency))


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


# ----------------------------------------------------- GlobalBatchSampler
def test_global_batch_stacks_with_device_axis(tile_records, norm):
    g = GlobalBatchSampler.for_mesh(_sampler(tile_records, norm), 2)
    b = g.batch(0)
    assert b.targets.shape[0] == 2 and b.valid.shape[0] == 2
    for leaf in jax.tree_util.tree_leaves(b.graphs):
        assert np.shape(leaf)[0] == 2
    # deterministic: same step -> identical global batch
    b2 = g.batch(0)
    np.testing.assert_array_equal(b.targets, b2.targets)
    for x, y in zip(jax.tree_util.tree_leaves(b.graphs),
                    jax.tree_util.tree_leaves(b2.graphs)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("adjacency", ["dense", "sparse", "segmented"])
def test_global_batch_dp1_is_base_stream_with_leading_axis(tile_records,
                                                           norm, adjacency):
    s = _sampler(tile_records, norm, adjacency)
    g = GlobalBatchSampler.for_mesh(_sampler(tile_records, norm, adjacency),
                                    1)
    for step in (0, 3):
        a, b = s.batch(step), g.batch(step)
        np.testing.assert_array_equal(a.targets, b.targets[0])
        np.testing.assert_array_equal(a.valid, b.valid[0])
        assert type(a.graphs) is type(b.graphs)
        for x, y in zip(jax.tree_util.tree_leaves(a.graphs),
                        jax.tree_util.tree_leaves(b.graphs), strict=True):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y)[0])


def test_global_batch_shards_draw_disjoint_records(tile_records, norm):
    s = _sampler(tile_records, norm)
    views = [s.with_host(d, 2) for d in range(2)]
    r0 = {id(r) for r in views[0].records}
    r1 = {id(r) for r in views[1].records}
    assert not r0 & r1
    assert len(r0) + len(r1) == len(tile_records)
    # multi-host x multi-device composition: host h of H, device d of dp
    # -> global worker h*dp+d of H*dp
    s_h1 = _sampler(tile_records, norm, host_id=1, num_hosts=2)
    g = GlobalBatchSampler.for_mesh(s_h1, 2)
    assert [v.host_id for v in g.samplers] == [2, 3]
    assert all(v.num_hosts == 4 for v in g.samplers)


def test_global_batch_sampler_rejects_bad_inputs(tile_records, norm):
    with pytest.raises(ValueError, match=">= 1"):
        GlobalBatchSampler([])
    seg = _sampler(tile_records, norm, adjacency="segmented")
    with pytest.raises(ValueError, match="segmented"):
        GlobalBatchSampler.for_mesh(seg, 2)
    dense = _sampler(tile_records, norm, adjacency="dense")
    sparse = _sampler(tile_records, norm, adjacency="sparse")
    with pytest.raises(ValueError, match="adjacencies"):
        GlobalBatchSampler([dense, sparse])


def test_global_batch_sparse_common_bucket(tile_records, norm):
    """All dp sub-batches of a sparse global batch share one BucketSpec,
    so a single executable serves every device."""
    g = GlobalBatchSampler.for_mesh(_sampler(tile_records, norm), 2)
    b = g.batch(1)
    ops = np.asarray(b.graphs.opcodes)
    assert ops.shape[0] == 2          # identical padded capacity per shard
    assert np.asarray(b.graphs.edge_src).shape[0] == 2


def test_balanced_sampler_shards_too(tile_records, norm):
    sim = TPUSimulator()
    from repro.data.fusion_dataset import build_fusion_records
    recs = []
    for i, fam in enumerate(("mlp", "norm")):
        recs.extend(build_fusion_records(generate_program(fam, i, 0), sim,
                                         configs_per_program=4))
    from repro.core.features import fit_normalizer
    fnorm = fit_normalizer([r.kernel for r in recs])
    s = BalancedSampler(recs, fnorm, batch_size=6, adjacency="dense")
    g = GlobalBatchSampler.for_mesh(s, 2)
    b = g.batch(0)
    assert b.targets.shape == (2, 6)
    np.testing.assert_array_equal(
        b.targets[0], s.with_host(0, 2).batch(0).targets)


# ----------------------------------------------------------- validation
def test_trainer_rejects_segmented_under_mesh(tile_records, norm):
    """Segmented training runs at dp=1 only; dp=2 is refused before the
    mesh is built, so a one-device host raises the same error."""
    with pytest.raises(ValueError, match="segmented.*dp=1"):
        _trainer(tile_records, norm, dp=2, adjacency="segmented")


@pytest.mark.parametrize("dp", [0, -1])
def test_trainer_rejects_dp_below_one(tile_records, norm, dp):
    with pytest.raises(ValueError, match="dp must be >= 1"):
        _trainer(tile_records, norm, dp=dp)


def test_make_train_mesh_errors_name_the_fix():
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_train_mesh(jax.device_count() + 1)
    with pytest.raises(ValueError, match=">= 1"):
        make_train_mesh(0)


def test_trainer_rejects_mismatched_global_sampler(tile_records, norm):
    mcfg = CostModelConfig(hidden_dim=16, gnn_layers=1, adjacency="sparse")
    g = GlobalBatchSampler.for_mesh(_sampler(tile_records, norm), 2)
    with pytest.raises(ValueError, match="shards"):
        CostModelTrainer(mcfg, TrainerConfig(task="tile", dp=1), g)


# ------------------------------------------------------------ bit-parity
@pytest.mark.parametrize("adjacency", ["dense", "sparse", "segmented"])
def test_dp1_step_matches_plain_reference(tile_records, norm, adjacency):
    """TrainerConfig(dp=1) trains exactly as a plain jitted value_and_grad
    + AdamW step on the base sampler's batches with device 0's dropout
    key: same loss float, byte-identical params, after 3 steps."""
    t = _trainer(tile_records, norm, dp=1, adjacency=adjacency)
    mcfg, cfg = t.model_cfg, t.cfg
    res = t.run(resume=False)

    @jax.jit
    def ref_step(params, opt, graphs, targets, groups, valid, base, step):
        def loss_fn(p):
            preds = cost_model_apply(p, mcfg, graphs,
                                     rng=step_keys(base, step, 1)[0],
                                     deterministic=False)
            return pairwise_rank_loss(preds, targets, groups, valid)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt, _ = adamw_update(params, grads, opt, cfg.optim)
        return params, opt, loss

    params = cost_model_init(jax.random.key(cfg.seed), mcfg)
    opt = adamw_init(params)
    base = _sampler(tile_records, norm, adjacency)
    for step in range(cfg.steps):
        b = base.batch(step)
        params, opt, loss = ref_step(params, opt, b.graphs, b.targets,
                                     b.group_ids, b.valid,
                                     jax.random.key(cfg.seed + 1),
                                     np.int32(step))
    assert res["loss"] == float(loss)
    for a, b in zip(_leaves(params), _leaves(t.params), strict=True):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------ step keys
@pytest.mark.parametrize("step", [0, 1, 37, 2**20])
@pytest.mark.parametrize("dp", [1, pytest.param(2, marks=needs_two)])
def test_step_keys_in_jit_match_host_ladder(dp, step):
    """The keys the jitted step folds from the step number are the host
    ladder fold_in(key(seed + 1), step*dp + d) of DESIGN.md §13, byte for
    byte, and under the mesh device d holds key d."""
    base = jax.random.key(2**31 + 8)      # key(seed + 1), seed 2**31 + 7
    data = jax.random.key_data
    per_device = shard_map_nocheck(data, make_train_mesh(dp),
                                   in_specs=P(DATA_AXIS),
                                   out_specs=P(DATA_AXIS))
    got = jax.jit(lambda k, s: per_device(step_keys(k, s, dp)))(
        base, np.int32(step))
    want = [np.asarray(data(jax.random.fold_in(base, step * dp + d)))
            for d in range(dp)]
    np.testing.assert_array_equal(np.asarray(got), np.stack(want))


@pytest.mark.parametrize("adjacency", ["sparse", "segmented"])
def test_step_number_adds_no_compile(tile_records, norm, adjacency):
    """The step number is a traced argument of the train step: after a
    warm-up run, eight more steps of the same bucket compile nothing."""
    t = _trainer(tile_records, norm, dp=1, adjacency=adjacency)
    sampler = t.sampler

    class Replay:
        def batch(self, step):
            return sampler.batch(0)

    t.sampler = Replay()
    t.run(steps=2, resume=False)
    compiles = []

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        res = t.run(steps=10, resume=False)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert res["step"] == 10 and np.isfinite(res["loss"])
    assert compiles == []


@pytest.mark.parametrize("adjacency", ["sparse", "dense"])
def test_train_step_program_is_the_same_for_every_seed(tile_records, norm,
                                                       adjacency):
    """The seed reaches the train step as an argument, not as a constant
    of its trace: trainers of two seeds lower the same program, so one
    persistent-cache entry serves every seed."""
    def program(seed):
        t = _trainer(tile_records, norm, dp=1, adjacency=adjacency,
                     seed=seed)
        b = t.sampler.batch(0)
        return t._train_step.lower(
            t.params, t.opt_state, b.graphs, b.targets, b.group_ids,
            b.valid, t._rng_base, np.int32(0)).as_text()

    assert program(0) == program(2**31 + 7)


# --------------------------------------------------------- two devices
@needs_two
def test_dp2_trains_on_disjoint_shards(tile_records, norm):
    t = _trainer(tile_records, norm, dp=2)
    assert isinstance(t.sampler, GlobalBatchSampler)
    assert t.sampler.num_shards == 2
    res = t.run(resume=False)
    assert res["step"] == 3 and np.isfinite(res["loss"])


@needs_two
def test_dp2_compress_composes_with_sparse(tile_records, norm):
    t = _trainer(tile_records, norm, dp=2, compress_grads=True)
    for leaf in jax.tree_util.tree_leaves(t.opt_state["ef"]):
        assert np.shape(leaf)[0] == 2        # per-device residuals
    res = t.run(resume=False)
    assert np.isfinite(res["loss"])


@needs_two
def test_ckpt_dp2_restores_dp1_bit_exact(tile_records, norm, tmp_path):
    t2 = _trainer(tile_records, norm, dp=2, ckpt_dir=str(tmp_path),
                  ckpt_every=3)
    t2.run(resume=False)
    t1 = _trainer(tile_records, norm, dp=1, ckpt_dir=str(tmp_path))
    assert t1.maybe_resume()
    assert t1.step == 3
    for a, b in zip(_leaves(t2.params), _leaves(t1.params)):
        np.testing.assert_array_equal(a, b)
    # and the restored run continues
    t1.cfg.steps = 4
    res = t1.run(resume=True)
    assert res["step"] == 4


@needs_two
def test_ckpt_dp2_compress_restore_reinits_ef(tile_records, norm, tmp_path):
    t2 = _trainer(tile_records, norm, dp=2, compress_grads=True,
                  ckpt_dir=str(tmp_path), ckpt_every=3)
    t2.run(resume=False)
    t1 = _trainer(tile_records, norm, dp=1, compress_grads=True,
                  ckpt_dir=str(tmp_path))
    assert t1.maybe_resume()
    for a, b in zip(_leaves(t2.params), _leaves(t1.params)):
        np.testing.assert_array_equal(a, b)
    for leaf in _leaves(t1.opt_state["ef"]):
        assert leaf.shape[0] == 1 and not leaf.any()
