"""Int8 quantized inference (DESIGN.md §14): scale-math round trips,
int8-vs-f32 prediction fidelity (rank correlation), the fused Pallas
sparse path vs the jnp path, the checkpoint sidecar, serving integration
(QuantizedCostModel backends, snapshot meta binding), and the config /
trainer validation guards."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import features as F
from repro.core.model import CostModelConfig, cost_model_apply, \
    cost_model_init
from repro.data import batching
from repro.data.synthetic import random_kernel
from repro.quant.quantize import (
    calibrate_activations,
    dequantize_params,
    load_quantized,
    quantize_params,
    save_quantized,
    tree_bytes,
)
from repro.quant.scale import (
    QuantizedLeaf,
    amax_scale,
    dequantize_int8,
    per_channel_scale,
    quantize_int8,
    tree_is_quantized,
)

SIZES = [5, 12, 3, 20, 1, 17]


def _graphs(sizes=None, seed0=0):
    return [random_kernel(n, seed=seed0 + i)
            for i, n in enumerate(sizes or SIZES)]


def _cfg(**kw):
    base = dict(hidden_dim=32, opcode_embed_dim=8, max_nodes=24,
                dropout=0.0, adjacency="sparse", reduction="per_node")
    base.update(kw)
    return CostModelConfig(**base)


def _predict(params, cfg, graphs, norm):
    batch = batching.encode_packed(graphs, norm)
    return np.asarray(cost_model_apply(params, cfg, batch))[:len(graphs)]


# ----------------------------------------------------------------------------
# scale math (repro.quant.scale — shared with training.compression)
# ----------------------------------------------------------------------------
def test_quantize_dequantize_round_trip_exact():
    """dequantize∘quantize of an already-quantized array is the identity."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3, (64, 48)), jnp.float32)
    s = per_channel_scale(x)
    q = quantize_int8(x, s)
    assert q.dtype == jnp.int8
    q2 = quantize_int8(dequantize_int8(q, s), s)
    assert jnp.array_equal(q, q2)


def test_quantized_leaf_round_trip_and_pytree():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(0, 1, (16, 8)), jnp.float32)
    leaf = QuantizedLeaf.quantize(w)
    assert leaf.shape == w.shape and leaf.q.dtype == jnp.int8
    # flatten/unflatten preserves both arrays
    flat, tree = jax.tree_util.tree_flatten(leaf)
    back = jax.tree_util.tree_unflatten(tree, flat)
    assert jnp.array_equal(back.q, leaf.q)
    assert jnp.array_equal(back.scale, leaf.scale)
    assert tree_is_quantized({"a": leaf}) and not tree_is_quantized({"a": w})


def test_scale_matches_compression_allreduce_math():
    """One copy of the int8 math: the gradient-compression path computes
    bit-identical (q, scale) to the quant primitives it now imports."""
    from repro.training.compression import compress_int8, decompress_int8
    rng = np.random.default_rng(2)
    g = jnp.asarray(rng.normal(0, 0.1, (32, 32)), jnp.float32)
    scale = amax_scale(jnp.max(jnp.abs(g)))
    q, err = compress_int8(g, scale)
    assert jnp.array_equal(q, quantize_int8(g, scale))
    assert jnp.array_equal(decompress_int8(q, scale),
                           dequantize_int8(q, scale))
    # error feedback is exactly the rounding residual
    np.testing.assert_allclose(np.asarray(err),
                               np.asarray(g - dequantize_int8(q, scale)),
                               rtol=0, atol=0)


@given(st.integers(min_value=0, max_value=1000))
@settings(max_examples=25, deadline=None)
def test_quantization_error_bounded_by_half_scale(seed):
    """|x - dq(q(x))| <= scale/2 elementwise whenever |x| <= amax (the
    clip never engages at the abs-max that defined the scale)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, rng.uniform(0.01, 10), (17, 9)),
                    jnp.float32)
    s = per_channel_scale(x)
    err = jnp.abs(x - dequantize_int8(quantize_int8(x, s), s))
    assert bool(jnp.all(err <= 0.5 * s + 1e-7))


def test_all_zero_channel_quantizes_to_zero():
    x = jnp.zeros((8, 4))
    s = per_channel_scale(x)
    assert bool(jnp.all(s > 0))          # floored, never a div-by-zero
    assert bool(jnp.all(dequantize_int8(quantize_int8(x, s), s) == 0))


# ----------------------------------------------------------------------------
# quantize_params / QuantizedCostModel
# ----------------------------------------------------------------------------
def test_quantize_params_selects_weight_leaves():
    cfg = _cfg(scan_layers=True)
    params = cost_model_init(jax.random.key(0), cfg)
    qm = quantize_params(params, cfg)
    assert qm.num_quantized > 0
    assert qm.quantized_bytes() < tree_bytes(params)
    # small leaves survive as f32, big matrices are all quantized
    from repro.quant.quantize import DEFAULT_MIN_SIZE, _is_qleaf
    for leaf in jax.tree_util.tree_leaves(qm.params, is_leaf=_is_qleaf):
        if _is_qleaf(leaf):
            assert leaf.q.ndim >= 2 and leaf.q.size >= DEFAULT_MIN_SIZE
        else:
            assert (leaf.ndim < 2 or leaf.size < DEFAULT_MIN_SIZE
                    or not jnp.issubdtype(leaf.dtype, jnp.floating))
    # stacked [L, ...] GNN leaves carry per-layer AND per-channel scales,
    # so lax.scan slices q and scale along L together
    stacked = qm.params["gnn"]["stacked"]["f2_in"]["w"]
    assert isinstance(stacked, QuantizedLeaf)
    assert stacked.scale.shape[0] == stacked.q.shape[0]
    assert stacked.scale.shape[-1] == stacked.q.shape[-1]


def test_dequantize_round_trip_close():
    cfg = _cfg()
    params = cost_model_init(jax.random.key(0), cfg)
    qm = quantize_params(params, cfg)
    back = dequantize_params(qm)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_flatten_with_path(back)[0]):
        assert pa == pb
        amax = float(jnp.max(jnp.abs(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=amax / 127 * 0.5 + 1e-7)


@pytest.mark.parametrize("scan_layers", [False, True],
                         ids=["unrolled", "scan"])
def test_int8_predictions_close_to_f32(scan_layers):
    graphs = _graphs()
    norm = F.fit_normalizer(graphs)
    cfg = _cfg(scan_layers=scan_layers)
    params = cost_model_init(jax.random.key(0), cfg)
    qm = quantize_params(params, cfg)
    pf = _predict(params, cfg, graphs, norm)
    pq = _predict(qm.params, qm.serving_config(), graphs, norm)
    assert np.max(np.abs(pf - pq)) < 0.35 * max(np.std(pf), 0.1)


def _kendall(a, b):
    n = len(a)
    con = dis = 0
    for i in range(n):
        for j in range(i + 1, n):
            s = (a[i] - a[j]) * (b[i] - b[j])
            con += s > 0
            dis += s < 0
    total = con + dis
    return (con - dis) / total if total else 1.0


@given(st.integers(min_value=0, max_value=10 ** 6))
@settings(max_examples=8, deadline=None)
def test_int8_rank_correlation_property(seed):
    """Int8 serving must preserve the f32 model's *ranking* of candidate
    kernels — the quantity tile/fusion search consumes — on arbitrary
    synthetic corpora (near-constant prediction sets are vacuous and
    exempted)."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(2, 24, 10).tolist()
    graphs = _graphs(sizes, seed0=seed % 9973)
    norm = F.fit_normalizer(graphs)
    cfg = _cfg()
    params = cost_model_init(jax.random.key(seed % 101), cfg)
    qm = quantize_params(params, cfg)
    pf = _predict(params, cfg, graphs, norm)
    pq = _predict(qm.params, qm.serving_config(), graphs, norm)
    if np.std(pf) < 1e-3:                 # degenerate: nothing to rank
        return
    assert _kendall(pf, pq) >= 0.8


def test_calibration_records_f1_and_gnn_stages():
    graphs = _graphs()
    norm = F.fit_normalizer(graphs)
    cfg = _cfg()
    params = cost_model_init(jax.random.key(0), cfg)
    scales = calibrate_activations(params, cfg, graphs, norm)
    assert scales["f1"] > 0
    for i in range(cfg.gnn_layers):
        assert 0 < scales[f"gnn_{i}"] <= 1.0 + 1e-5   # l2-normalized hops
    qm = quantize_params(params, cfg, calib_graphs=graphs, normalizer=norm)
    assert qm.act_scales == scales


# ----------------------------------------------------------------------------
# the fused Pallas sparse path (kernels/segment_aggregate)
# ----------------------------------------------------------------------------
@pytest.mark.parametrize("scan_layers", [False, True],
                         ids=["unrolled", "scan"])
def test_pallas_int8_matches_jnp_int8(scan_layers):
    """The in-VMEM dequantizing kernel and the jnp dequantize-then-apply
    path compute the same int8 predictions."""
    graphs = _graphs()
    norm = F.fit_normalizer(graphs)
    cfg = _cfg(scan_layers=scan_layers)
    params = cost_model_init(jax.random.key(0), cfg)
    qm = quantize_params(params, cfg)
    jnp_cfg = qm.serving_config()
    pal_cfg = CostModelConfig.from_dict(
        dict(jnp_cfg.to_dict(), use_pallas_aggregate=True))
    a = _predict(qm.params, jnp_cfg, graphs, norm)
    b = _predict(qm.params, pal_cfg, graphs, norm)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_pallas_f32_sparse_matches_jnp_f32():
    """use_pallas_aggregate + sparse is a supported f32 combination too:
    f32 weights ride the same kernel with unit scales."""
    graphs = _graphs()
    norm = F.fit_normalizer(graphs)
    cfg = _cfg()
    params = cost_model_init(jax.random.key(0), cfg)
    pal_cfg = CostModelConfig.from_dict(
        dict(cfg.to_dict(), use_pallas_aggregate=True))
    a = _predict(params, cfg, graphs, norm)
    b = _predict(params, pal_cfg, graphs, norm)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------------
# checkpoint sidecar
# ----------------------------------------------------------------------------
def test_sidecar_round_trip_bit_exact(tmp_path):
    graphs = _graphs()
    norm = F.fit_normalizer(graphs)
    cfg = _cfg(scan_layers=True)
    params = cost_model_init(jax.random.key(3), cfg)
    qm = quantize_params(params, cfg, calib_graphs=graphs, normalizer=norm)
    path = str(tmp_path / "model.int8.npz")
    assert save_quantized(path, qm) == path
    back = load_quantized(path)
    assert back.config == qm.config
    assert back.act_scales == pytest.approx(qm.act_scales)
    fa = jax.tree_util.tree_leaves(qm.params)
    fb = jax.tree_util.tree_leaves(back.params)
    assert len(fa) == len(fb)
    for a, b in zip(fa, fb):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # ... and the restored model serves bit-identical predictions
    pa = _predict(qm.params, qm.serving_config(), graphs, norm)
    pb = _predict(back.params, back.serving_config(), graphs, norm)
    assert np.array_equal(pa, pb)


def test_sidecar_checksum_mismatch_raises(tmp_path):
    cfg = _cfg()
    qm = quantize_params(cost_model_init(jax.random.key(0), cfg), cfg)
    path = str(tmp_path / "m.npz")
    save_quantized(path, qm)
    with np.load(path) as z:
        arrays = {k: np.array(z[k]) for k in z.files}
    victim = next(k for k in arrays if k.endswith(".q"))
    arrays[victim] = arrays[victim].copy()
    arrays[victim].flat[0] ^= 1                        # flip one bit
    np.savez(path, **arrays)
    with pytest.raises(ValueError, match="checksum"):
        load_quantized(path)


# ----------------------------------------------------------------------------
# serving + search integration
# ----------------------------------------------------------------------------
def test_service_accepts_quantized_model():
    from repro.serving import CostModelService
    graphs = _graphs()
    norm = F.fit_normalizer(graphs)
    cfg = _cfg()
    params = cost_model_init(jax.random.key(0), cfg)
    qm = quantize_params(params, cfg)
    svc = CostModelService(qm, cfg, norm)
    assert svc.precision == "int8"
    got = svc.predict_many(graphs)
    want = _predict(qm.params, qm.serving_config(), graphs, norm)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_estimator_accepts_quantized_model():
    from repro.search.estimator import LearnedEstimator
    graphs = _graphs()
    norm = F.fit_normalizer(graphs)
    cfg = _cfg()
    params = cost_model_init(jax.random.key(0), cfg)
    qm = quantize_params(params, cfg)
    est = LearnedEstimator.from_params(qm, cfg, norm,
                                       max_nodes=cfg.max_nodes)
    f32 = LearnedEstimator.from_params(params, cfg, norm,
                                       max_nodes=cfg.max_nodes)
    a = np.asarray(est.estimate(graphs))
    b = np.asarray(f32.estimate(graphs))
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) < 0.35 * max(float(np.std(b)), 0.1)


def test_cache_snapshot_meta_binding(tmp_path):
    from repro.serving.cache import PredictionCache, SnapshotFormatError
    path = str(tmp_path / "warm.npz")
    c = PredictionCache(8)
    c.put("k1", 1.5)
    c.snapshot(path, meta={"precision": "int8"})
    # matching expectation restores
    warm = PredictionCache(8)
    assert warm.restore(path, expect_meta={"precision": "int8"}) == 1
    # contradicting expectation refuses
    with pytest.raises(SnapshotFormatError, match="precision"):
        PredictionCache(8).restore(path, expect_meta={"precision": "f32"})
    # pre-meta snapshots (v1: no meta stamped) are accepted under any
    # expectation — the key is simply absent
    legacy = str(tmp_path / "legacy.npz")
    c.snapshot(legacy)
    assert PredictionCache(8).restore(
        legacy, expect_meta={"precision": "f32"}) == 1


def test_service_snapshot_stamps_precision(tmp_path):
    from repro.serving import CostModelService
    from repro.serving.cache import SnapshotFormatError
    graphs = _graphs()
    norm = F.fit_normalizer(graphs)
    cfg = _cfg()
    params = cost_model_init(jax.random.key(0), cfg)
    qm = quantize_params(params, cfg)
    q_svc = CostModelService(qm, cfg, norm)
    q_svc.predict_many(graphs)
    path = str(tmp_path / "cache.npz")
    assert q_svc.snapshot_cache(path) > 0
    # an int8 warm cache must not seed an f32 service (stale predictions)
    f_svc = CostModelService(params, cfg, norm)
    with pytest.raises(SnapshotFormatError, match="precision"):
        f_svc.restore_cache(path)
    # ... but a fresh int8 service restores it fine
    q2 = CostModelService(qm, cfg, norm)
    assert q2.restore_cache(path) > 0


# ----------------------------------------------------------------------------
# validation guards
# ----------------------------------------------------------------------------
def test_config_rejects_unknown_precision():
    with pytest.raises(ValueError, match="precision"):
        _cfg(precision="fp8")


def test_config_rejects_pallas_with_gat():
    with pytest.raises(ValueError, match="graphsage"):
        _cfg(gnn="gat", use_pallas_aggregate=True)


def test_config_rejects_pallas_with_segmented():
    """A whole-program segment pack has no node bound, so it cannot take
    the VMEM-resident segment_aggregate kernel."""
    with pytest.raises(ValueError, match="segmented"):
        _cfg(adjacency="segmented", use_pallas_aggregate=True)


def test_trainer_rejects_int8_precision(tmp_path):
    from repro.training.trainer import CostModelTrainer, TrainerConfig
    mc = _cfg(precision="int8")
    tc = TrainerConfig(steps=1, ckpt_dir=str(tmp_path))
    with pytest.raises(ValueError, match="f32"):
        CostModelTrainer(mc, tc, sampler=None)
