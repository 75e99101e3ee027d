"""Per-kernel validation: shape/dtype sweeps against ref.py oracles,
interpret=True (CPU container; TPU is the lowering target)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.graph_aggregate.ops import graph_aggregate
from repro.kernels.graph_aggregate.ref import graph_aggregate_ref
from repro.kernels.segment_aggregate.ops import (
    block_candidates,
    segment_aggregate,
)
from repro.kernels.segment_aggregate.ref import segment_aggregate_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref

RNG = np.random.default_rng(0)


# ------------------------------------------------------------------ flash
FLASH_CASES = [
    # (B, S, H, KH, hd, causal, window, dtype)
    (1, 64, 2, 2, 32, True, None, jnp.float32),
    (2, 128, 4, 2, 64, True, None, jnp.float32),
    (1, 96, 4, 1, 32, True, 32, jnp.float32),        # MQA + SWA
    (2, 64, 8, 2, 16, False, None, jnp.float32),
    (1, 128, 2, 2, 64, True, 64, jnp.bfloat16),
    (1, 80, 3, 3, 48, True, None, jnp.float32),      # ragged block edges
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=str)
def test_flash_attention_matches_ref(case):
    B, S, H, KH, hd, causal, window, dtype = case
    q = jnp.asarray(RNG.normal(0, 1, (B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (B, S, KH, hd)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (B, S, KH, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_k=32, interpret=True)
    ref = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=causal,
                        window=window).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_block_shape_invariance():
    """Different BlockSpec tilings must give identical results — the
    property the tile-size autotuner relies on."""
    B, S, H, hd = 1, 128, 2, 32
    q = jnp.asarray(RNG.normal(0, 1, (B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (B, S, H, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (B, S, H, hd)), jnp.float32)
    outs = [flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
            for bq, bk in [(32, 32), (64, 32), (32, 64), (128, 128)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-5)


def test_flash_matches_model_chunked_attention():
    """The model's jnp chunked attention and the Pallas kernel agree."""
    from repro.models.layers import chunked_attention
    B, S, H, KH, hd = 1, 64, 4, 2, 16
    q = jnp.asarray(RNG.normal(0, 1, (B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (B, S, KH, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (B, S, KH, hd)), jnp.float32)
    a = chunked_attention(q, k, v, causal=True, window=None, block_kv=32)
    b = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


# --------------------------------------------------------------- aggregate
AGG_CASES = [(1, 8, 16, 32, "relu", True), (3, 16, 32, 64, "relu", False),
             (2, 48, 64, 160, "none", True), (1, 64, 48, 96, "relu", True)]


@pytest.mark.parametrize("case", AGG_CASES, ids=str)
def test_graph_aggregate_matches_ref(case):
    B, N, D, F, act, mean = case
    adj = (RNG.random((B, N, N)) < 0.15).astype(np.float32)
    x = RNG.normal(0, 1, (B, N, D)).astype(np.float32)
    w = RNG.normal(0, 1, (D, F)).astype(np.float32)
    out = graph_aggregate(jnp.asarray(adj), jnp.asarray(x), jnp.asarray(w),
                          act=act, mean=mean, block_f=128, interpret=True)
    ref = graph_aggregate_ref(adj, x, w, act=act, mean=mean)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


@given(st.integers(min_value=2, max_value=24),
       st.integers(min_value=1, max_value=4))
@settings(max_examples=10, deadline=None)
def test_graph_aggregate_property(n, b):
    adj = (RNG.random((b, n, n)) < 0.3).astype(np.float32)
    x = RNG.normal(0, 1, (b, n, 8)).astype(np.float32)
    w = RNG.normal(0, 1, (8, 16)).astype(np.float32)
    out = graph_aggregate(jnp.asarray(adj), jnp.asarray(x), jnp.asarray(w),
                          block_f=16, interpret=True)
    ref = graph_aggregate_ref(adj, x, w)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


def test_graph_aggregate_isolated_nodes_zero():
    adj = np.zeros((1, 8, 8), np.float32)
    x = RNG.normal(0, 1, (1, 8, 8)).astype(np.float32)
    w = RNG.normal(0, 1, (8, 8)).astype(np.float32)
    out = graph_aggregate(jnp.asarray(adj), jnp.asarray(x), jnp.asarray(w),
                          interpret=True)
    assert float(jnp.max(jnp.abs(out))) == 0.0


# ------------------------------------------------------- segment aggregate
def _seg_inputs(M, D, F, E, *, int8=True, seed=0, integer=False):
    """Random packed edge list + weights (int8 per-channel or f32+ones)."""
    rng = np.random.default_rng(seed)
    if integer:
        x = rng.integers(-3, 4, (M, D)).astype(np.float32)
        w = rng.integers(-5, 6, (D, F)).astype(np.int8 if int8 else np.float32)
        scale = np.ones((1, F), np.float32)
    else:
        x = rng.normal(0, 1, (M, D)).astype(np.float32)
        wf = rng.normal(0, 1, (D, F)).astype(np.float32)
        if int8:
            scale = np.maximum(
                np.abs(wf).max(axis=0, keepdims=True) / 127.0, 1e-12)
            w = np.clip(np.round(wf / scale), -127, 127).astype(np.int8)
        else:
            w, scale = wf, np.ones((1, F), np.float32)
    gather = rng.integers(0, M, E).astype(np.int32)
    scatter = rng.integers(0, M, E).astype(np.int32)
    edge_mask = (rng.random(E) < 0.8).astype(np.float32)
    node_mask = (rng.random(M) < 0.9).astype(np.float32)
    return x, w, scale, gather, scatter, edge_mask, node_mask


SEG_CASES = [
    # (M, D, F, E, act, mean, int8)  — shapes straddle the (8, 32, 128,
    # block_e) padding boundaries on every operand
    (16, 12, 20, 33, "relu", True, True),
    (64, 192, 192, 256, "relu", True, True),
    (9, 7, 5, 3, "none", False, True),
    (32, 32, 128, 64, "relu", False, True),
    (24, 48, 64, 100, "relu", True, False),          # f32 weights, unit scale
    (8, 16, 16, 512, "none", True, True),            # E >> M fan-in
]


@pytest.mark.parametrize("case", SEG_CASES, ids=str)
def test_segment_aggregate_matches_ref(case):
    M, D, F, E, act, mean, int8 = case
    x, w, s, g, sc, em, nm = _seg_inputs(M, D, F, E, int8=int8, seed=M + E)
    out = segment_aggregate(jnp.asarray(x), jnp.asarray(w), jnp.asarray(s),
                            jnp.asarray(g), jnp.asarray(sc), jnp.asarray(em),
                            jnp.asarray(nm), act=act, mean=mean,
                            block_e=128, interpret=True)
    ref = segment_aggregate_ref(x, w, s, g, sc, em, nm, act=act, mean=mean)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mean", [True, False], ids=["mean", "sum"])
def test_segment_aggregate_bitexact_on_integers(mean):
    """Integer-valued inputs make every f32 intermediate exact, so the
    Pallas one-hot-matmul formulation must equal the sequential edge-loop
    oracle bit for bit — no tolerance."""
    x, w, s, g, sc, em, nm = _seg_inputs(32, 16, 24, 96, integer=True,
                                         seed=7)
    out = segment_aggregate(jnp.asarray(x), jnp.asarray(w), jnp.asarray(s),
                            jnp.asarray(g), jnp.asarray(sc), jnp.asarray(em),
                            jnp.asarray(nm), mean=mean, interpret=True)
    ref = segment_aggregate_ref(x, w, s, g, sc, em, nm, mean=mean)
    assert np.array_equal(np.asarray(out), ref)


def test_segment_aggregate_block_e_invariance():
    """Different edge-block widths must give identical results — the
    property the block_candidates autotuner hints rely on."""
    args = [jnp.asarray(a) for a in _seg_inputs(24, 16, 32, 600, seed=3)]
    blocks = block_candidates(600)
    assert blocks == [128, 256, 512]
    outs = [segment_aggregate(*args, block_e=be, interpret=True)
            for be in blocks]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-6, atol=1e-6)


def test_segment_aggregate_all_edges_masked_is_zero():
    x, w, s, g, sc, em, nm = _seg_inputs(16, 8, 16, 40, seed=5)
    out = segment_aggregate(jnp.asarray(x), jnp.asarray(w), jnp.asarray(s),
                            jnp.asarray(g), jnp.asarray(sc),
                            jnp.zeros_like(jnp.asarray(em)), jnp.asarray(nm),
                            interpret=True)
    assert float(jnp.max(jnp.abs(out))) == 0.0


@given(st.integers(min_value=1, max_value=20),
       st.integers(min_value=1, max_value=40),
       st.booleans())
@settings(max_examples=10, deadline=None)
def test_segment_aggregate_property(m, e, mean):
    x, w, s, g, sc, em, nm = _seg_inputs(m, 6, 10, e, seed=m * 41 + e)
    out = segment_aggregate(jnp.asarray(x), jnp.asarray(w), jnp.asarray(s),
                            jnp.asarray(g), jnp.asarray(sc), jnp.asarray(em),
                            jnp.asarray(nm), mean=mean, block_e=128,
                            interpret=True)
    ref = segment_aggregate_ref(x, w, s, g, sc, em, nm, mean=mean)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- ssd scan
SSD_CASES = [(1, 2, 1, 8, 8), (2, 4, 3, 16, 8), (1, 8, 5, 32, 16),
             (2, 16, 2, 64, 32)]


@pytest.mark.parametrize("case", SSD_CASES, ids=str)
def test_ssd_scan_matches_ref(case):
    B, nc, H, N, P = case
    S = RNG.normal(0, 1, (B, nc, H, N, P)).astype(np.float32)
    d = RNG.uniform(0.05, 0.999, (B, nc, H)).astype(np.float32)
    hb, hf = ssd_scan(jnp.asarray(S), jnp.asarray(d), interpret=True)
    rb, rf = ssd_scan_ref(jnp.asarray(S), jnp.asarray(d))
    np.testing.assert_allclose(np.asarray(hb), np.asarray(rb), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(rf), rtol=1e-5,
                               atol=1e-5)


def test_ssd_scan_first_chunk_state_is_zero():
    S = jnp.ones((1, 3, 1, 4, 4))
    d = jnp.full((1, 3, 1), 0.5)
    hb, _ = ssd_scan(S, d, interpret=True)
    assert float(jnp.max(jnp.abs(hb[:, 0]))) == 0.0


# ------------------------------------------------- TPU-legal block shapes
def test_segment_aggregate_refuses_pack_over_vmem():
    """A pack whose whole-node-axis blocks cannot fit VMEM fails with a
    ValueError naming the bound, before any compiler sees it (the v5e
    compiler refuses M=4096, D=F=256 with RESOURCE_EXHAUSTED)."""
    from repro.kernels.segment_aggregate.ops import (VMEM_LIMIT_BYTES,
                                                     vmem_bytes)
    assert vmem_bytes(2048, 256, 256, 256) <= VMEM_LIMIT_BYTES
    M, D, E = 4096, 256, 8192
    x = jnp.zeros((M, D), jnp.float32)
    w = jnp.zeros((D, D), jnp.float32)
    idx = jnp.zeros((E,), jnp.int32)
    with pytest.raises(ValueError, match="VMEM_LIMIT_BYTES"):
        segment_aggregate(x, w, jnp.ones((1, D)), idx, idx,
                          jnp.ones((E,)), jnp.ones((M,)), interpret=True)


@pytest.mark.parametrize("block_e", [64, 8, 200])
def test_segment_aggregate_refuses_unaligned_block(block_e):
    args = [jnp.asarray(a) for a in _seg_inputs(16, 8, 16, 40, seed=1)]
    with pytest.raises(ValueError, match="multiple of 128"):
        segment_aggregate(*args, block_e=block_e, interpret=True)


def test_graph_aggregate_refuses_unaligned_f_block():
    adj = jnp.zeros((1, 8, 8))
    x = jnp.zeros((1, 8, 16))
    w = jnp.zeros((16, 192))
    with pytest.raises(ValueError, match="multiple of 128"):
        graph_aggregate(adj, x, w, block_f=64, interpret=True)


def test_block_candidates_are_lane_aligned():
    from repro.kernels.graph_aggregate.ops import (
        block_candidates as f_blocks,
    )
    for cands in (block_candidates(32), block_candidates(4096),
                  f_blocks(64), f_blocks(512)):
        assert cands and all(b % 128 == 0 for b in cands)


def test_interpret_mode_off_tpu():
    """The model's kernel call sites interpret off-TPU and never on it."""
    import jax
    from repro.kernels import interpret_mode
    assert interpret_mode() == (jax.default_backend() != "tpu")
