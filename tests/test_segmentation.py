"""Graph segmentation properties (DESIGN.md §12): partition/halo
invariants under random graphs+budgets, identity-path bit-equality with
the unsegmented batcher, and embedding-reassembly order."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import features as F
from repro.core.model import CostModelConfig, cost_model_apply, \
    cost_model_init
from repro.data import batching
from repro.data.segmentation import segment_graph
from repro.data.synthetic import random_kernel, whole_model_graph


# ----------------------------------------------------------------------------
# partition / halo properties
# ----------------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=2, max_value=120),
       st.integers(min_value=8, max_value=48),
       st.integers(min_value=0, max_value=5))
def test_segments_partition_nodes(num_nodes, budget, seed):
    g = random_kernel(num_nodes, seed=seed)
    seg = segment_graph(g, max_nodes=budget)
    owned = sorted(i for s in seg.segments for i in s.owned_global)
    assert owned == list(range(num_nodes))       # every node exactly once
    for s in seg.segments:
        assert s.graph.num_nodes <= budget       # owned + halo bounded
        assert len(s.owned_local) == len(s.owned_global)
        assert s.graph.num_nodes == len(s.owned_global) + len(s.halo_global)


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=2, max_value=120),
       st.integers(min_value=8, max_value=48),
       st.integers(min_value=0, max_value=5))
def test_cross_edges_accounted_in_halo(num_nodes, budget, seed):
    """Every original edge appears in exactly one segment — internal edges
    stay owned→owned, cut edges become halo→owned in the dst's segment."""
    g = random_kernel(num_nodes, seed=seed)
    seg = segment_graph(g, max_nodes=budget)
    rebuilt = []
    for s in seg.segments:
        owned = dict(zip(s.owned_local, s.owned_global))
        local_to_global = dict(owned)
        for k, glob in enumerate(sorted(s.halo_global)):
            local_to_global[k] = glob
        for src, dst in s.graph.unique_edges():
            assert dst in owned, "edge destination must be an owned node"
            rebuilt.append((local_to_global[src], local_to_global[dst]))
        # a halo node is present because some owned node consumes it
        consumed = {src for src, _ in s.graph.unique_edges()}
        halo_locals = set(range(len(s.halo_global)))
        assert halo_locals <= consumed
    assert sorted(rebuilt) == sorted(g.unique_edges())


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=2, max_value=60),
       st.integers(min_value=4, max_value=16))
def test_segment_determinism(num_nodes, budget):
    g = random_kernel(num_nodes, seed=7)
    a = segment_graph(g, max_nodes=budget)
    c = segment_graph(g, max_nodes=budget)
    assert [s.owned_global for s in a.segments] == \
        [s.owned_global for s in c.segments]
    assert [s.halo_global for s in a.segments] == \
        [s.halo_global for s in c.segments]


def test_identity_path_is_the_original_graph():
    g = random_kernel(20, seed=0)
    seg = segment_graph(g, max_nodes=20)         # exactly at budget
    assert seg.num_segments == 1
    assert seg.segments[0].graph is g            # no copies on the fast path
    assert seg.segments[0].halo_global == ()


def test_overflowing_fanin_raises():
    # a graph whose bridge node consumes more producers than any segment
    # can hold can never be segmented at that budget
    from repro.core import opset
    from repro.core.graph import KernelGraph, Node
    nodes = [Node(opset.PARAMETER, (4,)) for _ in range(6)]
    nodes.append(Node(opset.CONCATENATE, (24,), inputs=tuple(range(6))))
    nodes.extend(Node(opset.EXP, (24,), inputs=(6 + i,)) for i in range(4))
    g = KernelGraph(nodes, name="fanin")
    with pytest.raises(ValueError, match="out-of-block producers"):
        segment_graph(g, max_nodes=4)


# ----------------------------------------------------------------------------
# encode_segmented: identity path bit-equality + reassembly order
# ----------------------------------------------------------------------------
def _norm(graphs):
    return F.fit_normalizer(graphs)


def test_identity_encode_bit_identical_to_unsegmented():
    graphs = [random_kernel(n, seed=n) for n in (20, 9, 15)]
    norm = _norm(graphs)
    sb = batching.encode_segmented(graphs, node_budget=64, normalizer=norm)
    pb = batching.encode_packed(graphs, norm)
    for field in ("opcodes", "node_feats", "node_mask", "graph_ids",
                  "edge_src", "edge_dst", "edge_mask", "kernel_feats",
                  "gather_idx", "gather_mask"):
        np.testing.assert_array_equal(getattr(sb.inner, field),
                                      getattr(pb, field), err_msg=field)
    # the scatter is the identity on real nodes
    n_real = sum(g.num_nodes for g in graphs)
    np.testing.assert_array_equal(sb.scatter_idx[:n_real],
                                  np.arange(n_real))
    assert np.all(sb.scatter_idx[n_real:] == sb.num_nodes)   # padding→dummy


def test_identity_predictions_bit_identical():
    graphs = [random_kernel(n, seed=n) for n in (20, 9, 15)]
    norm = _norm(graphs)
    for reduction in ("per_node", "column_wise", "transformer"):
        cfg = CostModelConfig(hidden_dim=32, opcode_embed_dim=8,
                              transformer_heads=4, dropout=0.0,
                              adjacency="segmented", reduction=reduction)
        params = cost_model_init(jax.random.key(0), cfg)
        sb = batching.encode_segmented(graphs, node_budget=64,
                                       normalizer=norm)
        pb = batching.encode_packed(graphs, norm)
        ys = np.asarray(cost_model_apply(params, cfg, sb))[:3]
        yp = np.asarray(cost_model_apply(params, cfg, pb))[:3]
        assert np.max(np.abs(ys - yp)) == 0.0, reduction


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=40, max_value=150),
       st.integers(min_value=12, max_value=40))
def test_reassembly_preserves_node_order(num_nodes, budget):
    """Scattered owned embeddings land at their original node positions:
    checked by pushing a recognizable per-node value (the node's global
    index, via scatter of arange) through the segmented bookkeeping."""
    g = random_kernel(num_nodes, seed=1)
    sb = batching.encode_segmented([g], node_budget=budget)
    # emulate the model's scatter with node positions as 'embeddings':
    # every outer slot must be written with its own global node index
    buf = np.full((sb.num_nodes + 1,), -1, np.int64)
    buf[sb.scatter_idx] = sb.scatter_idx
    assert np.array_equal(buf[:num_nodes], np.arange(num_nodes))
    # and the outer gather walks them in original order
    n = g.num_nodes
    np.testing.assert_array_equal(sb.gather_idx[0, :n], np.arange(n))
    assert np.all(sb.gather_idx[0, n:] == sb.num_nodes)


def test_segmented_whole_model_forward_finite():
    g = whole_model_graph(1200, seed=0)
    small = random_kernel(10, seed=3)
    norm = _norm([small])          # normalizer origin irrelevant here
    cfg = CostModelConfig(hidden_dim=32, opcode_embed_dim=8,
                          adjacency="segmented", reduction="column_wise",
                          dropout=0.0, scan_layers=True)
    params = cost_model_init(jax.random.key(1), cfg)
    sb = batching.encode_segmented([g, small], node_budget=256,
                                   normalizer=norm)
    y = np.asarray(cost_model_apply(params, cfg, sb))
    assert y.shape == (2,)
    assert np.all(np.isfinite(y))


def _segmented_by_whole_encode(graphs, budget, normalizer,
                               include_static_perf):
    """`encode_segmented` as it read each program's kernel feats off the
    program's whole structural encode, once per segment slot and again for
    its outer slot; the oracle of the program-only kernel feats."""
    segs = [segment_graph(g, budget) for g in graphs]
    inner = batching.encode_packed([s.graph for sg in segs
                                    for s in sg.segments], normalizer,
                                   include_static_perf=include_static_perf)
    M = batching.round_up_pow2(sum(g.num_nodes for g in graphs), 32)
    R = batching.round_up_pow2(max(g.num_nodes for g in graphs), 8)
    G = len(graphs)
    scatter = np.full((inner.num_nodes,), M, np.int32)
    node_mask = np.zeros((M,), np.float32)
    graph_ids = np.zeros((M,), np.int32)
    kf = np.zeros((G, F.KERNEL_FEATURE_DIM), np.float32)
    gather_idx = np.full((G, R), M, np.int32)
    gather_mask = np.zeros((G, R), np.float32)

    def whole(g):
        out = F._build_encoded_reference(g).kernel_feats(
            g.tile_size, include_static_perf=include_static_perf)
        return out if normalizer is None else \
            normalizer.transform_kernel(out)

    slot = n_off = g_off = 0
    for gi, (g, sg) in enumerate(zip(graphs, segs)):
        for s in sg.segments:
            for loc, glob in zip(s.owned_local, s.owned_global):
                scatter[n_off + loc] = g_off + glob
            n_off += s.graph.num_nodes
            inner.kernel_feats[slot] = whole(g)
            slot += 1
        n = g.num_nodes
        node_mask[g_off:g_off + n] = 1.0
        graph_ids[g_off:g_off + n] = gi
        kf[gi] = whole(g)
        gather_idx[gi, :n] = np.arange(g_off, g_off + n)
        gather_mask[gi, :n] = 1.0
        g_off += n
    return F.SegmentedGraphBatch(inner, scatter, node_mask, graph_ids, kf,
                                 np.ones((G,), np.float32), gather_idx,
                                 gather_mask)


@pytest.mark.parametrize("static", [True, False])
def test_segmented_encode_reads_only_the_programs_kernel_feats(
        static, monkeypatch):
    """Past the node budget, `encode_segmented` gives the batch the whole
    program's structural encode gave, byte for byte, while it encodes only
    the segments' node features: the program itself is neither encoded
    with its nodes nor cached."""
    graphs = [whole_model_graph(700, seed=4).with_tile((8, 16)),
              random_kernel(30, seed=2)]
    norm = _norm(graphs)
    want = _segmented_by_whole_encode(graphs, 160, norm, static)
    calls = []
    columnar = F._columnar

    def spy(g, **kw):
        calls.append((g.num_nodes, kw.get("with_nodes", True)))
        return columnar(g, **kw)

    monkeypatch.setattr(F, "_columnar", spy)
    cache = F.EncodeCache(4096)
    old = F.set_encode_cache(cache)
    try:
        got = batching.encode_segmented(graphs, 160, norm,
                                        include_static_perf=static)
        segs = [segment_graph(g, 160) for g in graphs]
    finally:
        F.set_encode_cache(old)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    n_segments = sum(sg.num_segments for sg in segs)
    assert segs[0].num_segments > 1
    assert cache.stats().misses == n_segments
    assert (graphs[0].num_nodes, True) not in calls
    assert calls.count((graphs[0].num_nodes, False)) == 1
