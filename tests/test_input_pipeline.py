"""Encode-once cache + async prefetch input pipeline (DESIGN.md §9).

Covers the three invariants the pipeline promises:
  * vectorized node features are bit-identical to the reference loop;
  * cached encodes are bit-identical to fresh encodes (dense and sparse),
    and `with_tile` variants of one kernel share one structural entry;
  * the prefetched batch stream is byte-identical to the synchronous one,
    including after a simulated restart, with clean shutdown and error
    propagation.
"""
import dataclasses
import time

import numpy as np
import pytest

from repro.core import features as F
from repro.core import opset
from repro.core.graph import KernelGraph
from repro.core.simulator import TPUSimulator
from repro.data.prefetch import Prefetcher
from repro.data.sampler import BalancedSampler, TileBatchSampler
from repro.data.synthetic import FAMILIES, generate_corpus, random_kernel
from repro.data.tile_dataset import build_tile_dataset, fit_tile_normalizer


@pytest.fixture()
def fresh_cache():
    """Isolate each test from the process-wide encode cache."""
    old = F.set_encode_cache(F.EncodeCache(4096))
    yield F.encode_cache()
    F.set_encode_cache(old)


@pytest.fixture(scope="module")
def tile_world():
    sim = TPUSimulator()
    kernels = [random_kernel(n, seed=n) for n in (6, 11, 19, 27, 34)]
    ds = build_tile_dataset([], sim, extra_kernels=kernels,
                            max_configs_per_kernel=6)
    assert ds.records, "tile dataset empty"
    return ds.records, fit_tile_normalizer(ds.records)


def _graphs(n=8):
    return [random_kernel(4 + 3 * i, seed=i) for i in range(n)]


def assert_batches_identical(a, b):
    assert type(a) is type(b)
    assert np.array_equal(a.targets, b.targets)
    assert np.array_equal(a.valid, b.valid)
    if hasattr(a, "group_ids"):
        assert np.array_equal(a.group_ids, b.group_ids)
    for fa, fb in zip(dataclasses.astuple(a.graphs),
                      dataclasses.astuple(b.graphs)):
        assert np.array_equal(np.asarray(fa), np.asarray(fb))


# ---------------------------------------------------------------------------
# vectorized node features == reference loop
# ---------------------------------------------------------------------------
def test_node_features_matches_reference_bitwise():
    graphs = _graphs(10)
    from repro.data.fusion import apply_fusion, default_fusion
    for p in generate_corpus(3, seed=2):
        graphs.extend(apply_fusion(p, default_fusion(p)))
    assert len(graphs) > 10
    for g in graphs:
        assert np.array_equal(F.node_features(g),
                              F.node_features_reference(g))


def _family_block(fam):
    return FAMILIES[fam](np.random.default_rng(len(fam)), f"block_{fam}")


def _handmade(*nodes, outputs=True):
    """A graph of `Node(op, shape, inputs, **kw)` specs; the last node is
    the output unless `outputs` is False."""
    from repro.core.graph import Node
    out = [Node(op, shape, inputs=inputs, **kw)
           for op, shape, inputs, kw in nodes]
    if outputs:
        out[-1] = dataclasses.replace(out[-1], is_output=True)
    return KernelGraph(out, name="handmade")


def _whole_model():
    from repro.data.synthetic import whole_model_graph
    return whole_model_graph(1500, seed=3)


def _smoke_dsv3():
    from repro.core.hlo_import import import_arch_program
    return import_arch_program("deepseek-v3-671b")


# (_BIG, _BIG + 12, 3): a volume past 2**53 whose f64 product of dims
# rounds off the exact one by enough to move its log1p
_BIG = 2 ** 27 + 1
ENCODE_CASES = {
    **{f"family-{fam}": (lambda fam=fam: _family_block(fam))
       for fam in FAMILIES},
    "whole-model": _whole_model,
    "dsv3-smoke": _smoke_dsv3,
    "conv-filters": lambda: _handmade(
        (opset.PARAMETER, (2, 16, 16, 8), (), {}),
        (opset.PARAMETER, (3, 5, 8, 4), (), {}),
        (opset.CONV, (2, 16, 16, 4), (0, 1),
         {"contract_dim": 8, "filter_size": (3, 5)}),
        (opset.CONV, (2, 16, 16, 4), (2, 1),
         {"contract_dim": 0, "filter_size": (0, 7)})),
    "empty-shapes-and-reduced": lambda: _handmade(
        (opset.PARAMETER, (), (), {}),
        (opset.CONSTANT, (4, 0), (), {}),
        (opset.REDUCE_SUM, (), (0,), {"reduced_dims": ()}),
        (opset.REDUCE_MAX, (1,), (1,), {"reduced_dims": (4, 0)}),
        (opset.ADD, (), (2, 3), {})),
    "one-producer-twice": lambda: _handmade(
        (opset.PARAMETER, (8, 8), (), {}),
        (opset.MUL, (8, 8), (0, 0), {}),
        (opset.SELECT, (8, 8), (1, 0, 1), {})),
    "no-output-flag": lambda: _handmade(
        (opset.PARAMETER, (2, 3), (), {}),
        (opset.EXP, (2, 3), (0,), {}),
        (opset.BROADCAST, (8, 8), (1,), {}), outputs=False),
    "volume-past-2**53": lambda: _handmade(
        (opset.PARAMETER, (_BIG, _BIG + 12, 3), (), {}),
        (opset.EXP, (_BIG, _BIG + 12, 3), (0,), {}),
        (opset.DOT, (_BIG, 3, 5), (1, 0), {"contract_dim": _BIG}),
        (opset.REDUCE_SUM, (3, 7), (2,), {"reduced_dims": (_BIG,)}),
        (opset.NEG, (3, 7), (3,), {})),
    "volume-past-int64": lambda: _handmade(
        (opset.PARAMETER, (2 ** 40, 2 ** 30 + 1), (), {}),
        (opset.LOG, (2 ** 40, 2 ** 30 + 1), (0,), {})),
    "one-node": lambda: _handmade((opset.PARAMETER, (3, 4), (), {})),
}


@pytest.mark.parametrize("case", list(ENCODE_CASES))
def test_columnar_encode_matches_oracles_bitwise(case):
    """The columnar `_build_encoded` equals the per-node oracles array for
    array, and `graph_kernel_feats` equals `kernel_features`."""
    g = ENCODE_CASES[case]()
    enc = F._build_encoded(g)
    tiled = g.with_tile((4, 16))
    oracle = {
        "opcodes": F.opcode_ids(g),
        "node_feats": F.node_features_reference(g),
        "kernel_feats_base": F.kernel_features(g, include_tile=False),
        "edges": np.asarray(g.unique_edges(), np.int32).reshape(-1, 2),
    }
    for name, want in oracle.items():
        got = getattr(enc, name)
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert np.array_equal(got, want), name
    assert enc.key == g.structural_digest(order_sensitive=True)
    assert np.array_equal(F.node_features(g), oracle["node_feats"])
    for static in (True, False):
        assert np.array_equal(
            F.graph_kernel_feats(tiled, include_static_perf=static),
            F.kernel_features(tiled, include_static_perf=static))


def test_subvec_rows_matches_subvec():
    seqs = [(), (5,), (3, 1024), (2, 3, 4, 5, 6, 7, 8, 9)]
    rows = F._subvec_rows(seqs, 6)
    for i, s in enumerate(seqs):
        assert np.array_equal(rows[i], F._subvec(s, 6))


# ---------------------------------------------------------------------------
# encode cache: bit-equality + structural sharing
# ---------------------------------------------------------------------------
def test_cached_encode_bit_equal_dense(fresh_cache):
    graphs = _graphs()
    norm = F.fit_normalizer(graphs)
    cold = F.encode_batch(graphs, 40, norm)          # fills the cache
    warm = F.encode_batch(graphs, 40, norm)          # served from it
    assert fresh_cache.stats().hits > 0
    prev = F.set_encode_cache(F.EncodeCache(0))      # truly uncached encode
    try:
        fresh = F.encode_batch(graphs, 40, norm)
    finally:
        F.set_encode_cache(prev)
    for name in ("opcodes", "node_feats", "adj", "node_mask", "kernel_feats"):
        assert np.array_equal(getattr(cold, name), getattr(warm, name))
        assert np.array_equal(getattr(cold, name), getattr(fresh, name))


def test_cached_encode_bit_equal_sparse(fresh_cache):
    graphs = _graphs()
    norm = F.fit_normalizer(graphs)
    cold = F.encode_sparse_batch(graphs, norm)
    warm = F.encode_sparse_batch(graphs, norm)
    assert fresh_cache.stats().hits > 0
    prev = F.set_encode_cache(F.EncodeCache(0))      # truly uncached encode
    try:
        fresh = F.encode_sparse_batch(graphs, norm)
    finally:
        F.set_encode_cache(prev)
    for fld in dataclasses.fields(F.SparseGraphBatch):
        assert np.array_equal(getattr(cold, fld.name),
                              getattr(warm, fld.name)), fld.name
        assert np.array_equal(getattr(cold, fld.name),
                              getattr(fresh, fld.name)), fld.name


def test_with_tile_variants_share_one_entry(fresh_cache):
    k = random_kernel(15, seed=3)
    tiles = [(1, 1), (2, 4), (8, 8), (16, 2)]
    encs = [F.encode_graph(k.with_tile(t), 20) for t in tiles]
    s = fresh_cache.stats()
    assert s.size == 1 and s.misses == 1 and s.hits == len(tiles) - 1
    # node-level arrays identical across tile variants...
    for e in encs[1:]:
        assert np.array_equal(encs[0]["node_feats"], e["node_feats"])
        assert np.array_equal(encs[0]["adj"], e["adj"])
    # ...while kernel features differ exactly in the tile sub-vector
    for t, e in zip(tiles, encs):
        expect = F.kernel_features(k.with_tile(t))
        assert np.array_equal(e["kernel_feats"],
                              expect.astype(np.float32))


def test_kernel_feats_assembly_matches_kernel_features(fresh_cache):
    k = random_kernel(12, seed=5)
    enc = F.encode_structural(k)
    for tile in ((), (4, 8)):
        for static in (True, False):
            got = enc.kernel_feats(tile, include_static_perf=static)
            want = F.kernel_features(k.with_tile(tile),
                                     include_static_perf=static)
            assert np.array_equal(got, want)


def test_cache_eviction_and_disable():
    c = F.EncodeCache(2)
    gs = _graphs(4)
    for g in gs:
        c.get_or_encode(g)
    s = c.stats()
    assert s.size == 2 and s.evictions == 2
    c0 = F.EncodeCache(0)
    a, b = c0.get_or_encode(gs[0]), c0.get_or_encode(gs[0])
    assert a is not b and c0.stats().size == 0
    assert np.array_equal(a.node_feats, b.node_feats)


def test_order_sensitive_cache_key(fresh_cache):
    # two topo-order-preserving renumberings encode different row orders —
    # they must NOT share a cache entry
    from repro.core import opset
    from repro.core.graph import Node
    g = KernelGraph([Node(opset.PARAMETER, (8, 8)),
                     Node(opset.PARAMETER, (4, 8)),
                     Node(opset.DOT, (4, 8), inputs=(1, 0), contract_dim=8,
                          is_output=True)])
    h = g.renumbered([1, 0, 2])
    ea, eb = F.encode_structural(g), F.encode_structural(h)
    assert ea is not eb
    assert not np.array_equal(ea.node_feats, eb.node_feats)


def test_normalized_memo_tracks_normalizer(fresh_cache):
    g = random_kernel(9, seed=7)
    enc = F.encode_structural(g)
    n1 = F.fit_normalizer([g])
    n2 = F.fit_normalizer([g, random_kernel(30, seed=8)])
    a1 = enc.normalized_node_feats(n1)
    assert enc.normalized_node_feats(n1) is a1          # memo hit
    a2 = enc.normalized_node_feats(n2)                  # different normalizer
    assert np.array_equal(a1, n1.transform_node(enc.node_feats))
    assert np.array_equal(a2, n2.transform_node(enc.node_feats))


# ---------------------------------------------------------------------------
# sampler: pad rows + cached stream
# ---------------------------------------------------------------------------
def test_tile_sampler_pad_rows_reuse_encoded_slot(fresh_cache, tile_world):
    records, norm = tile_world
    # configs_per_kernel far above any record's tile count forces padding
    s = TileBatchSampler(records, norm, kernels_per_batch=2,
                         configs_per_kernel=12, max_nodes=40, seed=1)
    b = s.batch(0)
    assert float(b.valid.sum()) < len(b.valid)          # padding happened
    # pad slots carry tiles[0]'s encoding: group them and compare features
    kf = np.asarray(b.graphs.kernel_feats)
    for ki in range(2):
        sl = slice(ki * 12, (ki + 1) * 12)
        vals, kfs = b.valid[sl], kf[sl]
        pad_rows = np.where(vals == 0.0)[0]
        if len(pad_rows):
            assert np.array_equal(kfs[pad_rows[0]], kfs[pad_rows[-1]])


def test_tile_sampler_stream_identical_with_and_without_cache(tile_world):
    records, norm = tile_world
    old = F.set_encode_cache(F.EncodeCache(0))
    try:
        cold = [TileBatchSampler(records, norm, max_nodes=40).batch(s)
                for s in range(3)]
    finally:
        F.set_encode_cache(old)
    old = F.set_encode_cache(F.EncodeCache(4096))
    try:
        warm = [TileBatchSampler(records, norm, max_nodes=40).batch(s)
                for s in range(3)]
    finally:
        F.set_encode_cache(old)
    for a, b in zip(cold, warm):
        assert_batches_identical(a, b)


# ---------------------------------------------------------------------------
# prefetcher
# ---------------------------------------------------------------------------
class _ScriptedSampler:
    """Deterministic toy sampler; optionally raises at one step."""

    def __init__(self, fail_at=None):
        self.fail_at = fail_at
        self.calls = []

    def batch(self, step):
        self.calls.append(step)
        if step == self.fail_at:
            raise RuntimeError(f"boom at {step}")
        return {"step": step, "payload": np.full((3,), step)}


def test_prefetcher_sequential_stream():
    with Prefetcher(_ScriptedSampler(), depth=2) as p:
        for s in range(5):
            got = p.batch(s)
            assert got["step"] == s


def test_prefetcher_matches_sync_sampler(tile_world):
    records, norm = tile_world
    sync = TileBatchSampler(records, norm, max_nodes=40, seed=2)
    with Prefetcher(TileBatchSampler(records, norm, max_nodes=40, seed=2),
                    depth=3) as pre:
        for s in range(4):
            assert_batches_identical(sync.batch(s), pre.batch(s))


def test_prefetcher_matches_sync_fusion_sampler(tile_world):
    records, norm = tile_world
    recs = [type("R", (), {"kernel": r.kernel, "runtime": float(i + 1),
                           "program": r.program})()
            for i, r in enumerate(records)]
    sync = BalancedSampler(recs, norm, batch_size=6, max_nodes=40, seed=3)
    with Prefetcher(BalancedSampler(recs, norm, batch_size=6, max_nodes=40,
                                    seed=3), depth=2) as pre:
        for s in range(3):
            assert_batches_identical(sync.batch(s), pre.batch(s))


def test_prefetcher_restart_and_seek(tile_world):
    records, norm = tile_world
    sync = TileBatchSampler(records, norm, max_nodes=40, seed=4)
    # simulated preempt-and-restart: a fresh prefetcher starting mid-stream
    with Prefetcher(TileBatchSampler(records, norm, max_nodes=40, seed=4),
                    depth=2, start_step=5) as pre:
        assert_batches_identical(sync.batch(5), pre.batch(5))
        assert_batches_identical(sync.batch(6), pre.batch(6))
        # seek backwards (non-sequential access) restarts deterministically
        assert_batches_identical(sync.batch(0), pre.batch(0))
        assert_batches_identical(sync.batch(1), pre.batch(1))


def test_prefetcher_propagates_worker_errors():
    p = Prefetcher(_ScriptedSampler(fail_at=2), depth=2)
    assert p.batch(0)["step"] == 0
    assert p.batch(1)["step"] == 1
    with pytest.raises(RuntimeError, match="boom at 2"):
        p.batch(2)
    # recovers: next request restarts a worker (which fails again at 2,
    # but serves other steps fine)
    assert p.batch(0)["step"] == 0
    p.close()


def test_prefetcher_close_unblocks_full_queue_and_is_idempotent():
    p = Prefetcher(_ScriptedSampler(), depth=1)
    p.batch(0)
    deadline = time.time() + 5.0          # let the worker fill the queue
    while p._state["queue"] is not None and p._state["queue"].empty() \
            and time.time() < deadline:
        time.sleep(0.01)
    p.close()
    p.close()                             # idempotent
    thread = p._state["thread"]
    assert thread is None                 # state fully torn down


def test_prefetcher_runs_ahead_of_consumer():
    s = _ScriptedSampler()
    with Prefetcher(s, depth=3) as p:
        p.batch(0)
        deadline = time.time() + 5.0
        while len(s.calls) < 4 and time.time() < deadline:
            time.sleep(0.01)
    # after serving step 0, the worker had encoded ahead (steps 1..3+)
    assert len(s.calls) >= 4
