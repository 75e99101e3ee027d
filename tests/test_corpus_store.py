"""Corpus store tests: serialization round trip, dedup, checksums,
manifest determinism, streaming parity with in-memory sampling
(DESIGN.md §11, docs/DATA.md)."""
import json
import os

import numpy as np
import pytest

from repro.core import opset
from repro.core.graph import KernelGraph, Node
from repro.core.simulator import TPUSimulator
from repro.data.fusion_dataset import build_fusion_records
from repro.data.prefetch import Prefetcher
from repro.data.sampler import BalancedSampler, TileBatchSampler
from repro.data.store import (
    CorpusFormatError,
    StreamingCorpus,
    load_manifest,
    record_key,
    write_corpus,
)
from repro.data.synthetic import generate_program, random_kernel
from repro.data.tile_dataset import build_tile_records, fit_tile_normalizer
from repro.launch.build_corpus import build_corpus


@pytest.fixture(scope="module")
def sim():
    return TPUSimulator()


@pytest.fixture(scope="module")
def tile_records(sim):
    kernels = [random_kernel(n, seed=i)
               for i, n in enumerate((10, 14, 18, 12, 16, 20))]
    return build_tile_records(kernels, sim, max_configs_per_kernel=8)


@pytest.fixture(scope="module")
def fusion_records(sim):
    recs = []
    for i, fam in enumerate(("mlp", "norm")):
        recs.extend(build_fusion_records(generate_program(fam, i, 0), sim,
                                         configs_per_program=4))
    return recs


# --------------------------------------------------------------- graph serde
def test_graph_dict_round_trip_preserves_hashes():
    g = generate_program("attention", 0, seed=3)
    g2 = KernelGraph.from_dict(g.to_dict())
    assert g2.program == g.program and g2.name == g.name
    assert g2.canonical_hash() == g.canonical_hash()
    assert (g2.canonical_hash(order_sensitive=True)
            == g.canonical_hash(order_sensitive=True))
    assert [n.to_dict() for n in g2.nodes] == [n.to_dict() for n in g.nodes]


def test_graph_dict_round_trip_with_tile():
    g = random_kernel(9, seed=1).with_tile((8, 8))
    g2 = KernelGraph.from_dict(g.to_dict())
    assert g2.tile_size == (8, 8)
    assert g2.canonical_hash() == g.canonical_hash()


def test_node_from_dict_rejects_unknown_op():
    d = Node(opset.ADD, (4,), inputs=()).to_dict()
    d["op"] = "not-an-op"
    with pytest.raises(KeyError):
        Node.from_dict(d)


# ------------------------------------------------------------ store roundtrip
def test_tile_round_trip_exact(tile_records, tmp_path):
    m = write_corpus(str(tmp_path / "t"), "tile", tile_records,
                     shard_records=2)
    c = StreamingCorpus.open(str(tmp_path / "t"), verify=True)
    assert len(c) == len(tile_records)
    assert c.kind == "tile" and c.num_samples == m["stats"]["samples"]
    for a, b in zip(tile_records, c):
        assert a.tiles == b.tiles and a.program == b.program
        assert a.runtimes.dtype == b.runtimes.dtype == np.float64
        np.testing.assert_array_equal(a.runtimes, b.runtimes)  # bit-exact
        assert record_key(a) == record_key(b)


def test_fusion_round_trip_exact(fusion_records, tmp_path):
    write_corpus(str(tmp_path / "f"), "fusion", fusion_records)
    c = StreamingCorpus.open(str(tmp_path / "f"))
    assert [r.runtime for r in c] == [r.runtime for r in fusion_records]
    assert c.record_programs == [r.program for r in fusion_records]


def test_random_access_and_shard_lru(tile_records, tmp_path):
    write_corpus(str(tmp_path / "t"), "tile", tile_records, shard_records=1)
    c = StreamingCorpus.open(str(tmp_path / "t"), max_cached_shards=1)
    # thrash: every access evicts the only cached shard
    for i in (3, 0, 5, 2, 3, -1):
        want = tile_records[i]
        got = c[i]
        assert got.tiles == want.tiles
        np.testing.assert_array_equal(got.runtimes, want.runtimes)
    with pytest.raises(IndexError):
        c[len(tile_records)]


def test_iter_shards_streams_in_order(tile_records, tmp_path):
    write_corpus(str(tmp_path / "t"), "tile", tile_records, shard_records=2)
    seen = [r for shard in
            StreamingCorpus.open(str(tmp_path / "t")).iter_shards()
            for r in shard]
    assert [record_key(r) for r in seen] == \
        [record_key(r) for r in tile_records]


# --------------------------------------------------------------------- dedup
def test_dedup_drops_exact_duplicates(fusion_records, tmp_path):
    doubled = fusion_records + fusion_records[:3]
    m = write_corpus(str(tmp_path / "f"), "fusion", doubled)
    assert m["stats"]["records"] == len(fusion_records)
    assert m["stats"]["duplicates_dropped"] == 3


def test_dedup_off_preserves_duplicates(fusion_records, tmp_path):
    doubled = fusion_records + fusion_records[:3]
    m = write_corpus(str(tmp_path / "f"), "fusion", doubled, dedup=False)
    assert m["stats"]["records"] == len(doubled)
    assert m["stats"]["duplicates_dropped"] == 0


def test_tile_key_covers_tile_sweep(tile_records):
    r = tile_records[0]
    import dataclasses
    trimmed = dataclasses.replace(r, tiles=r.tiles[:-1],
                                  runtimes=r.runtimes[:-1])
    assert record_key(r) != record_key(trimmed)
    assert record_key(r) == record_key(dataclasses.replace(r, program="x"))


# ----------------------------------------------------- integrity + manifests
def test_checksum_mismatch_detected(fusion_records, tmp_path):
    d = str(tmp_path / "f")
    m = write_corpus(d, "fusion", fusion_records, shard_records=4)
    shard = os.path.join(d, m["shards"][0]["file"])
    blob = bytearray(open(shard, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(shard, "wb") as f:
        f.write(blob)
    with pytest.raises(CorpusFormatError, match="checksum"):
        StreamingCorpus.open(d, verify=True)
    with pytest.raises(CorpusFormatError, match="checksum"):
        StreamingCorpus.open(d)[0]          # lazy load checks too


def test_manifest_tamper_detected(fusion_records, tmp_path):
    d = str(tmp_path / "f")
    write_corpus(d, "fusion", fusion_records)
    mpath = os.path.join(d, "manifest.json")
    m = json.load(open(mpath))
    m["stats"]["records"] = 9999
    json.dump(m, open(mpath, "w"))
    with pytest.raises(CorpusFormatError, match="manifest hash"):
        StreamingCorpus.open(d, verify=True)


def test_open_missing_raises(tmp_path):
    with pytest.raises(CorpusFormatError):
        StreamingCorpus.open(str(tmp_path / "nope"))
    assert load_manifest(str(tmp_path / "nope")) is None


def test_writer_refuses_non_store_dir(fusion_records, tmp_path):
    d = tmp_path / "precious"
    d.mkdir()
    (d / "notes.txt").write_text("do not delete")
    with pytest.raises(CorpusFormatError, match="refusing"):
        write_corpus(str(d), "fusion", fusion_records)
    assert (d / "notes.txt").exists()


def test_write_is_deterministic(fusion_records, tmp_path):
    m1 = write_corpus(str(tmp_path / "a"), "fusion", fusion_records)
    m2 = write_corpus(str(tmp_path / "b"), "fusion", fusion_records)
    assert m1["manifest_hash"] == m2["manifest_hash"]
    for s1, s2 in zip(m1["shards"], m2["shards"]):
        assert s1["sha256"] == s2["sha256"]


# ------------------------------------------------------------- builder CLI
def test_build_corpus_noop_and_determinism(tmp_path):
    kw = dict(kinds=("fusion",), programs=4, seed=0, workers=1,
              fusion_opts={"configs_per_program": 3}, quiet=True)
    m1 = build_corpus(str(tmp_path / "c"), **kw)
    m2 = build_corpus(str(tmp_path / "c"), **kw)            # no-op
    assert m1["fusion"]["manifest_hash"] == m2["fusion"]["manifest_hash"]
    m3 = build_corpus(str(tmp_path / "c2"), **dict(kw, force=True))
    assert m3["fusion"]["manifest_hash"] == m1["fusion"]["manifest_hash"]
    m4 = build_corpus(str(tmp_path / "c3"), **dict(kw, seed=1))
    assert m4["fusion"]["manifest_hash"] != m1["fusion"]["manifest_hash"]


@pytest.mark.slow
def test_build_corpus_workers_match_serial(tmp_path):
    kw = dict(kinds=("tile", "fusion"), programs=6, seed=0,
              tile_opts={"max_configs_per_kernel": 8},
              fusion_opts={"configs_per_program": 3}, quiet=True)
    m1 = build_corpus(str(tmp_path / "w1"), workers=1, **kw)
    m2 = build_corpus(str(tmp_path / "w2"), workers=2, **kw)
    for kind in ("tile", "fusion"):
        assert m1[kind]["manifest_hash"] == m2[kind]["manifest_hash"]


def test_build_corpus_workers_never_open_the_accelerator(monkeypatch):
    """The pool initializer pins each worker to the host CPU before any
    task can import jax, so workers never contend for the chip even when
    the parent's environment names one."""
    import multiprocessing
    from repro.launch.build_corpus import _pin_worker_to_cpu
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(processes=2, initializer=_pin_worker_to_cpu) as pool:
        got = [pool.apply(os.getenv, ("JAX_PLATFORMS",)) for _ in range(4)]
    assert got == ["cpu"] * 4


# -------------------------------------------------------- streaming parity
def test_tile_sampler_stream_parity(tile_records, tmp_path):
    d = str(tmp_path / "t")
    write_corpus(d, "tile", tile_records, shard_records=2)
    corpus = StreamingCorpus.open(d, max_cached_shards=2)
    norm = fit_tile_normalizer(tile_records)
    mk = lambda recs: TileBatchSampler(  # noqa: E731
        recs, norm, kernels_per_batch=3, configs_per_kernel=4,
        max_nodes=24, seed=0)
    s_mem, s_store = mk(tile_records), mk(corpus)
    for step in range(4):
        a, b = s_mem.batch(step), s_store.batch(step)
        np.testing.assert_array_equal(a.targets, b.targets)
        np.testing.assert_array_equal(a.group_ids, b.group_ids)
        np.testing.assert_array_equal(a.valid, b.valid)
        import jax
        for x, y in zip(jax.tree_util.tree_leaves(a.graphs),
                        jax.tree_util.tree_leaves(b.graphs)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fusion_sampler_prefetch_parity(fusion_records, tmp_path):
    d = str(tmp_path / "f")
    write_corpus(d, "fusion", fusion_records, shard_records=4)
    corpus = StreamingCorpus.open(d, max_cached_shards=1)
    from repro.core.features import fit_normalizer
    norm = fit_normalizer([r.kernel for r in fusion_records])
    s_mem = BalancedSampler(fusion_records, norm, batch_size=8,
                            max_nodes=24, seed=0)
    with Prefetcher(BalancedSampler(corpus, norm, batch_size=8,
                                    max_nodes=24, seed=0), depth=2) as pre:
        for step in range(4):
            a, b = s_mem.batch(step), pre.batch(step)
            np.testing.assert_array_equal(a.targets, b.targets)
            import jax
            for x, y in zip(jax.tree_util.tree_leaves(a.graphs),
                            jax.tree_util.tree_leaves(b.graphs)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_select_programs_view(fusion_records, tmp_path):
    d = str(tmp_path / "f")
    write_corpus(d, "fusion", fusion_records)
    corpus = StreamingCorpus.open(d)
    programs = corpus.programs()
    assert len(programs) == 2
    sub = corpus.select_programs([programs[0]])
    assert 0 < len(sub) < len(corpus)
    assert set(sub.record_programs) == {programs[0]}
    assert sub[0].program == programs[0]
    # a sampler over the view draws only from the selected program
    from repro.core.features import fit_normalizer
    norm = fit_normalizer([sub[0].kernel])
    s = BalancedSampler(sub, norm, batch_size=4, max_nodes=24, seed=0)
    assert s.batch(0).targets.shape == (4,)

# ------------------------------------------------- worker shard properties
# `StreamingCorpus.shard(idx, num)` (DESIGN.md §13): deterministic,
# disjoint, manifest-only round-robin views whose position interleave is
# the unsharded stream. Property tests can't take pytest fixtures (the
# hypothesis @given wrapper owns the signature), so they share one
# module-memoized on-disk corpus.
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.data.sampler import shard_records  # noqa: E402
from repro.data.store import CorpusSubset  # noqa: E402

_SHARD_CORPUS: dict = {}


def _shard_corpus() -> StreamingCorpus:
    if "c" not in _SHARD_CORPUS:
        import tempfile
        sim = TPUSimulator()
        kernels = [random_kernel(n, seed=i)
                   for i, n in enumerate((10, 14, 18, 12, 16, 20, 11))]
        recs = build_tile_records(kernels, sim, max_configs_per_kernel=4)
        d = tempfile.mkdtemp(prefix="shard_corpus_")
        write_corpus(d, "tile", recs, shard_records=3)
        _SHARD_CORPUS["c"] = StreamingCorpus.open(d, max_cached_shards=2)
    return _SHARD_CORPUS["c"]


@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=1, max_value=9))
def test_shards_disjoint_exhaustive_interleave(num):
    corpus = _shard_corpus()
    shards = [corpus.shard(i, num) for i in range(num)]
    assert sum(len(s) for s in shards) == len(corpus)
    keys = [record_key(r) for s in shards for r in s]
    assert len(set(keys)) == len(keys)                      # disjoint
    for k in range(len(corpus)):                            # exhaustive +
        got = shards[k % num][k // num]                     # ordered union
        want = corpus[k]
        assert record_key(got) == record_key(want)
        np.testing.assert_array_equal(got.runtimes, want.runtimes)


@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=1, max_value=6),
       st.integers(min_value=0, max_value=5))
def test_shard_deterministic_and_manifest_only(num, idx):
    corpus = _shard_corpus()
    idx = idx % num
    a, b = corpus.shard(idx, num), corpus.shard(idx, num)
    # same records on every call, computed from the manifest alone
    assert a._indices == b._indices == list(range(idx, len(corpus), num))
    assert [r["key"] for r in
            (corpus.manifest["index"][i] for i in a._indices)] == \
        [record_key(r) for r in a]


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=2, max_value=4),
       st.integers(min_value=2, max_value=3))
def test_shard_composes_with_subshard(num, sub):
    """shard().shard() flattens to one round-robin over num*sub workers."""
    corpus = _shard_corpus()
    nested = corpus.shard(1 % num, num).shard(1 % sub, sub)
    want = list(range(len(corpus)))[1 % num::num][1 % sub::sub]
    assert nested._indices == want


def test_shard_identity_view_shares_parent_lru():
    corpus = _shard_corpus()
    view = corpus.shard(0, 1)
    assert isinstance(view, CorpusSubset)
    assert view._corpus is corpus                 # same LRU, no copy
    assert len(view) == len(corpus)
    assert [record_key(r) for r in view] == \
        [record_key(r) for r in corpus]
    corpus._cache.clear()
    _ = view[0]                                   # decode through the view…
    assert len(corpus._cache) == 1                # …lands in the parent LRU


def test_shard_validation_errors():
    corpus = _shard_corpus()
    with pytest.raises(ValueError):
        corpus.shard(0, 0)
    with pytest.raises(ValueError):
        corpus.shard(2, 2)
    with pytest.raises(ValueError):
        corpus.shard(-1, 2)
    with pytest.raises(ValueError):
        corpus.shard(0, 2).shard(3, 3)


def test_shard_records_prefers_manifest_view():
    corpus = _shard_corpus()
    view = shard_records(corpus, 1, 3)
    assert isinstance(view, CorpusSubset)         # no decode, no list copy
    assert view._corpus is corpus
    # plain lists fall back to strided slicing with identical membership
    recs = list(corpus)
    assert [record_key(r) for r in shard_records(recs, 1, 3)] == \
        [record_key(r) for r in view]
    assert shard_records(recs, 0, 1) is recs      # num=1: untouched


@pytest.mark.slow
def test_shard_deterministic_under_build_workers(tmp_path):
    """The shard views of a corpus built with --workers N are identical to
    the serial build's — partitioning the build cannot move records
    between worker shards."""
    kw = dict(kinds=("tile",), programs=6, seed=0,
              tile_opts={"max_configs_per_kernel": 6}, quiet=True)
    build_corpus(str(tmp_path / "w1"), workers=1, **kw)
    build_corpus(str(tmp_path / "w2"), workers=2, **kw)
    c1 = StreamingCorpus.open(str(tmp_path / "w1" / "tile"))
    c2 = StreamingCorpus.open(str(tmp_path / "w2" / "tile"))
    for w in (2, 3):
        for i in range(w):
            assert [record_key(r) for r in c1.shard(i, w)] == \
                [record_key(r) for r in c2.shard(i, w)]
