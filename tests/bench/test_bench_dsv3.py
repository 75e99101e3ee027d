"""The segmented serve cell, serve-dsv3-whole-program, on the CPU at a
small size: DeepSeek-V3's smoke program cut at a 128-node budget in place
of the published one at 8 192, and the readout's attention in key blocks
from 256 nodes on. A sound run agrees with the segmented reference; the
control, altered answers and faults planted in the reference read not
correct; the census's shapes are the ones the service encodes."""
from __future__ import annotations

import json
import math
import os
import time

import numpy as np
import pytest

import tiny

import run
import reference_segmented
from repro.nn import transformer

# the sizes as the modules define them, before a test patches them
PROGRAM_BLOCKING = (transformer.DENSE_MAX_NODES, transformer.KEY_BLOCK)
REFERENCE_BLOCKING = (reference_segmented.DENSE_MAX_NODES,
                      reference_segmented.KEY_BLOCK)

pytestmark = pytest.mark.timeout(900)
CELL = "serve-dsv3-whole-program"
SMALL = {"clients": 2, "arch_blocks": ["deepseek-v3-671b"],
         "node_budget": 128, "census_requests": 3,
         "norm_requests": 1, "check_requests": 2}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import serve_segmented
    import traffic
    root = tiny.make_root(str(tmp_path_factory.mktemp("root")))
    path = os.path.join(root, "benchmarks", "chip", "traffic",
                        "dsv3-programs.json")
    with open(path) as f:
        mix = json.load(f)
    mix.update(SMALL)
    traffic.GENERATORS = os.path.join(root, "benchmarks", "chip",
                                      "generators")
    mix["digest"] = traffic.digest(mix, serve_segmented.arch_blocks(mix),
                                   requests=2)
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


@pytest.fixture(autouse=True)
def blocked_at_small_sizes(monkeypatch):
    """The program's readout and the reference's in blocks at this size."""
    for module in (transformer, reference_segmented):
        monkeypatch.setattr(module, "DENSE_MAX_NODES", 256)
        monkeypatch.setattr(module, "KEY_BLOCK", 128)


def test_the_reference_blocks_the_readout_as_the_program_does():
    """The reference attends over all keys at once up to the program's
    dense limit and in the program's key blocks past it, so the default
    precision rounds the same operands on both sides; a block size changed
    in one alone would move the score gaps the limits were set from."""
    assert REFERENCE_BLOCKING == PROGRAM_BLOCKING == (8192, 1024)


def _run(root, fault=None, trace=0, seed=5):
    return run.run_cell(root, CELL, seed, 1.5, trace, require_tpu=False,
                        fault=fault, t0=time.monotonic())


def test_a_sound_run_is_correct_and_segments_every_program(root):
    out = _run(root, seed=2**31 + 11)
    assert out["correct"], out["checks"]
    c = out["counters"]
    assert c["executables"] == 0, c["window_executables"]
    assert c["segmented_graphs"] == c["graphs_scored"] > 0
    assert c["segments"] >= 4 * c["segmented_graphs"]
    assert 0 < c["halo_nodes"] < c["owned_nodes"]
    assert c["model_flops"] > 0


def test_a_traced_run_reads_the_segment_metrics(root):
    out = _run(root, trace=1)
    assert out["correct"], out["checks"]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])
              and m["source"] != "device_trace"}       # no device here
    assert {"segment_ms_per_graph.serve", "segments_per_graph.serve",
            "halo_share.serve", "encode_ms_per_pack.serve",
            "predict_ms_per_call.serve"} <= listed
    for name in listed:
        value = out["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
    assert out["metrics"]["segments_per_graph.serve"]["value"] >= 4
    assert out["metrics"]["segment_ms_per_graph.serve"]["value"] > 0


def _alter_answer(out, batch):
    return np.asarray(out) * 1.05 + 1e-3


def test_an_altered_answer_is_not_correct(root):
    out = _run(root, fault={"scores": _alter_answer})
    assert not out["correct"]
    assert out["checks"]["score_gap_max"]["value"] > \
        out["checks"]["score_gap_max"]["limit"]


def _own_kernel_feats(monkeypatch):
    """Each block featurized with its own kernel features, not the whole
    program's."""
    import jax.numpy as jnp
    import reference
    import reference_segmented

    def embed(params, cfg, g, norm, max_nodes, dtype, gnn):
        out = np.zeros((len(g["nodes"]), cfg["hidden_dim"]), np.float32)
        for lo, hi, halo in reference_segmented.segments(g, max_nodes):
            f = reference.featurize(
                reference_segmented.block_graph(g, lo, hi, halo))
            b = reference.dense_batch([f], norm, max_nodes)
            b = reference.cast({k: jnp.asarray(v) for k, v in b.items()},
                               dtype)
            h = np.asarray(gnn(params, reference.frozen(cfg), b))[0]
            out[lo:hi] = h[len(halo):len(halo) + hi - lo]
        return out
    monkeypatch.setattr(reference_segmented, "embed_program", embed)


def _padding_attended(monkeypatch):
    """The readout's attention over the padding rows too."""
    import reference_segmented
    real = reference_segmented._attention
    monkeypatch.setattr(reference_segmented, "_attention",
                        lambda q, k, v, mask: real(q, k, v,
                                                   mask * 0 + 1))


def _no_halo(monkeypatch):
    """Blocks cut without their halo: edges across a cut are lost."""
    import reference_segmented
    real = reference_segmented.block_graph

    def block_graph(g, lo, hi, halo):
        cut = dict(g, nodes=[dict(nd, inputs=[k for k in nd["inputs"]
                                              if k >= lo])
                             for nd in g["nodes"]])
        return real(cut, lo, hi, [])
    monkeypatch.setattr(reference_segmented, "block_graph", block_graph)


@pytest.mark.parametrize("plant", [_own_kernel_feats, _padding_attended,
                                   _no_halo],
                         ids=["block-kernel-feats", "padding-attended",
                              "no-halo"])
def test_a_fault_planted_in_the_reference_is_not_correct(root, monkeypatch,
                                                         plant):
    import jax
    plant(monkeypatch)
    jax.clear_caches()          # traces of the sound reference are gone
    try:
        out = _run(root)
    finally:
        jax.clear_caches()      # and so are the faulty ones
    assert not out["correct"], out["checks"]


def test_the_control_fails_a_check(root):
    import serve_segmented
    _, _, cfg, mix = run.load_cell(root, CELL)
    got = serve_segmented.control(cfg, mix, 7, program=True)
    assert got["score_gap_p95"] > mix["limit_score_gap_p95"], got
    for k, v in got["program"].items():
        assert v <= mix["limit_" + k], got


def test_the_census_shape_is_the_encoded_shape(root):
    """`segmented_spec` gives the shapes `encode_segmented` makes, and
    `segmented_counts` reads back each program's real nodes and edges."""
    import serve_segmented
    import traffic
    from repro.data.batching import encode_segmented
    _, _, cfg, mix = run.load_cell(root, CELL)
    arch = serve_segmented.arch_blocks(mix)
    gen = traffic.generator(mix, 3, "run", 1, arch)
    for i in range(3):
        g = gen.build(i)
        b = encode_segmented([g], mix["node_budget"])
        assert serve_segmented.segmented_spec(g, mix["node_budget"]) == (
            b.inner.num_nodes, b.inner.edge_src.shape[0],
            b.inner.kernel_feats.shape[0], b.inner.gather_idx.shape[1],
            b.num_nodes, b.batch_size, b.reduce_capacity)
        n, e = serve_segmented.segmented_counts(b)
        assert (n[0], e[0]) == (g.num_nodes, len(g.unique_edges()))


def test_the_programs_are_the_architecture_block_and_a_few_more(root):
    """Each request holds the whole architecture block and 2-10% more
    nodes, and no two requests hash alike."""
    import serve_segmented
    import traffic
    _, _, _, mix = run.load_cell(root, CELL)
    arch = serve_segmented.arch_blocks(mix)
    base = len(arch[0]["nodes"])
    seen = set()
    for c in range(2):
        gen = traffic.generator(mix, 2**32 + 5, "run", c, arch)
        for i in range(4):
            g = gen.build(i)
            assert base * 1.02 <= g.num_nodes <= base * 1.10 + 200
            seen.add(g.canonical_hash())
    assert len(seen) == 8


@pytest.mark.parametrize("name", ["whole-program", "dsv3-programs"])
def test_the_committed_digests_hold(name):
    """The serve cells' traffic digests, with their architecture blocks as
    the chip runs import them: whole-program's smoke blocks keep their
    graphs, and dsv3-programs' published DeepSeek-V3 program is the one
    committed."""
    import serve_segmented
    import traffic
    traffic.GENERATORS = os.path.join(tiny.BENCH, "generators")
    with open(os.path.join(tiny.BENCH, "traffic", name + ".json")) as f:
        mix = json.load(f)
    arch = serve_segmented.arch_blocks(mix)
    if name == "whole-program":
        assert traffic.digest(mix, arch) == mix["digest"] == \
            "d8de44b3e99cf8b7"
    else:
        assert traffic.digest(mix, arch, requests=2) == mix["digest"]
