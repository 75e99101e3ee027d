"""The benchmark's yardstick on the CPU: FLOP counts against hand counts,
the trace reduction on small traces, the request pools' determinism."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

import tiny

import common
import traffic

CFG = {"hidden_dim": 4, "opcode_embed_dim": 2, "gnn_layers": 1,
       "node_final_layers": 1, "transformer_layers": 1,
       "transformer_heads": 2}


def test_flops_of_a_three_node_graph_by_hand():
    # 3 nodes, 2 edges, d = 4, f1 input 2 + 31 + 15 = 48
    n, e, d = 3, 2, 4
    f1 = 2 * n * 48 * d                                  # 1152
    gnn = 2 * (2 * n * d * d) + 2 * e * d + 2 * n * 12 * d   # 192+16+288
    assert common.embed_flops(CFG, n, e) == f1 + gnn == 1648
    assert common.node_final_flops(CFG, n) == 2 * n * d * d == 96
    lstm = dict(CFG, reduction="lstm")
    # input and recurrent gate matmuls, [n, d] x [d, 4d] each
    assert common.reduction_flops(lstm, n) == 2 * (2 * n * d * 4 * d) == 768
    xf = dict(CFG, reduction="transformer")
    qkvo = 4 * 2 * n * d * d                             # 384
    attn = 2 * 2 * n * n * d                             # 144
    ffn = 2 * 2 * n * d * 4 * d                          # 768
    assert common.reduction_flops(xf, n) == qkvo + attn + ffn == 1296
    assert common.head_flops(CFG) == 8
    assert common.forward_flops(lstm, n, e) == 1648 + 96 + 768 + 8
    assert common.train_flops(lstm, n, e) == 3 * (1648 + 96 + 768 + 8)


def test_pack_counts_reads_real_nodes_and_edges_per_slot():
    from repro.data.batching import encode_packed
    from repro.data.synthetic import random_kernel
    gs = [random_kernel(5, seed=1), random_kernel(3, seed=2)]
    b = encode_packed(gs)
    nodes, edges = common.pack_counts(b)
    assert nodes[:2].tolist() == [5, 3]
    assert edges[:2].tolist() == [len(g.unique_edges()) for g in gs]


def _ms(*ivs):
    return [(name, s * 1e6, e * 1e6) for name, s, e in ivs]


def test_reduce_trace_busy_union_top_ops_and_gap_attribution():
    # window 0..100 ms; ops overlap (10-30, 20-40) -> busy 10-40 and 60-70
    trace = {
        "devices": {"/device:TPU:0": {
            "ops": _ms(("a", 10, 30), ("b", 20, 40), ("a", 60, 70),
                       ("late", 95, 120)),
            "modules": _ms(("jit_step", 10, 40))}},
        "spans": _ms((common.WINDOW_SPAN, 0, 100),
                     ("bench.sampler", 40, 58), ("bench.predict", 75, 90),
                     ("bench.predict", 80, 85))}
    r = common.reduce_trace(trace)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.045)     # 30 + 10 + 5 (clipped)
    assert r["device_ops"][0] == ["a", pytest.approx(0.03)]
    # idle 0-10, 40-60, 70-95: the spans cover 18 and 15 ms of it
    gaps = dict(r["idle_gaps"])
    assert gaps["sampler"] == pytest.approx(0.018)
    assert gaps["predict"] == pytest.approx(0.015)
    assert gaps["none"] == pytest.approx(0.010 + 0.002 + 0.010)
    assert r["module_s"] == {"jit_step": pytest.approx(0.03)}


def test_reduce_trace_without_device_ops_reads_no_busy_time():
    trace = {"devices": {}, "spans": _ms((common.WINDOW_SPAN, 0, 10))}
    r = common.reduce_trace(trace)
    assert r["devices_busy"] == 0 and r["busy_s"] == 0.0
    assert r["collective_s"] == 0.0


def test_reduce_trace_sums_device_0s_collectives_in_the_window():
    """All-reduce time on device 0 alone, overlaps counted once, clipped to
    the window; a fusion that only takes an all-reduce's result is no
    collective, nor is device 1's all-reduce."""
    ar = "%all-reduce.3 = f32[192,768]{1,0} all-reduce(f32[192,768] %x)"
    trace = {
        "devices": {
            "/device:TPU:0": {"ops": _ms(
                ("%fusion.1 = f32[8] fusion(%all-reduce.3)", 0, 5),
                (ar, 10, 14),
                ("%all-reduce-start.1 = f32[8] all-reduce-start(%y)", 20,
                 21),
                ("%all-reduce-done.1 = f32[8] all-reduce-done(%s)", 20.5,
                 23),
                (ar, 98, 104)), "modules": []},
            "/device:TPU:1": {"ops": _ms((ar, 30, 90)), "modules": []}},
        "spans": _ms((common.WINDOW_SPAN, 0, 100))}
    r = common.reduce_trace(trace)
    assert r["collective_s"] == pytest.approx(0.004 + 0.003 + 0.002)
    assert r["devices_busy"] == 2


@pytest.mark.parametrize("hlo,want", [
    ("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%add",
     True),
    ("%all-reduce-start.1 = (f32[8], f32[8]) all-reduce-start(%x, %y)",
     True),
    ("%all-gather.2 = f32[32] all-gather(%x), dimensions={0}", True),
    ("%reduce-scatter.1 = f32[2] reduce-scatter(%x)", True),
    ("all-reduce.7", True),
    ("%fusion.1 = f32[8] fusion(%all-reduce.3, %y)", False),
    ("%copy.1 = f32[8] copy(%x)", False),
    ("%reduce.4 = f32[] reduce(%x, %c)", False)])
def test_is_collective_reads_the_instruction_not_its_operands(hlo, want):
    assert common.is_collective(hlo) is want


def test_load_trace_finds_the_window_span_in_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(common.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.predict"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = common.load_trace(str(tmp_path))
    names = [s[0] for s in tr["spans"]]
    assert common.WINDOW_SPAN in names and "bench.predict" in names
    r = common.reduce_trace(tr)
    assert r["window_s"] > 0


def test_op_names_keep_the_name_and_result_type():
    assert common.op_name("%fusion.19 = f32[4,8192]{1,0:T(4,128)} fusion(x)"
                          ) == "%fusion.19 = f32[4,8192]"


def _mix(name):
    with open(os.path.join(tiny.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _stream(mix, seed, n, client=0):
    gen = traffic.generator(mix, seed, "run", client).requests()
    return [json.dumps([g.to_dict() for g in next(gen)[1]])
            for _ in range(n)]


@pytest.mark.parametrize("name", ["tile-search", "whole-program"])
def test_request_streams_follow_the_seed(name):
    mix = dict(_mix(name), arch_blocks=[])
    if name == "whole-program":
        mix.update(min_nodes=64, max_nodes=256)
    a = _stream(mix, 2**33 + 5, 4)
    assert a == _stream(mix, 2**33 + 5, 4)
    assert a != _stream(mix, 2**33 + 6, 4)
    assert a != _stream(mix, 2**33 + 5, 4, client=1)


def test_a_request_rebuilds_from_its_descriptor():
    mix = _mix("tile-search")
    gen = traffic.generator(mix, 17, "run", 3)
    desc, graphs = next(gen.requests())
    again = traffic.generator(mix, 17, "run", 3).rebuild(desc)
    assert [g.to_dict() for g in again] == [g.to_dict() for g in graphs]


def test_tile_digest_matches_the_committed_traffic_file():
    mix = _mix("tile-search")
    assert traffic.digest(mix) == mix["digest"]


def test_request_buckets_match_a_service_that_flushes_each_request():
    """The census's replay of per-request flushes finds the buckets a
    service really packs."""
    import jax
    from repro.core.model import CostModelConfig, cost_model_init
    from repro.data import batching
    from repro.serving import CostModelService
    mix = dict(_mix("tile-search"), pool_programs=32)
    want = traffic.request_buckets(
        traffic.generator(mix, 5, "run", 0).requests(), 30,
        mix["node_budget"], True)
    seen = {}
    real = batching.bucket_for

    def spy(graphs, **kw):
        b = real(graphs, **kw)
        key = (b.node_capacity, b.edge_capacity, b.graph_capacity,
               b.reduce_capacity)
        seen[key] = seen.get(key, 0) + 1
        return b
    mc = CostModelConfig(reduction="lstm", hidden_dim=8, opcode_embed_dim=4,
                         adjacency="sparse")
    params = cost_model_init(jax.random.key(0), mc)
    svc = CostModelService(params, mc, None, node_budget=mix["node_budget"])
    import repro.serving.service as service_mod
    old = service_mod.bucket_for
    service_mod.bucket_for = spy
    try:
        gen = traffic.generator(mix, 5, "run", 0).requests()
        for _ in range(30):
            svc.predict_many(next(gen)[1])
    finally:
        service_mod.bucket_for = old
    assert seen == want
    assert np.sum(list(want.values())) > 0
