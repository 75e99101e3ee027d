"""The serve loop of whole programs above the node budget: the segmented
path. As `serve.run`, with `serve`'s clients, counters and check numbers,
around a service whose configuration says `adjacency: "segmented"`:

- the census replays each client's first `census_requests` programs, cut
  and packed as the service cuts and packs them (`segmented_spec`), in
  worker processes, and the warm-up compiles each shape;
- the predict wrapper counts the algorithm's FLOPs of a segmented batch;
- the check scores a sample with `reference_segmented`;
- the counters carry the service's segmented graphs, segments, owned and
  halo nodes.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import sys
import time
from collections import Counter
from concurrent.futures import ProcessPoolExecutor

import numpy as np

import common
import reference
import reference_segmented
import serve
import traffic


class SegmentedPredict(serve.TimedPredict):
    """`serve.TimedPredict` for the segmented batches: the FLOPs of each
    program are those of its real nodes and edges, read from the outer
    node mask and the inner edge mask (each edge lies in one segment)."""

    def __call__(self, params, batch):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.predict"):
            out = np.asarray(self.fn(params, batch))
        dt = time.perf_counter() - t0
        if self.fault is not None:
            out = self.fault(out, batch)
        if self.live:
            n, e = segmented_counts(batch)
            self.calls += 1
            self.seconds += dt
            self.flops += float(np.sum(
                common.forward_flops(self.cfg, n, e)[n > 0]))
        return out


def segmented_counts(batch):
    """Real nodes and unique edges of each program of a segmented batch:
    (nodes [G], edges [G]) as float64 numpy arrays."""
    gids = np.asarray(batch.graph_ids)
    g = np.asarray(batch.graph_mask).shape[-1]
    nodes = np.bincount(gids[np.asarray(batch.node_mask) > 0],
                        minlength=g).astype(np.float64)
    inner = batch.inner
    dst = np.asarray(inner.edge_dst)[np.asarray(inner.edge_mask) > 0]
    edges = np.bincount(gids[np.asarray(batch.scatter_idx)[dst]],
                        minlength=g).astype(np.float64)
    return nodes, edges


def segmented_spec(g, node_budget: int) -> tuple:
    """The shapes `encode_segmented([g], node_budget)` gives: the inner
    pack's (node, edge, graph, reduce) capacities, then the outer node
    capacity, graph slots and reduce width."""
    from repro.data.batching import bucket_for, round_up_pow2
    from repro.data.segmentation import segment_graph
    inner = bucket_for([s.graph for s in
                        segment_graph(g, node_budget).segments])
    n = g.num_nodes
    return (inner.node_capacity, inner.edge_capacity, inner.graph_capacity,
            inner.reduce_capacity, round_up_pow2(n, 32), 1,
            round_up_pow2(n, 8))


def zero_batch(spec: tuple):
    """An all-zero segmented batch of one census shape."""
    from repro.core import features as F
    from repro.data.batching import BucketSpec
    m, e, g, r, mo, go, ro = spec
    f32, i32 = np.float32, np.int32
    return F.SegmentedGraphBatch(
        serve.zero_batch(BucketSpec(m, e, g, r)), np.zeros(m, i32),
        np.zeros(mo, f32), np.zeros(mo, i32),
        np.zeros((go, F.KERNEL_FEATURE_DIM), f32), np.zeros(go, f32),
        np.full((go, ro), mo, i32), np.zeros((go, ro), f32))


_WORKER: dict = {}


def _census_init(spec: dict) -> None:
    sys.path[:0] = spec["sys_path"]
    traffic.GENERATORS = spec["generators"]
    _WORKER.update(spec)


def _census_spec(job: tuple) -> tuple:
    client, i = job
    w = _WORKER
    gens = w.setdefault("gens", {})
    if client not in gens:
        gens[client] = traffic.generator(w["mix"], w["seed"], "run", client,
                                         w["arch_blocks"])
    g = gens[client].build(i)
    if g.num_nodes <= w["mix"]["node_budget"]:
        raise ValueError(f"a {g.num_nodes}-node program fits the node "
                         "budget: this loop warms segmented shapes only")
    return segmented_spec(g, w["mix"]["node_budget"])


def census(mix: dict, seed: int, arch: list) -> list:
    """The segmented shapes of each client's first `census_requests`
    programs, most frequent first: with every program a miss and
    `coalesce_limit` 1, each request is scored on its own, so the window's
    shapes are these. Up to 8 processes, half the host's cores, share the
    programs, eight or more each; fewer take one pass here."""
    spec = {"mix": mix, "seed": seed, "arch_blocks": arch,
            "sys_path": list(sys.path), "generators": traffic.GENERATORS}
    jobs = [(c, i) for c in range(mix["clients"])
            for i in range(mix["census_requests"])]
    workers = min(8, (os.cpu_count() or 1) // 2, len(jobs) // 8)
    if workers <= 1:
        _census_init(spec)
        got = [_census_spec(j) for j in jobs]
    else:
        with ProcessPoolExecutor(workers,
                                 mp_context=mp.get_context("spawn"),
                                 initializer=_census_init,
                                 initargs=(spec,)) as pool:
            got = list(pool.map(_census_spec, jobs))
    return [s for s, _ in Counter(got).most_common()]


def segment_counters(service) -> dict:
    """The service's segmented graphs, segments, owned and halo nodes."""
    s = service.stats()
    return {k: getattr(s, k) for k in ("segmented_graphs", "segments",
                                       "owned_nodes", "halo_nodes")}


def fit_norm(mix: dict, seed: int, arch: list) -> dict:
    """The normalizer's min/max, fitted on the `norm` role's programs."""
    gen = traffic.generator(mix, seed, "norm", 0, arch).requests()
    return reference.fit_normalizer(
        [reference.featurize(g.to_dict())
         for _ in range(mix["norm_requests"]) for g in next(gen)[1]])


def arch_blocks(mix: dict) -> list:
    from repro.core.hlo_import import import_arch_program
    return [import_arch_program(a).to_dict() for a in mix["arch_blocks"]]


def run(ctx) -> dict:
    import jax.numpy as jnp

    from repro.core.evaluate import make_predict_fn
    from repro.serving import CostModelService
    from repro.serving.server import CostModelServer

    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    mcfg = cfg["model"]
    mc = common.model_config(cfg)
    if mc.adjacency != "segmented":
        raise SystemExit("the segmented serve loop needs a configuration "
                         "with adjacency 'segmented'")
    params = common.make_params(cfg, seed)
    arch = arch_blocks(mix)
    got = traffic.digest(mix, arch, requests=2)
    if got != mix["digest"]:
        raise SystemExit(f"the program's graph generators changed: traffic "
                         f"digest {got}, expected {mix['digest']}")
    norm = fit_norm(mix, seed, arch)
    predict = SegmentedPredict(make_predict_fn(mc), mcfg,
                               ctx.fault.get("scores"))

    clients = serve.Clients({"mix": mix, "seed": seed, "arch_blocks": arch,
                             "sys_path": list(sys.path),
                             "generators": traffic.GENERATORS},
                            mix["clients"])
    try:
        t_c = time.monotonic()
        shapes = census(mix, seed, arch)
        exe_c = ctx.clock.executables
        for spec in shapes:
            np.asarray(predict.fn(params, zero_batch(spec)))
        ctx.counters.update({
            "census_buckets": len(shapes),
            "census_shapes": [list(s) for s in shapes[:8]],
            "census_executables": ctx.clock.executables - exe_c,
            "census_s": time.monotonic() - t_c})

        svc = CostModelService(
            params, mc, common.normalizer(norm),
            node_budget=mix["node_budget"],
            cache_capacity=mix["cache_capacity"], predict_fn=predict)
        srv = CostModelServer(svc, max_queue=mix["max_queue"],
                              coalesce_limit=mix["coalesce_limit"]).start()
        t_start = time.monotonic() + (2.0 if ctx.trace else 0.5)
        t_end = t_start + ctx.seconds
        clients.start(srv.address, "run", t_start, t_end)
        ctx.begin_window(t_start)
        exe0 = ctx.clock.executables
        predict.live = True
        time.sleep(max(0.0, ctx.trace_end - time.monotonic()))
        ctx.end_trace()
        time.sleep(max(0.0, t_end - time.monotonic()))
        predict.live = False
        in_window = ctx.clock.names[exe0:]
        stats = dict(serve._stats(svc), **segment_counters(svc))
        records = clients.collect()
        srv.stop()
    finally:
        clients.close()
    del svc, srv
    memory = common.memory_peak(ctx.devices)

    reqs = [(c, r) for c, recs in enumerate(records) for r in recs
            if t_start <= r[0] < t_end]
    done = [(c, r) for c, r in reqs if r[6] is None]
    lost = sum(1 for _, r in reqs if r[6] in ("ClientError", "ProtocolError"))
    lat = np.array([(r[1] - r[0]) * 1e3 for _, r in done])
    served = sum(r[2] for _, r in done if r[1] <= t_end)

    # the check: a sample drawn from the seed, with the largest request
    rng = np.random.default_rng(traffic.seq(seed, 99))
    k = min(mix["check_requests"], len(done))
    pick = set(int(i) for i in rng.choice(len(done), k, replace=False))
    if done:
        pick.add(int(np.argmax([r[3] for _, r in done])))
    gens, graphs, got_scores = {}, [], []
    for i in sorted(pick):
        c, r = done[i]
        gen = gens.setdefault(c, traffic.generator(mix, seed, "run", c,
                                                   arch))
        for g, s in zip(gen.rebuild(r[4]), r[5]):
            graphs.append(g.to_dict())
            got_scores.append(float(s))
    got_scores = np.asarray(got_scores, np.float64)
    t_ref = time.monotonic()
    ref = (reference_segmented.score(params, mcfg, graphs, norm,
                                     mix["node_budget"], jnp.float32)
           if graphs else np.zeros(0))
    checks = {k: (v, mix["limit_" + k])
              for k, v in serve.score_gaps(got_scores, ref).items()}
    checks["lost_requests"] = (float(lost), 0.0)
    correct = all(v <= lim for v, lim in checks.values())

    ctx.counters.update(stats)
    ctx.counters.update({
        "model_calls": predict.calls, "model_seconds": predict.seconds,
        "model_flops": predict.flops, "executables": len(in_window),
        "window_executables": in_window[:8],
        "check_graphs": len(graphs),
        "check_s": time.monotonic() - t_ref})
    return {
        "correct": correct, "attempted": len(reqs),
        "failed": len(reqs) - len(done), "memory_peak_bytes": memory,
        "checks": checks,
        "end_to_end": {
            "served_graphs_per_s": served / ctx.seconds,
            "serve_p95_ms": (float(np.percentile(lat, 95)) if lat.size
                             else float("nan"))},
    }


def control(cfg: dict, mix: dict, seed: int, program: bool = False) -> dict:
    """The control's numbers on the cell's sample, the first requests of
    every client's stream: the reference in bfloat16 against the float32
    one, by the check's own numbers; with `program`, the service's scores
    of the same programs beside them, one flush each."""
    import jax.numpy as jnp
    arch = arch_blocks(mix)
    norm = fit_norm(mix, seed, arch)
    per_client = max(1, mix["check_requests"] // mix["clients"])
    graphs = []
    for c in range(mix["clients"]):
        gen = traffic.generator(mix, seed, "run", c, arch).requests()
        graphs += [g for _ in range(per_client) for g in next(gen)[1]]
    wire = [g.to_dict() for g in graphs]
    params = common.make_params(cfg, seed)
    ref = reference_segmented.score(params, cfg["model"], wire, norm,
                                    mix["node_budget"], jnp.float32)
    low = reference_segmented.score(params, cfg["model"], wire, norm,
                                    mix["node_budget"], jnp.bfloat16)
    out = dict(serve.score_gaps(low, ref), graphs=len(graphs),
               nodes=[g.num_nodes for g in graphs])
    if program:
        from repro.core.evaluate import make_predict_fn
        from repro.serving import CostModelService
        mc = common.model_config(cfg)
        svc = CostModelService(
            params, mc, common.normalizer(norm),
            node_budget=mix["node_budget"],
            cache_capacity=mix["cache_capacity"],
            predict_fn=make_predict_fn(mc))
        got = np.array([svc.predict_many([g])[0] for g in graphs],
                       np.float64)
        out["program"] = serve.score_gaps(got, ref)
    return out
