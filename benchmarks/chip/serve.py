"""The serve loop: one `CostModelServer` in this process, closed-loop
client processes over TCP, and the check of their answers against the
reference."""
from __future__ import annotations

import multiprocessing as mp
import sys
import time

import numpy as np

import common
import reference
import traffic


class TimedPredict:
    """The program's jitted predict function with a host clock around each
    call (ending in the host copy of its scores), the FLOPs of the graphs it
    computed, and an optional fault applied to its output (the tests'
    mutation checks)."""

    def __init__(self, fn, cfg: dict, fault=None):
        self.fn, self.cfg, self.fault = fn, cfg, fault
        self.live = False
        self.calls, self.seconds, self.flops = 0, 0.0, 0.0

    def __call__(self, params, batch):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.predict"):
            out = np.asarray(self.fn(params, batch))
        dt = time.perf_counter() - t0
        if self.fault is not None:
            out = self.fault(out, batch)
        if self.live:
            n, e = common.pack_counts(batch)
            self.calls += 1
            self.seconds += dt
            self.flops += float(np.sum(
                common.forward_flops(self.cfg, n, e)[n > 0]))
        return out


class Clients:
    """Spawned client processes, driven phase by phase."""

    def __init__(self, spec: dict, n: int):
        ctx = mp.get_context("spawn")
        self.stop = ctx.Event()
        self.conns, self.procs = [], []
        for c in range(n):
            mine, theirs = ctx.Pipe()
            p = ctx.Process(target=traffic.client_main,
                            args=(theirs, self.stop, dict(spec, client=c)),
                            daemon=True)
            p.start()
            self.conns.append(mine)
            self.procs.append(p)

    def start(self, address, role: str, t_start: float, t_end: float):
        self.stop.clear()
        for conn in self.conns:
            conn.send({"op": "run", "host": address[0], "port": address[1],
                       "role": role, "t_start": t_start, "t_end": t_end})

    def census(self, role: str, n: int, order_sensitive: bool) -> list:
        for conn in self.conns:
            conn.send({"op": "census", "role": role, "n": n,
                       "order_sensitive": order_sensitive})
        return self.collect()

    def sizes(self, role: str, n: int) -> list:
        for conn in self.conns:
            conn.send({"op": "sizes", "role": role, "n": n})
        return self.collect()

    def collect(self, timeout: float = 90.0) -> list:
        out = []
        for conn in self.conns:
            if not conn.poll(timeout):
                raise RuntimeError("a client sent no records")
            out.append(conn.recv())
        return out

    def close(self):
        for conn in self.conns:
            try:
                conn.send({"op": "exit"})
            except OSError:
                pass
        for p in self.procs:
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
                p.join(timeout=5)


class _Sized:
    """A stand-in graph of given size for the program's packing functions,
    which read only `num_nodes`, `unique_edges()` and `name`."""

    def __init__(self, nodes: int, edges: int):
        self.num_nodes, self._edges, self.name = nodes, edges, "sized"

    def unique_edges(self):
        return range(self._edges)


def census(mix: dict, seed: int, order_sensitive: bool, clients) -> list:
    """The bucket shapes of the packs the window's traffic makes, most
    frequent first. A server that flushes every request on its own makes
    shapes that follow from each client's stream, which the client
    processes replay (`traffic.request_buckets`, `census_requests` each).
    Otherwise a flush holds the misses of whichever requests meet in the
    queue: with every request a miss (whole programs), `census_flushes`
    flushes of 1 to `clients` requests from near the same place in each
    client's stream are packed from the requests' sizes."""
    from collections import Counter

    from repro.data.batching import BucketSpec, bucket_for, pack_graphs
    specs: Counter = Counter()
    n = mix["census_requests"]
    if mix["coalesce_limit"] == 1:
        for part in clients.census("run", n, order_sensitive):
            specs.update(part)
        return [BucketSpec(*k) for k, _ in specs.most_common()]
    sizes = clients.sizes("run", n)
    rng = np.random.default_rng(traffic.seq(seed, 98))
    for _ in range(mix["census_flushes"]):
        at = int(rng.integers(n))
        pick = rng.choice(len(sizes), int(rng.integers(1, len(sizes) + 1)),
                          replace=False)
        graphs = [_Sized(*g) for c in pick
                  for g in sizes[int(c)][min(n - 1, max(0, at + int(
                      rng.integers(-2, 3))))]]
        for pack in pack_graphs(graphs, mix["node_budget"],
                                oversized="singleton"):
            specs[bucket_for([graphs[i] for i in pack])] += 1
    return [s for s, _ in specs.most_common()]


def zero_batch(spec):
    """An all-zero packed batch of one bucket's shapes."""
    from repro.core import features as F
    m, e = spec.node_capacity, spec.edge_capacity
    g, r = spec.graph_capacity, spec.reduce_capacity
    f32, i32 = np.float32, np.int32
    return F.SparseGraphBatch(
        np.zeros(m, i32), np.zeros((m, F.NODE_FEATURE_DIM), f32),
        np.zeros(m, f32), np.zeros(m, i32), np.zeros(e, i32),
        np.zeros(e, i32), np.zeros(e, f32),
        np.zeros((g, F.KERNEL_FEATURE_DIM), f32), np.zeros(g, f32),
        np.full((g, r), m, i32), np.zeros((g, r), f32))


def _stats(service) -> dict:
    s = service.stats()
    graphs = sum(b.graphs for b in s.buckets.values())
    packs = sum(b.flushes for b in s.buckets.values())
    fill = sum(b.mean_node_occupancy * b.flushes for b in s.buckets.values())
    return {"hits": s.cache.hits, "misses": s.cache.misses,
            "flushes": s.flushes, "graphs_scored": graphs, "packs": packs,
            "pack_fill_sum": fill}


def score_gaps(got, ref) -> dict:
    """Each score's gap from the reference's, over the largest reference
    score of the sample (infinite for a non-finite score): the 95th
    percentile and the largest. The percentile separates the bfloat16
    control: the program's scores equal the reference's bit for bit but for
    about one in a hundred, where a bfloat16 operand rounded the other way,
    and those few lie as far off as the control's typical gap (PERF.md,
    Findings). The largest gap catches one answer that is grossly wrong."""
    if not len(got) or not np.all(np.isfinite(got)):
        return {"score_gap_p95": float("inf"),
                "score_gap_max": float("inf")}
    gap = np.abs(got - ref) / np.max(np.abs(ref))
    return {"score_gap_p95": float(np.percentile(gap, 95)),
            "score_gap_max": float(np.max(gap))}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.evaluate import make_predict_fn
    from repro.serving import CostModelService
    from repro.serving.server import CostModelServer

    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    mcfg = cfg["model"]
    mc = common.model_config(cfg)
    params = common.make_params(cfg, seed)

    arch = []
    if mix.get("arch_blocks"):
        from repro.core.hlo_import import import_arch_program
        arch = [import_arch_program(a).to_dict() for a in mix["arch_blocks"]]
    got = traffic.digest(mix, arch)
    if got != mix["digest"]:
        raise SystemExit(f"the program's graph generators changed: traffic "
                         f"digest {got}, expected {mix['digest']}")

    norm_gen = traffic.generator(mix, seed, "norm", 0, arch).requests()
    norm_graphs = [g for _ in range(mix["norm_requests"])
                   for g in next(norm_gen)[1]]
    norm = reference.fit_normalizer(
        [reference.featurize(g.to_dict()) for g in norm_graphs])

    predict = TimedPredict(make_predict_fn(mc), mcfg,
                           ctx.fault.get("scores"))

    clients = Clients({"mix": mix, "seed": seed, "arch_blocks": arch,
                       "sys_path": list(sys.path),
                       "generators": traffic.GENERATORS}, mix["clients"])
    try:
        # warm-up: every bucket shape of the census, through the program's
        # own jitted predict function, on all-zero packs
        t_c = time.monotonic()
        shapes = census(mix, seed, mc.reduction == "lstm", clients)
        exe_c = ctx.clock.executables
        for spec in shapes:
            np.asarray(predict.fn(params, zero_batch(spec)))
        ctx.counters.update({
            "census_buckets": len(shapes),
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
        stats = _stats(svc)
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
    gens = {}
    feats, got_scores = [], []
    for i in sorted(pick):
        c, r = done[i]
        gen = gens.setdefault(c, traffic.generator(mix, seed, "run", c,
                                                   arch))
        for g, s in zip(gen.rebuild(r[4]), r[5]):
            feats.append(reference.featurize(g.to_dict()))
            got_scores.append(float(s))
    got_scores = np.asarray(got_scores, np.float64)
    t_ref = time.monotonic()
    ref = (reference.score(params, mcfg, feats, norm, jnp.float32)
           if feats else np.zeros(0))
    checks = {k: (v, mix["limit_" + k])
              for k, v in score_gaps(got_scores, ref).items()}
    checks["lost_requests"] = (float(lost), 0.0)
    correct = all(v <= lim for v, lim in checks.values())

    ctx.counters.update(stats)
    ctx.counters.update({
        "model_calls": predict.calls, "model_seconds": predict.seconds,
        "model_flops": predict.flops, "executables": len(in_window),
        "window_executables": in_window[:8],
        "check_graphs": len(feats),
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
