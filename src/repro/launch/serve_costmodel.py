"""Cost-model prediction-service replay: throughput / hit-rate report.

Replays a deterministic tile-search query stream (overlapping candidate
subsets, several rounds per kernel — see `repro.serving.replay`) through
`CostModelService` and prints queries/sec, cache hit rate, coalescing and
flush behavior, per-bucket occupancy, and per-call latency percentiles.
With `--compare-direct` it also times the uncached per-request path
(`core.evaluate.predict_kernels`) on the same stream and reports the
speedup plus the max prediction delta between the two paths.

  PYTHONPATH=src python -m repro.launch.serve_costmodel \\
      --programs 8 --rounds 4 --compare-direct

Two additional modes expose the same service over a socket
(`repro.serving.server`, docs/SERVING.md §server):

  # serve: build the model once, answer predict requests until ^C
  PYTHONPATH=src python -m repro.launch.serve_costmodel \\
      --listen 127.0.0.1:7450 --snapshot /tmp/warm.npz

  # connect: replay the query stream against a running server
  PYTHONPATH=src python -m repro.launch.serve_costmodel \\
      --connect 127.0.0.1:7450

`--connect` never imports jax — the graphs travel as JSON and scoring
happens server-side — so replay clients are cheap to fan out.

Flags:
  --programs N        synthetic programs in the corpus        (default 8)
  --max-configs N     tile candidates per kernel              (default 16)
  --rounds N          search passes over each kernel          (default 4)
  --subset F          candidate fraction sampled per round    (default 0.75)
  --adjacency A       sparse | dense batching representation  (default sparse)
  --cache-capacity N  LRU prediction-cache entries            (default 65536)
  --node-budget N     sparse pack budget / coalescer flush    (default 8*max_nodes)
  --chunk N           dense chunk width                       (default 128)
  --hidden-dim N      model width (untrained params; serving  (default: the
                      throughput does not depend on training)  model's, 192)
  --precision P       f32 | int8 serving weights (int8 runs   (default f32)
                      `repro.quant.quantize_params` on the
                      init params, calibrated on the stream)
  --seed N            corpus/model seed                       (default 0)
  --compare-direct    also time uncached per-request scoring
  --listen H:P        serve over a socket instead of replaying locally
  --connect H:P       replay against a running --listen server (no jax)
  --max-queue N       --listen: admission queue bound         (default 64)
  --deadline-ms F     --listen: default per-request deadline  (default none)
  --snapshot PATH     --listen: warm-cache npz (restored at start,
                      written at shutdown)
"""
from __future__ import annotations

import argparse

import numpy as np


def _host_port(spec: str) -> tuple[str, int]:
    host, _, port = spec.rpartition(":")
    if not host or not port.isdigit():
        raise argparse.ArgumentTypeError(
            f"expected HOST:PORT, got {spec!r}")
    return host, int(port)


def _build_model(args):
    """The replay stream and the untrained model to serve it with, at the
    model's own widths. --precision int8 quantizes the weights
    per-channel, calibrating on a slice of the stream. Returns
    (replay, params, cfg)."""
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    import jax

    from repro.core.model import CostModelConfig, cost_model_init
    from repro.serving.replay import build_tile_replay

    replay = build_tile_replay(args.programs, max_configs=args.max_configs,
                               rounds=args.rounds, subset=args.subset,
                               seed=args.seed)
    max_nodes = max(g.num_nodes for r in replay.requests for g in r)
    cfg = CostModelConfig(gnn="graphsage", reduction="column_wise",
                          hidden_dim=(args.hidden_dim
                                      or CostModelConfig.hidden_dim),
                          dropout=0.0, max_nodes=max_nodes,
                          adjacency=args.adjacency)
    params = cost_model_init(jax.random.key(args.seed), cfg)
    if args.precision != "int8":
        return replay, params, cfg
    from repro.quant import quantize_params

    calib = [g for req in replay.requests[:4] for g in req]
    qm = quantize_params(params, cfg, calib_graphs=calib,
                         normalizer=replay.normalizer)
    return replay, qm.params, qm.serving_config(cfg)


def _serve(args) -> int:
    """--listen: stand up the model + socket server, block until ^C."""
    from repro.core.evaluate import make_predict_fn
    from repro.serving import CostModelService
    from repro.serving.server import CostModelServer

    replay, params, cfg = _build_model(args)
    service = CostModelService(params, cfg, replay.normalizer,
                               cache_capacity=args.cache_capacity,
                               node_budget=args.node_budget,
                               chunk=args.chunk,
                               predict_fn=make_predict_fn(cfg))
    host, port = args.listen
    server = CostModelServer(service, host=host, port=port,
                             max_queue=args.max_queue,
                             default_deadline_ms=args.deadline_ms,
                             snapshot_path=args.snapshot)
    server.start()
    bound = server.address
    print(f"serving cost model on {bound[0]}:{bound[1]} "
          f"(max_queue={args.max_queue}, "
          f"restored {server.stats.restored_entries} warm entries); ^C stops")
    try:
        import threading
        threading.Event().wait()       # serve until interrupted
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
        print(f"stopped; served {server.stats.completed} requests "
              f"({server.stats.shed_overloaded} shed)")
    return 0


def _connect(args) -> int:
    """--connect: replay the query stream through a running server.

    Stays jax-free: graphs are built with numpy and scored remotely."""
    from repro.serving.client import CostModelClient
    from repro.serving.replay import build_tile_replay, run_replay

    replay = build_tile_replay(args.programs, max_configs=args.max_configs,
                               rounds=args.rounds, subset=args.subset,
                               seed=args.seed)
    host, port = args.connect
    with CostModelClient(host, port) as client:
        client.ping()
        _, dt = run_replay(
            lambda gs: client.predict_many(gs, deadline_ms=args.deadline_ms),
            replay.requests)
        stats = client.stats()
    print(f"replayed {replay.num_queries} queries "
          f"({len(replay.requests)} requests) in {dt:.2f}s -> "
          f"{replay.num_queries / dt:.0f} queries/s")
    svc = stats["service"]
    print(f"server: hit_rate={svc['hit_rate']:.1%} "
          f"flushes={svc['flushes']} "
          f"completed={stats['server']['completed']} "
          f"shed={stats['server']['shed_overloaded']}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(
        description="Replay a tile-search query stream through the "
                    "cost-model prediction service.")
    ap.add_argument("--programs", type=int, default=8)
    ap.add_argument("--max-configs", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--subset", type=float, default=0.75)
    ap.add_argument("--adjacency", choices=("sparse", "dense"),
                    default="sparse")
    ap.add_argument("--cache-capacity", type=int, default=65536)
    ap.add_argument("--node-budget", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--hidden-dim", type=int, default=None)
    ap.add_argument("--precision", choices=("f32", "int8"), default="f32")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compare-direct", action="store_true")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--listen", type=_host_port, metavar="HOST:PORT")
    mode.add_argument("--connect", type=_host_port, metavar="HOST:PORT")
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--snapshot", default=None)
    args = ap.parse_args()

    if args.listen:
        return _serve(args)
    if args.connect:
        return _connect(args)

    from repro.core.evaluate import make_predict_fn, predict_kernels
    from repro.serving import CostModelService
    from repro.serving.replay import run_replay

    replay, params, cfg = _build_model(args)
    max_nodes = cfg.max_nodes
    predict_fn = make_predict_fn(cfg)
    print(f"replay: {replay.num_kernels} kernels, "
          f"{len(replay.requests)} requests, {replay.num_queries} queries "
          f"({replay.num_unique} unique graphs), adjacency={args.adjacency}, "
          f"precision={cfg.precision}")

    def make_service() -> CostModelService:
        return CostModelService(params, cfg, replay.normalizer,
                                cache_capacity=args.cache_capacity,
                                node_budget=args.node_budget,
                                chunk=args.chunk, predict_fn=predict_fn)

    # warm up jit on a throwaway service: one full pass traces every bucket
    # shape the stream can produce (compiles persist in the shared
    # predict_fn), so the timed passes below measure steady-state serving
    run_replay(make_service().predict_many, replay.requests)

    service = make_service()
    preds, dt = run_replay(service.predict_many, replay.requests)
    print(f"service: {replay.num_queries / dt:.0f} queries/s "
          f"({dt:.2f}s total)")
    print(service.stats().summary())

    if args.compare_direct:
        def direct(graphs):
            return predict_kernels(params, cfg, graphs, replay.normalizer,
                                   max_nodes=max_nodes, chunk=args.chunk,
                                   predict_fn=predict_fn,
                                   node_budget=args.node_budget)
        # the direct path's full-request packs can hit bucket shapes the
        # service warmup never produced; warm them before timing
        run_replay(direct, replay.requests)
        dpreds, ddt = run_replay(direct, replay.requests)
        err = max(float(np.max(np.abs(a - b)))
                  for a, b in zip(preds, dpreds))
        print(f"direct (uncached per-request): "
              f"{replay.num_queries / ddt:.0f} queries/s ({ddt:.2f}s)")
        print(f"speedup {ddt / dt:.2f}x, max prediction delta {err:.2e}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
