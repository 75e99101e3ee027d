"""Traffic of the chip benchmark: the loader of each mix's generator, the
content digest of a mix's requests, the bucket census of a request stream,
and the client processes of the serve cells.

A traffic mix is a JSON file of parameters under `traffic/`; its "kind"
names a generator module `generators/<kind>.py`, found by name, so a new
kind of traffic is a new file. Every serve request is a pure function of
(seed, role, client, index), so the reference can rebuild any request from
its descriptor after the window.

This module imports numpy and the program's graph generators only, never
JAX, so client processes do not touch the chip.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import time

import numpy as np

# a stream's role enters its seed: the window's requests, the requests the
# feature normalizer is fitted on, and the digest's, are disjoint
ROLES = {"run": 0, "norm": 2, "digest": 3}
# where `kind` looks for generator modules; a run points it at its
# checkout's `generators/`
GENERATORS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "generators")
_KINDS: dict = {}


def seq(*parts) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(p) for p in parts])


def kind(mix: dict):
    """The generator module the mix names, `<GENERATORS>/<kind>.py`, loaded
    by path once per process. Its `LOOP` names the loop module beside this
    one that drives it (`serve` or `train`); a serve generator module holds
    `Generator(mix, seed, role, client, arch_blocks)`, a train one the
    corpus, the trainer's task and the reference's loss."""
    name = mix["kind"]
    if not name.replace("_", "").isalnum():
        raise SystemExit(f"bad traffic kind {name!r}")
    path = os.path.join(GENERATORS, name + ".py")
    if path not in _KINDS:
        if not os.path.isfile(path):
            raise SystemExit(f"no generator {path} for traffic kind "
                             f"{name!r}")
        spec = importlib.util.spec_from_file_location("gen_" + name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _KINDS[path] = mod
    return _KINDS[path]


def generator(mix: dict, seed: int, role: str, client: int,
              arch_blocks=()):
    """One client's request stream under one role."""
    return kind(mix).Generator(mix, seed, role, client, list(arch_blocks))


def digest(mix: dict, arch_blocks=(), requests: int = 8) -> str:
    """Content digest of seed 0's first requests: it changes when the
    program's graph generators start to emit different graphs."""
    gen = generator(mix, 0, "digest", 0, arch_blocks).requests()
    h = hashlib.sha256()
    for _ in range(requests):
        _, graphs = next(gen)
        for g in graphs:
            h.update(json.dumps(g.to_dict(), sort_keys=True).encode())
    return h.hexdigest()[:16]


def request_buckets(requests, n: int, node_budget: int,
                    order_sensitive: bool) -> dict:
    """{(node, edge, graph, reduce) capacities: packs} of the first `n`
    requests of one client's stream when the server flushes each request
    on its own: the misses of the request (against everything scored
    before), flushed whenever `node_budget` nodes are pending and at its
    end, packed as the service packs them (`pack_graphs`, `bucket_for`)."""
    from repro.data.batching import bucket_for, pack_graphs
    seen: set = set()
    out: dict = {}

    def flush(graphs):
        for pack in pack_graphs(graphs, node_budget, oversized="singleton"):
            b = bucket_for([graphs[i] for i in pack])
            key = (b.node_capacity, b.edge_capacity, b.graph_capacity,
                   b.reduce_capacity)
            out[key] = out.get(key, 0) + 1

    for _ in range(n):
        pending, nodes = [], 0
        for g in next(requests)[1]:
            key = g.canonical_hash(order_sensitive=order_sensitive)
            if key in seen:
                continue
            seen.add(key)
            pending.append(g)
            nodes += g.num_nodes
            if nodes >= node_budget:
                flush(pending)
                pending, nodes = [], 0
        if pending:
            flush(pending)
    return out


def request_sizes(requests, n: int) -> list:
    """[(nodes, unique edges)] of every graph of the first `n` requests."""
    return [[(g.num_nodes, len(g.unique_edges())) for g in next(requests)[1]]
            for _ in range(n)]


# ----------------------------------------------------------------------------
# client processes (spawned; closed loop, one request in flight each)
# ----------------------------------------------------------------------------
def client_main(conn, stop, spec: dict) -> None:
    """Serve phases the parent sends over `conn` until it sends "exit".

    {"op": "census", "role", "n", "order_sensitive"}: send back
    `request_buckets` of the first n requests of this client's stream;
    {"op": "sizes", "role", "n"}: send back their `request_sizes`.
    {"op": "run", "host", "port", "role", "t_start", "t_end"}: wait until
    t_start (CLOCK_MONOTONIC, shared by every process of the machine), then
    send requests back to back until t_end or `stop` is set, and send back
    one record per request: (t_send, t_recv, graphs, nodes, descriptor,
    scores or None, error name or None)."""
    import sys
    global GENERATORS
    sys.path[:0] = spec["sys_path"]
    GENERATORS = spec["generators"]
    from repro.serving.client import ClientError, CostModelClient
    gens = {}
    while True:
        cmd = conn.recv()
        if cmd["op"] == "exit":
            break
        role = cmd["role"]
        if cmd["op"] in ("census", "sizes"):
            fresh = generator(spec["mix"], spec["seed"], role,
                              spec["client"], spec["arch_blocks"]).requests()
            conn.send(request_buckets(fresh, cmd["n"],
                                      spec["mix"]["node_budget"],
                                      cmd["order_sensitive"])
                      if cmd["op"] == "census"
                      else request_sizes(fresh, cmd["n"]))
            continue
        if role not in gens:
            gens[role] = generator(spec["mix"], spec["seed"], role,
                                   spec["client"],
                                   spec["arch_blocks"]).requests()
        stream = gens[role]
        recs = []
        client = CostModelClient(cmd["host"], cmd["port"], timeout_s=120.0,
                                 retries=0)
        try:
            first = next(stream)
            delay = cmd["t_start"] - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            nxt = first
            while time.monotonic() < cmd["t_end"] and not stop.is_set():
                desc, graphs = nxt
                t0 = time.monotonic()
                try:
                    scores = client.predict_many(graphs)
                    err = None
                except ClientError as e:
                    scores, err = None, type(e).__name__
                t1 = time.monotonic()
                recs.append((t0, t1, len(graphs),
                             sum(g.num_nodes for g in graphs), desc, scores,
                             err))
                nxt = next(stream)
        finally:
            client.close()
        conn.send(recs)
