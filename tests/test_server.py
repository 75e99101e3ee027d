"""Concurrency + fault-injection suite for the cost-model socket server
(docs/SERVING.md §server).

Every test carries a deadline (`@pytest.mark.timeout` module-wide): a
deadlocked server must *fail* the suite, never hang it. Synchronization
is events/joins with timeouts — no sleeps. The blocking-service tests use
a jax-free stub (the server only needs the `submit/flush/stats/
snapshot_cache/restore_cache` protocol), so queue/deadline/shutdown
semantics are exercised without model latency noise; the parity tests run
against the real `CostModelService`.
"""
import os
import socket
import struct
import threading

import jax
import numpy as np
import pytest

from repro.core.evaluate import make_predict_fn, predict_kernels
from repro.core.model import CostModelConfig, cost_model_init
from repro.core import features as F
from repro.data.synthetic import random_kernel
from repro.serving import CostModelService, PredictionCache, RequestCoalescer
from repro.serving.client import (
    ClientError,
    CostModelClient,
    DeadlineExceeded,
    Overloaded,
    ProtocolError,
    WorkerFailure,
)
from repro.serving.server import CostModelServer, FaultPolicy, ServerStats

pytestmark = pytest.mark.timeout(180)

MAX_NODES = 32
JOIN_S = 30            # generous thread-join bound; tests fail, not hang


# ---------------------------------------------------------------------------
# Fixtures
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def world():
    graphs = [random_kernel(n, seed=n) for n in (5, 7, 9, 12, 15, 18)]
    norm = F.fit_normalizer(graphs)
    cfg = CostModelConfig(gnn="graphsage", reduction="column_wise",
                          hidden_dim=16, opcode_embed_dim=8, dropout=0.0,
                          max_nodes=MAX_NODES, adjacency="sparse")
    params = cost_model_init(jax.random.key(0), cfg)
    predict_fn = make_predict_fn(cfg)
    return {"graphs": graphs, "norm": norm, "cfg": cfg, "params": params,
            "predict_fn": predict_fn}


def _service(world, **kw):
    return CostModelService(world["params"], world["cfg"], world["norm"],
                            predict_fn=world["predict_fn"], **kw)


class StubService:
    """jax-free stand-in implementing the server's service protocol.

    `gate` blocks every scoring call until set (saturation/shutdown
    tests); `started` is set when a scoring call begins. Scores are the
    graphs' node counts, so results stay checkable."""

    def __init__(self, *, blocking: bool = False):
        self.cache = PredictionCache(4096)
        self.gate = threading.Event()
        self.started = threading.Event()
        if not blocking:
            self.gate.set()
        self.coalescer = RequestCoalescer(self._score, node_budget=1 << 30,
                                          on_scored=self.cache.put)

    def _score(self, graphs):
        self.started.set()
        if not self.gate.wait(timeout=JOIN_S):
            raise TimeoutError("test forgot to open the gate")
        return np.array([g.num_nodes for g in graphs], np.float32)

    def submit(self, graphs):
        entries = []
        for g in graphs:
            key = g.canonical_hash()
            val = self.cache.get(key)
            entries.append(self.coalescer.add(key, g) if val is None else val)
        return _StubPending(self, entries)

    def flush(self):
        self.coalescer.flush()

    def stats(self):
        from repro.serving.service import ServiceStats
        return ServiceStats(requests=0, graphs=0, cache=self.cache.stats(),
                            coalesced=self.coalescer.coalesced,
                            flushes=self.coalescer.flushes,
                            flush_sizes=tuple(self.coalescer.flush_sizes))

    def snapshot_cache(self, path):
        return self.cache.snapshot(path)

    def restore_cache(self, path):
        return self.cache.restore(path)


class _StubPending:
    def __init__(self, service, entries):
        self._service, self._entries = service, entries

    def result(self):
        if any(hasattr(e, "ready") and not e.ready for e in self._entries):
            self._service.flush()
        return np.array([e.value if hasattr(e, "ready") else e
                         for e in self._entries], np.float32)


def _start(service, **kw) -> CostModelServer:
    return CostModelServer(service, **kw).start()


def _drain_threads(before):
    """Names of costmodel threads that outlived a stop()."""
    return [t.name for t in threading.enumerate()
            if t not in before and t.is_alive()
            and t.name.startswith("costmodel-server")]


# ---------------------------------------------------------------------------
# Concurrency: N clients x M requests, bit-identical to the direct path
# ---------------------------------------------------------------------------
def test_concurrent_clients_bit_identical(world):
    graphs = world["graphs"]
    # per-thread request streams: overlapping slices, like interleaved
    # tile-search clients
    streams = [[graphs[i % len(graphs)], graphs[(i + t) % len(graphs)]]
               for t in range(8) for i in range(4)]
    direct = {g.canonical_hash(): s for g, s in zip(
        graphs, predict_kernels(world["params"], world["cfg"], graphs,
                                world["norm"], max_nodes=MAX_NODES,
                                predict_fn=world["predict_fn"]))}
    server = _start(_service(world))
    host, port = server.address
    failures = []

    def client_thread(t):
        try:
            with CostModelClient(host, port) as c:
                for req in streams[t * 4:(t + 1) * 4]:
                    got = c.predict_many(req, deadline_ms=60_000)
                    want = np.array([direct[g.canonical_hash()]
                                     for g in req], np.float32)
                    if not np.array_equal(got, want):
                        failures.append((t, got, want))
        except Exception as e:                        # noqa: BLE001
            failures.append((t, repr(e)))

    threads = [threading.Thread(target=client_thread, args=(t,))
               for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=JOIN_S)
    assert not any(t.is_alive() for t in threads), "client threads hung"
    assert not failures, failures[:3]
    stats = server.stats
    assert stats.completed == 8 * 4
    assert stats.shed_overloaded == 0 and stats.shed_deadline == 0
    server.stop()


def test_cross_client_coalescing(world):
    """Identical graphs sent by different sockets while the worker is
    busy share one coalescer ticket (scored once)."""
    stub = StubService(blocking=True)
    server = _start(stub, coalesce_limit=8)
    host, port = server.address
    g = random_kernel(6, seed=0)
    warm = random_kernel(4, seed=1)
    results = []

    def one_client():
        with CostModelClient(host, port) as c:
            results.append(c.predict_many([g], deadline_ms=60_000))

    # occupy the worker so later requests pile up in the queue
    blocker = threading.Thread(target=lambda: CostModelClient(
        host, port).predict_many([warm], deadline_ms=60_000))
    blocker.start()
    assert stub.started.wait(timeout=JOIN_S)
    stub.gate.clear()                    # next scoring call will block too
    clients = [threading.Thread(target=one_client) for _ in range(4)]
    for t in clients:
        t.start()
    # all 4 duplicates must be queued before the worker drains them
    deadline = threading.Event()
    for _ in range(2000):
        if server._queue.qsize() >= 4:
            break
        deadline.wait(0.005)
    stub.gate.set()
    blocker.join(timeout=JOIN_S)
    for t in clients:
        t.join(timeout=JOIN_S)
    assert not any(t.is_alive() for t in clients)
    assert len(results) == 4
    assert all(float(r[0]) == g.num_nodes for r in results)
    # 4 identical graphs -> one scored entry; the rest were coalescer
    # shares or cache hits, never separate model scores
    scored = sum(stub.coalescer.flush_sizes)
    assert scored <= 2                   # warm graph + g exactly once
    server.stop()


# ---------------------------------------------------------------------------
# Fault injection: every mode ends in a clean typed error or retry success
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def fault_server(world):
    server = _start(_service(world), allow_request_faults=True)
    yield server
    server.stop()


def test_fault_drop_is_clean_error(world, fault_server):
    host, port = fault_server.address
    with CostModelClient(host, port, retries=2, timeout_s=10) as c:
        with pytest.raises(ClientError):
            # the fault rides every resend, so retries exhaust cleanly
            c.inject_fault(world["graphs"][:2], "drop")
        # the connection was dropped, not the server: next call works
        out = c.predict_many(world["graphs"][:2], deadline_ms=60_000)
        assert out.shape == (2,)


def test_fault_delay_still_answers(world, fault_server):
    host, port = fault_server.address
    with CostModelClient(host, port) as c:
        want = c.predict_many(world["graphs"][:3], deadline_ms=60_000)
        got = c.inject_fault(world["graphs"][:3], "delay", delay_s=0.05)
        assert np.array_equal(got, want)


def test_fault_corrupt_frame_is_clean_error(world, fault_server):
    host, port = fault_server.address
    with CostModelClient(host, port, retries=1, timeout_s=10) as c:
        with pytest.raises(ProtocolError):
            c.inject_fault(world["graphs"][:2], "corrupt")
        assert c.predict_many(world["graphs"][:2],
                              deadline_ms=60_000).shape == (2,)


def test_fault_kill_flush_worker_recovers(world, fault_server):
    host, port = fault_server.address
    before = fault_server.stats.worker_failures
    with CostModelClient(host, port, retries=0, timeout_s=10) as c:
        with pytest.raises(WorkerFailure):
            c.inject_fault(world["graphs"][:2], "kill_flush")
        # the scoring pass died; the server did not
        out = c.predict_many(world["graphs"][:2], deadline_ms=60_000)
        assert out.shape == (2,)
    assert fault_server.stats.worker_failures > before


def test_server_side_fault_policy_retry_succeeds(world):
    """A transient server-side fault (one poisoned request) is survived by
    the client's retry: the resend gets a fresh sequence number."""
    server = _start(_service(world),
                    fault_policy=FaultPolicy("corrupt", requests=(1,)))
    host, port = server.address
    with CostModelClient(host, port, retries=2) as c:
        out = c.predict_many(world["graphs"][:2], deadline_ms=60_000)
        assert out.shape == (2,) and c.retried >= 1
    assert server.stats.faults_injected == 1
    server.stop()


def test_fault_policy_validates_mode():
    with pytest.raises(ValueError):
        FaultPolicy("segfault")


# ---------------------------------------------------------------------------
# Admission control: explicit shedding, never hangs, recovers
# ---------------------------------------------------------------------------
def test_overload_sheds_and_recovers():
    stub = StubService(blocking=True)
    server = _start(stub, max_queue=1, coalesce_limit=1)
    host, port = server.address
    results, errors = [], []

    def call(tag, **kw):
        try:
            with CostModelClient(host, port, retries=0, **kw) as c:
                results.append((tag, c.predict_many(
                    [random_kernel(5, seed=0)], deadline_ms=60_000)))
        except ClientError as e:
            errors.append((tag, e))

    # A occupies the worker (scoring blocked on the gate)...
    a = threading.Thread(target=call, args=("A",))
    a.start()
    assert stub.started.wait(timeout=JOIN_S)
    # ...B fills the queue (same graph: it will be a cache hit later)...
    b = threading.Thread(target=call, args=("B",))
    b.start()
    poll = threading.Event()
    for _ in range(2000):
        if server._queue.qsize() >= 1:
            break
        poll.wait(0.005)
    assert server._queue.qsize() >= 1
    # ...C must be shed immediately with an explicit `overloaded`
    with CostModelClient(host, port, retries=0) as c:
        with pytest.raises(Overloaded):
            c.predict_many([random_kernel(7, seed=1)], deadline_ms=60_000)
    assert server.stats.shed_overloaded == 1
    # release the gate: A and B complete, and the server has recovered
    stub.gate.set()
    a.join(timeout=JOIN_S)
    b.join(timeout=JOIN_S)
    assert not a.is_alive() and not b.is_alive()
    assert not errors and len(results) == 2
    with CostModelClient(host, port, retries=0) as c:
        assert c.predict_many([random_kernel(7, seed=1)],
                              deadline_ms=60_000).shape == (1,)
    # full accounting: every admitted request was answered
    s = server.stats
    assert s.requests == s.completed + s.shed_overloaded + s.shed_deadline
    server.stop()


def test_deadline_exceeded_while_queued():
    stub = StubService(blocking=True)
    server = _start(stub, max_queue=4, coalesce_limit=1)
    host, port = server.address
    outcome = {}

    def call_a():
        with CostModelClient(host, port) as c:
            outcome["A"] = c.predict_many([random_kernel(5, seed=0)],
                                          deadline_ms=60_000)

    def call_b():
        try:
            with CostModelClient(host, port, retries=0) as c:
                outcome["B"] = c.predict_many([random_kernel(9, seed=2)],
                                              deadline_ms=1.0)
        except DeadlineExceeded as e:
            outcome["B"] = e

    a = threading.Thread(target=call_a)
    a.start()
    assert stub.started.wait(timeout=JOIN_S)   # worker is busy scoring A
    b = threading.Thread(target=call_b)
    b.start()
    poll = threading.Event()
    for _ in range(2000):                       # B is parked in the queue
        if server._queue.qsize() >= 1:
            break
        poll.wait(0.005)
    poll.wait(0.01)                             # > B's 1ms deadline
    stub.gate.set()
    a.join(timeout=JOIN_S)
    b.join(timeout=JOIN_S)
    assert not a.is_alive() and not b.is_alive()
    assert isinstance(outcome["B"], DeadlineExceeded)
    assert outcome["A"].shape == (1,)
    assert server.stats.shed_deadline == 1
    server.stop()


# ---------------------------------------------------------------------------
# Warm cache: snapshot -> restart -> replay is hit-for-hit exact
# ---------------------------------------------------------------------------
def test_warm_snapshot_restart_replay_exact(world, tmp_path):
    snap = os.fspath(tmp_path / "warm-cache.npz")
    graphs = world["graphs"]
    cold_svc = _service(world)
    server = _start(cold_svc, snapshot_path=snap)
    host, port = server.address
    with CostModelClient(host, port) as c:
        want = c.predict_many(graphs, deadline_ms=60_000)
    server.stop()                               # writes the snapshot
    assert os.path.exists(snap)

    warm_svc = _service(world)
    server2 = _start(warm_svc, snapshot_path=snap)
    assert server2.stats.restored_entries == len(graphs)
    with CostModelClient(*server2.address) as c:
        got = c.predict_many(graphs, deadline_ms=60_000)
    s = warm_svc.stats()
    server2.stop()
    assert np.array_equal(got, want)            # hit-for-hit exact
    assert s.cache.misses == 0 and s.cache.hits == len(graphs)
    assert s.flushes == 0                       # the model was never touched


def test_snapshot_op_roundtrip(world, tmp_path):
    snap = os.fspath(tmp_path / "op-snapshot.npz")
    server = _start(_service(world))
    with CostModelClient(*server.address) as c:
        c.predict_many(world["graphs"][:4], deadline_ms=60_000)
        assert c.snapshot(snap) == 4
    server.stop()
    warm = PredictionCache(64)
    assert warm.restore(snap) == 4


# ---------------------------------------------------------------------------
# Shutdown: in-flight requests answered, no leaked threads or sockets
# ---------------------------------------------------------------------------
def test_shutdown_with_inflight_leaves_nothing_behind():
    before = set(threading.enumerate())
    stub = StubService(blocking=True)
    server = _start(stub, max_queue=8, coalesce_limit=1)
    host, port = server.address
    answered = []

    def call(tag):
        try:
            with CostModelClient(host, port, retries=0, timeout_s=20) as c:
                answered.append((tag, c.predict_many(
                    [random_kernel(5, seed=0)], deadline_ms=60_000)))
        except ClientError as e:
            answered.append((tag, e))

    a = threading.Thread(target=call, args=("inflight",))
    a.start()
    assert stub.started.wait(timeout=JOIN_S)
    b = threading.Thread(target=call, args=("queued",))
    b.start()
    poll = threading.Event()
    for _ in range(2000):
        if server._queue.qsize() >= 1:
            break
        poll.wait(0.005)
    stopper = threading.Thread(target=server.stop)
    stopper.start()
    stub.gate.set()                     # let the in-flight batch finish
    stopper.join(timeout=JOIN_S)
    a.join(timeout=JOIN_S)
    b.join(timeout=JOIN_S)
    assert not stopper.is_alive() and not a.is_alive() and not b.is_alive()
    # both requests were *answered* — scores or a typed error, no silence
    assert len(answered) == 2
    assert _drain_threads(before) == []
    # the listener socket is really gone: a fresh connect must fail
    with pytest.raises(OSError):
        socket.create_connection((host, port), timeout=2)


def test_stop_is_idempotent(world):
    server = _start(_service(world))
    server.stop()
    server.stop()                               # second stop: clean no-op


def test_client_shutdown_op():
    before = set(threading.enumerate())
    stub = StubService()
    server = _start(stub)
    c = CostModelClient(*server.address)
    c.shutdown()
    # the stop runs in the background; join the server's own threads
    for _ in range(2000):
        if not server.running and _drain_threads(before) == []:
            break
        threading.Event().wait(0.005)
    assert not server.running
    assert _drain_threads(before) == []


# ---------------------------------------------------------------------------
# Protocol hygiene
# ---------------------------------------------------------------------------
def test_garbage_frame_drops_connection_only():
    stub = StubService()
    server = _start(stub)
    host, port = server.address
    raw = socket.create_connection((host, port), timeout=5)
    raw.sendall(struct.pack(">I", 8) + b"notjson!")
    # server closes this connection (recv -> EOF)...
    raw.settimeout(5)
    assert raw.recv(1) == b""
    raw.close()
    # ...but keeps serving fresh ones
    with CostModelClient(host, port) as c:
        assert c.ping() > 0
    server.stop()


def test_oversize_frame_rejected():
    stub = StubService()
    server = _start(stub)
    host, port = server.address
    raw = socket.create_connection((host, port), timeout=5)
    raw.sendall(struct.pack(">I", (64 << 20) + 1))    # absurd length
    raw.settimeout(5)
    assert raw.recv(1) == b""
    raw.close()
    server.stop()


def test_unknown_op_is_bad_request():
    stub = StubService()
    server = _start(stub)
    with CostModelClient(*server.address, retries=0) as c:
        with pytest.raises(ClientError, match="bad_request"):
            c._call({"op": "frobnicate"})
    server.stop()


def test_undecodable_graphs_are_bad_request():
    stub = StubService()
    server = _start(stub)
    with CostModelClient(*server.address, retries=0) as c:
        with pytest.raises(ClientError, match="bad_request"):
            c._call({"op": "predict", "graphs": [{"bogus": 1}]})
    server.stop()


def test_stats_and_ping_ops(world):
    server = _start(_service(world))
    with CostModelClient(*server.address) as c:
        assert c.ping() > 0
        c.predict_many(world["graphs"][:3], deadline_ms=60_000)
        st = c.stats()
    assert st["server"]["completed"] == 1
    assert st["service"]["cache_size"] == 3
    assert st["service"]["flushes"] >= 1
    server.stop()


def test_server_stats_to_dict_roundtrip():
    s = ServerStats(connections=2, requests=5, completed=4,
                    shed_overloaded=1)
    d = s.to_dict()
    assert d["connections"] == 2 and d["shed_overloaded"] == 1
    assert set(d) == {"connections", "requests", "completed",
                      "shed_overloaded", "shed_deadline", "worker_failures",
                      "faults_injected", "restored_entries"}


# ---------------------------------------------------------------------------
# Spans and per-request latency
# ---------------------------------------------------------------------------
def test_server_and_client_import_without_jax():
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = ("import sys; import repro.serving.server, repro.serving.client; "
            "sys.exit('jax' in sys.modules)")
    env = dict(os.environ, PYTHONPATH=src)
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=120).returncode == 0


class SpanLog:
    """Stand-in for `repro.spans.span`: records every span, its metadata
    and whether it closed."""

    def __init__(self):
        self.spans = []
        self._lock = threading.Lock()

    def __call__(self, name, **meta):
        rec = _LoggedSpan(name, meta)
        with self._lock:
            self.spans.append(rec)
        return rec

    def named(self, name):
        with self._lock:
            return [s for s in self.spans if s.name == name]


class _LoggedSpan:
    def __init__(self, name, meta):
        self.name, self.meta, self.closed = name, dict(meta), False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.closed = True

    def set_metadata(self, **meta):
        self.meta.update(meta)


def test_every_queue_wait_span_closes(monkeypatch):
    """One request served, one shed at its deadline, one refused as
    overloaded: each opens a queue-wait span at admission, and each span
    closes."""
    import repro.serving.server as server_mod
    log = SpanLog()
    monkeypatch.setattr(server_mod, "span", log)
    stub = StubService(blocking=True)
    server = _start(stub, max_queue=1, coalesce_limit=1)
    host, port = server.address
    outcome = {}

    def call(tag, deadline_ms, seed):
        try:
            with CostModelClient(host, port, retries=0) as c:
                outcome[tag] = c.predict_many(
                    [random_kernel(5, seed=seed)], deadline_ms=deadline_ms)
        except ClientError as e:
            outcome[tag] = e

    served = threading.Thread(target=call, args=("served", 60_000, 0))
    served.start()
    assert stub.started.wait(timeout=JOIN_S)        # the worker is busy
    late = threading.Thread(target=call, args=("late", 1.0, 1))
    late.start()
    poll = threading.Event()
    for _ in range(2000):                           # parked in the queue
        if server._queue.qsize() >= 1:
            break
        poll.wait(0.005)
    assert server._queue.qsize() >= 1
    call("refused", 60_000, 2)                      # the queue is full
    poll.wait(0.01)                                 # > the 1 ms deadline
    stub.gate.set()
    served.join(timeout=JOIN_S)
    late.join(timeout=JOIN_S)
    server.stop()
    assert outcome["served"].shape == (1,)
    assert isinstance(outcome["late"], DeadlineExceeded)
    assert isinstance(outcome["refused"], Overloaded)
    waits = log.named("repro.serve.queue_wait")
    assert sorted(s.meta["seq"] for s in waits) == [1, 2, 3]
    assert all(s.closed for s in waits)
    assert len(log.named("repro.serve.decode")) == 3
    assert all(s.closed for s in log.spans)


def test_served_requests_move_latency_percentiles(world):
    svc = _service(world)
    assert svc.stats().latency_p50_ms == 0.0
    server = _start(svc)
    with CostModelClient(*server.address) as c:
        c.predict_many(world["graphs"][:2], deadline_ms=60_000)
        st = c.stats()
    server.stop()
    assert svc.stats().latency_p50_ms > 0.0
    assert st["service"]["latency_p99_ms"] >= \
        st["service"]["latency_p50_ms"] > 0.0
