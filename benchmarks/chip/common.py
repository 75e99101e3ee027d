"""Shared yardstick of the chip benchmark: the peak table, the FLOP counts
of the cost model, the compile clock, the reduction of a profiler trace to
busy time, top device operations and attributed idle gaps, and the few
calls that build the system under test from a configuration file.

JAX and the `repro` package are imported inside the functions that need
them, so the client processes and the CPU tests stay light.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

# Published peaks per chip, keyed by `device_kind`. f32 matmuls at JAX's
# default precision run as bf16 passes on the MXU, so the bf16 peak bounds
# the f32 model too. Source: Google Cloud documentation, "TPU v5e".
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

SPAN_PREFIX = "bench."          # host spans the harness writes
WINDOW_SPAN = "bench.window"


def peak(device_kind: str) -> dict:
    """The peak row of `device_kind`; an unknown chip is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"no peak table entry for device kind "
                         f"{device_kind!r}") from None


# ----------------------------------------------------------------------------
# FLOPs the model needs for one graph of n nodes and e unique edges
# ----------------------------------------------------------------------------
NODE_FEATS, KERNEL_FEATS = 31, 15


def embed_flops(cfg: dict, n, e):
    """f1 projection and the GraphSAGE layers: per layer the two message
    transforms, the in- and out-edge sums, and f3 over the concatenation."""
    d = cfg["hidden_dim"]
    in_dim = cfg["opcode_embed_dim"] + NODE_FEATS + KERNEL_FEATS
    layer = 2 * (2 * n * d * d) + 2 * e * d + 2 * n * (3 * d) * d
    return 2 * n * in_dim * d + cfg["gnn_layers"] * layer


def node_final_flops(cfg: dict, n):
    d = cfg["hidden_dim"]
    return cfg["node_final_layers"] * 2 * n * d * d


def reduction_flops(cfg: dict, n):
    """LSTM: the input and recurrent gate matmuls per node. Transformer:
    the q, k, v, o projections, the scores and the weighted sum over all
    n² pairs, and the 4d-wide feed-forward layers."""
    d = cfg["hidden_dim"]
    if cfg["reduction"] == "lstm":
        return 2 * (2 * n * d * 4 * d)
    if cfg["reduction"] == "transformer":
        per_layer = 4 * (2 * n * d * d) + 2 * (2 * n * n * d) \
            + 2 * (2 * n * d * 4 * d)
        return cfg["transformer_layers"] * per_layer
    raise ValueError(f"no FLOP count for reduction {cfg['reduction']!r}")


def head_flops(cfg: dict):
    return 2 * cfg["hidden_dim"]


def forward_flops(cfg: dict, n, e):
    """Forward FLOPs of one graph (numpy arrays of n and e work too)."""
    return (embed_flops(cfg, n, e) + node_final_flops(cfg, n)
            + reduction_flops(cfg, n) + head_flops(cfg))


def train_flops(cfg: dict, n, e):
    """Forward and backward: three times the forward pass."""
    return 3 * forward_flops(cfg, n, e)


def pack_counts(batch):
    """Real nodes and edges of each graph slot of a packed batch, read from
    its masks: (nodes [G], edges [G]) as float64 numpy arrays."""
    import numpy as np
    gids = np.asarray(batch.graph_ids)
    nmask = np.asarray(batch.node_mask) > 0
    g = np.asarray(batch.graph_mask).shape[-1]
    nodes = np.bincount(gids[nmask], minlength=g).astype(np.float64)
    emask = np.asarray(batch.edge_mask) > 0
    src = np.asarray(batch.edge_src)[emask]
    edges = np.bincount(gids[src], minlength=g).astype(np.float64)
    return nodes, edges


# ----------------------------------------------------------------------------
# compiles
# ----------------------------------------------------------------------------
class CompileClock:
    """Counts the executables JAX builds or loads (one backend-compile
    event each, a persistent-cache hit included), the cache hits among
    them, and the seconds they took."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def executables(self) -> int:
        return self.compiles


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at `<root>/.jax_cache`, keeping
    every executable however fast it compiled. Call before importing JAX."""
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    return path


# ----------------------------------------------------------------------------
# profiler trace -> busy time, top device ops, attributed idle gaps
# ----------------------------------------------------------------------------
def load_trace(trace_dir: str) -> dict:
    """Plain intervals from the newest `.xplane.pb` under `trace_dir`:
    {"devices": {plane: {"ops": [...], "modules": [...]}},
     "spans": [...]} with each interval (name, start_ns, end_ns). Device
    operations come from a TPU plane's "XLA Ops" line, executables from its
    "XLA Modules" line, spans from the host's events named `SPAN_PREFIX*`."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out = {"devices": {}, "spans": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key] = [(ev.name, ev.start_ns, ev.end_ns)
                            for ev in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"].extend((ev.name, ev.start_ns, ev.end_ns)
                                    for ev in line.events
                                    if ev.name.startswith(SPAN_PREFIX))
    return out


def op_name(hlo: str) -> str:
    """A device op's HLO text cut to its name and result type:
    "%fusion.19 = f32[4,8192]{1,0:T(4,128)} fusion(...)" ->
    "%fusion.19 = f32[4,8192]"."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    cut = min((i for i in (rest.find("{"), rest.find(" ")) if i >= 0),
              default=len(rest))
    return f"{head} = {rest[:min(cut, 60)]}"


# collective instructions (their async halves "-start" and "-done" too)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def is_collective(hlo: str) -> bool:
    """Whether a device op's HLO text is a collective instruction, by its
    name ("%all-reduce.3 = f32[8] all-reduce(%x)"); an operand so named
    does not count."""
    return hlo.partition(" = ")[0].lstrip("%").startswith(COLLECTIVES)


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    merged: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_trace(trace: dict, *, top: int = 10) -> dict:
    """Reduce `load_trace`'s intervals over the window span.

    busy_s: the union of a device's op intervals inside the window,
    averaged over the devices that ran any op; window_s: the window span's
    length; device_ops: the `top` op names by summed device time (device
    0); idle_gaps: the idle time between busy intervals on device 0, split
    by name among the host spans that cover it, the rest "none"; module_s:
    device seconds per executable name; collective_s: the union of
    device 0's collective ops (`is_collective`) inside the window."""
    windows = [(s, e) for name, s, e in trace["spans"]
               if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    lo, hi = windows[-1]
    busy, ops, gaps, modules = [], defaultdict(float), defaultdict(float), \
        defaultdict(float)
    collective = []
    spans = [(n, s, e) for n, s, e in trace["spans"] if n != WINDOW_SPAN]
    for i, plane in enumerate(sorted(trace["devices"])):
        dev = trace["devices"][plane]
        ivs = merge(_clip([(s, e) for _, s, e in dev["ops"]], lo, hi))
        if not ivs:
            continue
        busy.append(sum(e - s for s, e in ivs))
        if i:
            continue
        for name, s, e in dev["ops"]:
            for cs, ce in _clip([(s, e)], lo, hi):
                ops[op_name(name)] += (ce - cs) / 1e9
                if is_collective(name):
                    collective.append((cs, ce))
        for name, s, e in dev["modules"]:
            for cs, ce in _clip([(s, e)], lo, hi):
                modules[name] += (ce - cs) / 1e9
        edges = [lo] + [t for iv in ivs for t in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            covered = 0.0
            for name in {n for n, _, _ in spans}:
                own = merge(_clip([(s, e) for n, s, e in spans if n == name],
                                  gs, ge))
                t = sum(e - s for s, e in own)
                if t:
                    gaps[name[len(SPAN_PREFIX):]] += t / 1e9
                    covered += t
            gaps["none"] += max(0.0, (ge - gs) - covered) / 1e9
    rank = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa: E731
                            key=lambda kv: -kv[1])[:top]
    return {"busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
            "window_s": (hi - lo) / 1e9, "devices_busy": len(busy),
            "device_ops": rank(ops), "idle_gaps": rank(gaps),
            "module_s": dict(modules),
            "collective_s": sum(e - s for s, e in merge(collective)) / 1e9}


# ----------------------------------------------------------------------------
# the system under test, built from a configuration file
# ----------------------------------------------------------------------------
def model_config(cfg: dict):
    """The program's model configuration from the file's "model" group."""
    from repro.core.model import CostModelConfig
    return CostModelConfig(**cfg["model"])


def make_params(cfg: dict, seed: int):
    """The weights, made on the device in one jitted call from the seed by
    the reference's initializer; refused unless the tree is the one the
    program's own initializer builds."""
    import jax

    import reference
    from repro.core.model import cost_model_init
    init = jax.jit(reference.init_params, static_argnums=1)
    params = init(jax.random.key(seed), reference.frozen(cfg["model"]))
    mc = model_config(cfg)
    want = jax.eval_shape(lambda: cost_model_init(jax.random.key(0), mc))
    got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), params)
    if jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want) != got:
        raise SystemExit("the reference's weight tree differs from the "
                         "program's cost_model_init tree")
    return params


def normalizer(norm: dict):
    """The program's feature normalizer holding the benchmark's min/max."""
    from repro.core.features import FeatureNormalizer
    return FeatureNormalizer(norm["node_min"], norm["node_max"],
                             norm["kernel_min"], norm["kernel_max"])


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of `devices`."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
