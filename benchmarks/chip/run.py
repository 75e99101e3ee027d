#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload serve-tile-search \
        --seed 7 --seconds 30 --trace 0

Everything a cell needs is found by name from `BENCHMARK.json` at the root
of the checkout: the workload's configuration file, its traffic mix
`benchmarks/chip/traffic/<traffic>.json`, the generator that mix names
(`generators/<kind>.py`) and the loop module that generator names, and
with `--trace 1` one reader
`benchmarks/chip/metrics/<metric>.py` per per-layer metric. The last line
of standard output is one JSON object: correct, attempted, failed, metrics,
device (and with --trace 1 the trace's breakdown), and last the checks,
each number compared beside its limit; the checks are also the last lines
of standard error.

It needs a TPU with as many chips as the cell asks for, and exits non-zero
without printing a result otherwise, or outside a checkout of the
repository. JAX's compilation cache lives in `.jax_cache/` at the root of
the checkout, a profile in `.bench_trace/`.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# a --trace 1 run profiles the first seconds of its window only: a trace of
# the whole window of a busy host path grows too large to read in time
TRACE_SECONDS = 10.0


class Context:
    """What one run knows: the cell, its files, the devices, the compile
    clock, the counters the loop fills, and the window's bounds."""

    def __init__(self, root, workload, seed, seconds, trace, fault, t0):
        self.root, self.workload = root, workload
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.fault, self.t0 = fault, t0
        self.counters: dict = {}
        self.window_s = float(seconds)
        self.setup_s = None
        self._span = None
        self.trace_dir = os.path.join(root, ".bench_trace", workload["name"])

    def begin_window(self, t_start: float) -> None:
        """Start the profiler (with --trace 1), wait for the window, close
        set-up and open the window's span, which `end_trace` closes
        `TRACE_SECONDS` in."""
        import jax
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # host spans and device ops; no Python call tracing, which
            # would slow the host path the cells measure many times over
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        delay = t_start - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        self.setup_s = max(t_start, time.monotonic()) - self.t0
        self.trace_end = time.monotonic() + min(self.seconds, TRACE_SECONDS)
        import common
        self._span = jax.profiler.TraceAnnotation(common.WINDOW_SPAN)
        self._span.__enter__()

    def end_trace(self) -> None:
        """Close the traced part of the window (idempotent)."""
        import jax
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            if self.trace:
                jax.profiler.stop_trace()


def load_cell(root: str, name: str):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    wl = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    with open(os.path.join(root, config["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, bench["paths"][0], "traffic",
                           wl["traffic"] + ".json")) as f:
        mix = json.load(f)
    return bench, wl, cfg, mix


def loop_of(mix: dict):
    """The loop module (`serve`, `train`, ...) that the mix's generator
    names as its `LOOP`."""
    import traffic
    return importlib.import_module(traffic.kind(mix).LOOP)


def _reader(root: str, bench: dict, name: str):
    path = os.path.join(root, bench["paths"][0], "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: int, *, require_tpu: bool = True, fault=None,
             t0: float | None = None) -> dict:
    """One run of one cell; returns the result object. `fault` breaks the
    timed path underneath for the tests' mutation checks: {"scores": f}
    alters a serve cell's predicted scores, {"batch": f} a train cell's
    batches, {"step": f} wraps its train step. `require_tpu=False` lets a
    test drive a run on the CPU."""
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise SystemExit(f"no repro package under {root}/src: run this "
                         "from a checkout of the repository")
    bench, wl, cfg, mix = load_cell(root, workload)
    for p in (os.path.join(root, "src"), HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    import common
    import traffic
    traffic.GENERATORS = os.path.join(root, bench["paths"][0], "generators")
    common.enable_compile_cache(root)
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < wl["chips"]):
        raise SystemExit(
            f"needs {wl['chips']} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s)")
    ctx = Context(root, wl, seed, seconds, trace, fault or {},
                  T0 if t0 is None else t0)
    ctx.cfg, ctx.mix, ctx.chips = cfg, mix, wl["chips"]
    ctx.devices = devices[:wl["chips"]]
    ctx.clock = common.CompileClock()
    res = loop_of(mix).run(ctx)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    metrics, breakdown = {}, None
    if not trace:
        e2e = dict(res["end_to_end"], setup_s=ctx.setup_s)
        for m in bench["end_to_end"]:
            if _applies(m, workload):
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
    else:
        reduced = common.reduce_trace(common.load_trace(ctx.trace_dir))
        ctx.trace = reduced
        ctx.peak = (common.peak(dev.device_kind) if require_tpu
                    else {"flops": 1.0})
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        moved = {m["name"] for m in bench["end_to_end"]
                 if _applies(m, workload)}
        for m in bench["per_layer"]:
            if m["moves"] not in moved or not _applies(m, workload):
                continue
            value = _reader(root, bench, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": float(v), "limit": float(lim)}
                     for k, (v, lim) in res["checks"].items()}
    out["counters"] = dict(ctx.counters, setup_s=ctx.setup_s)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_cell(ROOT, args.workload, args.seed, args.seconds, args.trace)
    checks = out.pop("checks")
    out["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
