"""A copy of the benchmark's data files at a size a CPU test can run: the
same cells and metrics, a model 16 wide, short warm-ups, few clients."""
from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks", "chip")
for p in (os.path.join(REPO, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL_MODEL = {"hidden_dim": 16, "opcode_embed_dim": 8}
SMALL_TRAFFIC = {
    "tile-search": {"clients": 2, "pool_programs": 64,
                    "census_requests": 200, "norm_requests": 8,
                    "check_requests": 4},
    "whole-program": {"clients": 2, "min_nodes": 48, "max_nodes": 160,
                      "arch_blocks": [], "arch_share": 0.0,
                      "node_budget": 256, "census_requests": 40,
                      "census_flushes": 200,
                      "norm_requests": 2, "check_requests": 2},
    "tile-training": {"programs": 8, "warm_steps": 64, "chunk_steps": 4},
    "fusion-training": {"programs": 8, "kernels_per_device": 2,
                        "warm_steps": 512, "chunk_steps": 4},
}
# A cell whose files are in benchmarks/chip while BENCHMARK.json holds it
# back until its configuration's widths have a published source: the CPU
# tests run it all the same, with the metric that only it reads, and it
# reports every train metric.
HELD_WORKLOADS = [
    {"name": "train-fusion-dp4", "config": "fusion-sage-xfmr",
     "traffic": "fusion-training", "chips": 4,
     "why": "CostModelTrainer.run at dp=4 on a fusion corpus: the gradient "
            "all-reduce, four sub-batches drawn and encoded on the host"}]
HELD_METRICS = [
    {"name": "allreduce_ms_per_step.train", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "trainer",
     "moves": "train_graphs_per_s", "workloads": ["train-fusion-dp4"]}]


def _dump(path: str, doc: dict) -> None:
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def make_root(tmp: str) -> str:
    """`tmp` laid out as a checkout: BENCHMARK.json with the held cells,
    benchmarks/chip's data files at the small sizes, and `src` linked to
    the repository's."""
    import traffic
    chip = os.path.join(tmp, "benchmarks", "chip")
    os.makedirs(chip)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for wl in HELD_WORKLOADS:
        bench["workloads"].append(wl)
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "train-tile" in m.get("workloads", []):
                m["workloads"].append(wl["name"])
    bench["per_layer"] += HELD_METRICS
    _dump(os.path.join(tmp, "BENCHMARK.json"), bench)
    for sub in ("configs", "traffic", "metrics", "generators"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(chip, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(tmp, "src"))
    for name in os.listdir(os.path.join(chip, "configs")):
        path = os.path.join(chip, "configs", name)
        with open(path) as f:
            cfg = json.load(f)
        cfg["model"].update(SMALL_MODEL)
        _dump(path, cfg)
    for name, small in SMALL_TRAFFIC.items():
        path = os.path.join(chip, "traffic", name + ".json")
        with open(path) as f:
            mix = json.load(f)
        mix.update(small)
        if "digest" in mix:
            mix["digest"] = traffic.digest(mix)
        _dump(path, mix)
    return tmp
