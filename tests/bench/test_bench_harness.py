"""The harness on the CPU at a small size: it refuses to run without a
TPU, finds new cells, mixes, traffic kinds and metrics by name, agrees
with the plain reference, and reads the control and every fault a cell can
have as not correct."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import faults
import tiny

import run

pytestmark = pytest.mark.timeout(900)
RUN = os.path.join(tiny.BENCH, "run.py")


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, RUN, "--workload", "serve-tile-search", "--seed",
         "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def test_exits_non_zero_without_a_tpu():
    p = _cli(tiny.REPO)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "TPU" in p.stderr


def test_exits_non_zero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(tiny.HERE, tmp_path / "tests" / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "serve-tile-search", "--seed", "3", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode != 0 and not p.stdout.strip()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("root")))


def _run(root, workload, fault=None, trace=0, seed=5):
    return run.run_cell(root, workload, seed, 1.5, trace, require_tpu=False,
                        fault=fault, t0=time.monotonic())


NEW_KIND = '''"""Requests of one random kernel each, a new kind of traffic."""
import traffic
from itertools import count

LOOP = "serve"


class Generator:
    def __init__(self, mix, seed, role, client, arch_blocks=()):
        self.mix, self.client = mix, client
        self.seed = int(traffic.seq(seed, traffic.ROLES[role],
                                    client).generate_state(1)[0])

    def rebuild(self, i):
        from repro.data.synthetic import random_kernel
        return [random_kernel(4 + (self.seed + i) % self.mix["spread"],
                              seed=self.seed + i)]

    def requests(self):
        for i in count():
            yield i, self.rebuild(i)
'''


def test_new_config_mix_kind_and_metric_are_found_by_name(root):
    """A cell added by files alone: a configuration, a new kind of traffic
    (its generator module and a mix), a per-layer metric reader and
    BENCHMARK.json entries, no harness edit."""
    import traffic
    chip = os.path.join(root, "benchmarks", "chip")
    with open(os.path.join(chip, "configs", "tile-sage-lstm.json")) as f:
        cfg = json.load(f)
    cfg["model"]["hidden_dim"] = 12
    with open(os.path.join(chip, "configs", "narrow-lstm.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(chip, "generators", "one_kernel.py"), "w") as f:
        f.write(NEW_KIND)
    with open(os.path.join(chip, "traffic", "tile-search.json")) as f:
        mix = json.load(f)
    mix.update(kind="one_kernel", clients=1, spread=9)
    traffic.GENERATORS = os.path.join(chip, "generators")
    mix["digest"] = traffic.digest(mix)
    with open(os.path.join(chip, "traffic", "one-kernel.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(chip, "metrics", "misses_seen.serve.py"),
              "w") as f:
        f.write("def read(ctx):\n    return ctx.counters['misses']\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(dict(bench["configs"][0], name="narrow-lstm",
                                 file="benchmarks/chip/configs/"
                                      "narrow-lstm.json"))
    bench["workloads"].append({"name": "serve-one-kernel",
                               "config": "narrow-lstm",
                               "traffic": "one-kernel", "chips": 1,
                               "why": "one client, one kernel a request"})
    for m in bench["end_to_end"]:
        if "served_graphs_per_s" == m["name"]:
            m["workloads"].append("serve-one-kernel")
    bench["per_layer"].append({
        "name": "misses_seen.serve", "unit": "graphs", "better": "lower",
        "source": "program_counter", "layer": "service cache",
        "moves": "served_graphs_per_s", "workloads": ["serve-one-kernel"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    out = _run(root, "serve-one-kernel", trace=1)
    assert out["correct"], out["checks"]
    assert out["counters"]["graphs_scored"] > 0
    assert out["metrics"]["misses_seen.serve"]["value"] > 0
    assert "cache_hit_rate.serve" not in out["metrics"]   # not its cell
    untraced = _run(root, "serve-one-kernel")
    assert set(untraced["metrics"]) == {"served_graphs_per_s", "setup_s"}


@pytest.mark.parametrize("workload", ["serve-tile-search",
                                      "serve-whole-program", "train-tile"])
def test_a_sound_run_is_correct(root, workload):
    out = _run(root, workload)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "counters" and "checks" in out


@pytest.mark.parametrize("workload", ["serve-tile-search",
                                      "serve-whole-program", "train-tile",
                                      "train-fusion-dp4"])
def test_the_control_fails_a_check(root, workload):
    """The control reads above a limit; the program, on the same sample,
    within every limit (a train cell's program where it runs on one
    device)."""
    import control
    import traffic
    _, wl, cfg, mix = run.load_cell(root, workload)
    if traffic.kind(mix).LOOP == "serve":
        got = control.serve_control(cfg, mix, 7, program=True)
        assert got["score_gap_p95"] > mix["limit_score_gap_p95"]
        for k, v in got["program"].items():
            assert v <= mix["limit_" + k], got
    else:
        one = wl["chips"] == 1
        got = control.train_control(cfg, mix, 7, wl["chips"], program=one)
        assert any(got[k] > v for k, v in mix["limits"].items()), got
        for k, v in mix["limits"].items():
            assert not one or got["program"][k] <= v, got


@pytest.mark.parametrize("workload", ["train-tile", "train-fusion-dp4"])
def test_the_census_bucket_is_the_timed_batch_shape(root, workload):
    """The bucket the census warms for a step is the shape of the timed
    sampler's batch of that step, at the cell's number of shards: where
    the program's rule for the shared bucket moves, this fails before a
    window compiles."""
    import traffic
    import train
    _, wl, cfg, mix = run.load_cell(root, workload)
    base, _ = traffic.kind(mix).corpus(cfg, mix, 5)
    timed = train._timed_sampler_class().for_mesh(base, wl["chips"])
    for step in range(0, 400, 40):
        g = timed.batch(step).graphs
        assert train.step_bucket(timed, step) == (
            g.opcodes.shape[1], g.edge_src.shape[1],
            g.kernel_feats.shape[1], g.gather_idx.shape[2]), step


def _alter_answer(out, batch):
    """Every score of the flush moved by 5% of its size and 1e-3."""
    return np.asarray(out) * 1.05 + 1e-3


def _alter_one_answer(out, batch):
    """The first graph's score of every flush moved by half its size and
    1e-2; the rest left as they are."""
    out = np.array(out, copy=True)
    out[0] = out[0] * 1.5 + 1e-2
    return out


@pytest.mark.parametrize("alter", [_alter_answer, _alter_one_answer],
                         ids=["every-answer", "one-answer"])
@pytest.mark.parametrize("workload", ["serve-tile-search",
                                      "serve-whole-program"])
def test_altered_answers_are_not_correct(root, workload, alter):
    out = _run(root, workload, fault={"scores": alter})
    assert not out["correct"]
    if alter is _alter_one_answer:
        assert out["checks"]["score_gap_max"]["value"] > \
            out["checks"]["score_gap_max"]["limit"], out["checks"]


@pytest.mark.parametrize("fault", [
    {"step": faults.unchanged}, {"batch": faults.half_batch},
    {"step": faults.altered_loss}],
    ids=["state-unchanged", "half-batch", "altered-loss"])
def test_a_training_fault_is_not_correct(root, fault):
    out = _run(root, "train-tile", fault=fault)
    assert not out["correct"], out["checks"]


def test_the_reference_agrees_with_the_program_on_the_cpu():
    import jax.numpy as jnp

    import common
    import reference
    from repro.serving import CostModelService
    from repro.serving.replay import build_tile_replay
    from repro.data.synthetic import whole_model_graph
    for red in ("lstm", "transformer"):
        cfg = {"model": dict(
            gnn="graphsage", reduction=red, hidden_dim=16,
            opcode_embed_dim=8, gnn_layers=3, node_final_layers=3,
            transformer_layers=1, transformer_heads=4, dropout=0.1,
            adjacency="sparse")}
        params = common.make_params(cfg, 11)
        if red == "lstm":
            graphs = [g for r in build_tile_replay(
                2, max_configs=8, seed=3).requests[:4] for g in r]
        else:
            graphs = [whole_model_graph(n, seed=n) for n in (200, 500)]
        feats = [reference.featurize(g.to_dict()) for g in graphs]
        norm = reference.fit_normalizer(feats)
        svc = CostModelService(params, common.model_config(cfg),
                               common.normalizer(norm), node_budget=1024)
        got = svc.predict_many(graphs).astype(np.float64)
        ref = reference.score(params, cfg["model"], feats, norm,
                              jnp.float32)
        assert np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2)) < 1e-5
