"""Training-substrate tests: optimizer, checkpoints (atomic + elastic),
compression (error feedback), trainer resume-reproducibility."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.features import fit_normalizer
from repro.core.model import CostModelConfig
from repro.core.simulator import TPUSimulator
from repro.data.sampler import TileBatchSampler
from repro.data.synthetic import generate_corpus
from repro.data.tile_dataset import build_tile_dataset
from repro.training.adafactor import adafactor_init, adafactor_update
from repro.training.checkpoint import (
    latest_step,
    list_steps,
    restore_checkpoint,
    save_checkpoint,
)
from repro.training.compression import (
    compress_int8,
    compressed_allreduce,
    decompress_int8,
)
from repro.training.optim import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    global_norm,
    schedule_lr,
)
from repro.training.trainer import CostModelTrainer, TrainerConfig


# ---------------------------------------------------------------- optimizer
def test_adamw_reduces_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw_init(params)
    cfg = AdamWConfig(lr=0.1, schedule="constant", grad_clip_norm=None)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(params, grads, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_schedules_and_clip():
    cfg = AdamWConfig(lr=1.0, schedule="exponential", lr_decay=0.5,
                      decay_every=10, warmup_steps=5)
    assert float(schedule_lr(cfg, jnp.asarray(0))) == pytest.approx(0.0)
    assert float(schedule_lr(cfg, jnp.asarray(10))) == pytest.approx(0.5)
    tree = {"a": jnp.ones((4,)) * 3.0}
    clipped, gn = clip_by_global_norm(tree, 1.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    assert float(gn) == pytest.approx(6.0)


def test_adafactor_reduces_quadratic_and_memory_shape():
    params = {"w": jnp.ones((8, 16)) * 3.0, "b": jnp.ones((16,))}
    state = adafactor_init(params)
    # factored state is O(n+m), not O(nm)
    assert state["factored"]["w"]["v_row"].shape == (8,)
    assert state["factored"]["w"]["v_col"].shape == (16,)
    for _ in range(300):
        grads = jax.tree_util.tree_map(lambda p: 2 * p, params)
        params, state, _ = adafactor_update(params, grads, state, lr=0.05)
    assert float(jnp.abs(params["w"]).max()) < 0.3


# -------------------------------------------------------------- checkpoints
def test_checkpoint_roundtrip_and_retention(tmp_path):
    d = str(tmp_path / "ck")
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
             "step": jnp.asarray(3)}
    for s in (1, 2, 3, 4):
        save_checkpoint(d, s, state, keep=2)
    assert list_steps(d) == [3, 4]
    restored, step, meta = restore_checkpoint(d, state)
    assert step == 4
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(state["params"]["w"]))


def test_checkpoint_atomicity_ignores_partial(tmp_path):
    d = str(tmp_path / "ck")
    state = {"w": jnp.ones((2,))}
    save_checkpoint(d, 1, state)
    # simulate a crashed writer: partial dir without manifest
    os.makedirs(os.path.join(d, "step_00000002"))
    assert latest_step(d) == 1
    restored, step, _ = restore_checkpoint(d, state)
    assert step == 1


def test_checkpoint_shape_mismatch_raises(tmp_path):
    d = str(tmp_path / "ck")
    save_checkpoint(d, 1, {"w": jnp.ones((2,))})
    with pytest.raises(ValueError, match=r"'w'.*\(2,\).*\(3,\)"):
        restore_checkpoint(d, {"w": jnp.ones((3,))})


def test_checkpoint_keep_gc(tmp_path):
    """`keep=` retention: oldest checkpoints are garbage-collected as new
    ones land, the window can grow, and keep >= count keeps everything."""
    d = str(tmp_path / "ck")
    state = {"w": jnp.ones((2,))}
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(d, s, state, keep=2)
    assert list_steps(d) == [4, 5]
    # directories of GC'd steps are actually gone, not just unlisted
    assert sorted(n for n in os.listdir(d) if n.startswith("step_")) == \
        ["step_00000004", "step_00000005"]
    save_checkpoint(d, 6, state, keep=10)      # widen: nothing collected
    assert list_steps(d) == [4, 5, 6]
    save_checkpoint(d, 7, state, keep=1)       # shrink: only the newest
    assert list_steps(d) == [7]


def test_checkpoint_keep_ignores_partial_dirs(tmp_path):
    """A crashed writer's manifest-less dir must not consume a retention
    slot (it is invisible to list_steps) nor survive as clutter forever —
    GC only counts *complete* checkpoints."""
    d = str(tmp_path / "ck")
    state = {"w": jnp.ones((2,))}
    save_checkpoint(d, 1, state, keep=2)
    os.makedirs(os.path.join(d, "step_00000002"))      # partial, no manifest
    save_checkpoint(d, 3, state, keep=2)
    assert list_steps(d) == [1, 3]                     # both complete kept


def test_checkpoint_restore_missing_leaf_raises_keyerror(tmp_path):
    """Restoring into a template with a leaf the checkpoint never saved
    (e.g. a model grown a parameter) fails loudly, naming the leaf."""
    d = str(tmp_path / "ck")
    save_checkpoint(d, 1, {"params": {"w": jnp.ones((2,))}})
    template = {"params": {"w": jnp.ones((2,)), "extra": jnp.ones((3,))}}
    with pytest.raises(KeyError, match="extra"):
        restore_checkpoint(d, template)


def test_checkpoint_restore_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(str(tmp_path / "nothing"), {"w": jnp.ones((2,))})


def test_checkpoint_restore_explicit_step(tmp_path):
    d = str(tmp_path / "ck")
    for s in (1, 2, 3):
        save_checkpoint(d, s, {"w": jnp.full((2,), float(s))}, keep=5)
    restored, step, _ = restore_checkpoint(d, {"w": jnp.zeros((2,))}, step=2)
    assert step == 2
    np.testing.assert_array_equal(np.asarray(restored["w"]), [2.0, 2.0])


def test_checkpoint_restore_shardings_tree_mismatch_raises(tmp_path):
    """A shardings pytree with the wrong number of leaves is rejected
    before any device_put happens."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    d = str(tmp_path / "ck")
    state = {"a": jnp.ones((2,)), "b": jnp.ones((2,))}
    save_checkpoint(d, 1, state)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="shardings"):
        restore_checkpoint(d, state,
                           shardings={"a": NamedSharding(mesh, P())})


def test_checkpoint_elastic_restore_with_shardings(tmp_path):
    """Restore onto explicit (single-device) shardings — the elastic path."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    d = str(tmp_path / "ck")
    state = {"w": jnp.arange(8.0).reshape(2, 4)}
    save_checkpoint(d, 5, state)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    sh = {"w": NamedSharding(mesh, P())}
    restored, step, _ = restore_checkpoint(d, state, shardings=sh)
    assert restored["w"].sharding == sh["w"]


# -------------------------------------------------------------- compression
@given(st.lists(st.floats(min_value=-10, max_value=10), min_size=1,
                max_size=64))
@settings(max_examples=50, deadline=None)
def test_int8_quantization_error_bound(values):
    g = jnp.asarray(values, jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(g)) / 127.0, 1e-12)
    q, err = compress_int8(g, scale)
    assert q.dtype == jnp.int8
    # error bounded by half a quantization step
    assert float(jnp.max(jnp.abs(err))) <= float(scale) * 0.5 + 1e-6
    np.testing.assert_allclose(np.asarray(decompress_int8(q, scale) + err),
                               np.asarray(g), rtol=1e-5, atol=1e-6)


def test_compressed_allreduce_error_feedback_converges():
    """With error feedback, the *accumulated* compressed gradient sum tracks
    the true sum (bias-free over time)."""
    g = jnp.asarray([0.001, -0.0005, 1.0])   # small entries vanish per-step
    ef = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    for _ in range(200):
        red, ef = compressed_allreduce({"g": g}, {"g": ef}, None)
        acc = acc + red["g"]
    np.testing.assert_allclose(np.asarray(acc), np.asarray(g * 200),
                               rtol=0.02, atol=1e-3)


# -------------------------------------------------------------- trainer
def _tiny_setup(tmp_path, steps=12, compress=False):
    progs = generate_corpus(6, seed=0)
    tds = build_tile_dataset(progs, TPUSimulator(), max_configs_per_kernel=6)
    from repro.data.tile_dataset import fit_tile_normalizer
    norm = fit_tile_normalizer(tds.records)
    sampler = TileBatchSampler(tds.records, norm, kernels_per_batch=2,
                               configs_per_kernel=4, max_nodes=48)
    mc = CostModelConfig(hidden_dim=32, opcode_embed_dim=8, max_nodes=48,
                         reduction="per_node", gnn_layers=1,
                         node_final_layers=1)
    tc = TrainerConfig(task="tile", steps=steps, ckpt_every=5, log_every=5,
                       ckpt_dir=str(tmp_path / "ck"),
                       compress_grads=compress,
                       optim=AdamWConfig(lr=3e-3))
    return mc, tc, sampler


def test_trainer_loss_decreases(tmp_path):
    mc, tc, sampler = _tiny_setup(tmp_path, steps=40)
    tc.ckpt_dir = ""
    tr = CostModelTrainer(mc, tc, sampler)
    first = None
    losses = []
    for ckpt in range(4):
        res = tr.run((ckpt + 1) * 10, resume=False)
        losses.append(res["loss"])
    assert losses[-1] < losses[0]


def test_trainer_resume_exact_reproduction(tmp_path):
    """Train 12 straight vs train 6 + restart + 6 — identical params
    (deterministic sampler + checkpointed optimizer state)."""
    mc, tc, sampler = _tiny_setup(tmp_path, steps=12)
    tr1 = CostModelTrainer(mc, tc, sampler)
    tr1.run(12, resume=False)
    w1 = jax.tree_util.tree_leaves(tr1.params)[0]

    tc2 = TrainerConfig(**{**tc.__dict__,
                           "ckpt_dir": str(tmp_path / "ck2")})
    tr2 = CostModelTrainer(mc, tc2, sampler)
    tr2.run(6, resume=False)
    del tr2
    tr3 = CostModelTrainer(mc, tc2, sampler)   # fresh process stand-in
    assert tr3.maybe_resume()
    assert tr3.step == 6                       # resumed from the checkpoint
    tr3.run(12, resume=False)
    w3 = jax.tree_util.tree_leaves(tr3.params)[0]
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w3), rtol=1e-5,
                               atol=1e-6)


def test_trainer_compressed_path_runs(tmp_path):
    mc, tc, sampler = _tiny_setup(tmp_path, steps=6, compress=True)
    tc.ckpt_dir = ""
    tr = CostModelTrainer(mc, tc, sampler)
    res = tr.run(6, resume=False)
    assert np.isfinite(res["loss"])


def test_checkpoint_cross_layout_restore_bit_exact(tmp_path):
    """A per-layer checkpoint written before the scan-over-layers refactor
    restores into a stacked template bit-exactly, and vice versa — old
    checkpoints keep loading either way (DESIGN.md §12)."""
    from repro.core import gnn as G
    d1 = str(tmp_path / "per_layer")
    d2 = str(tmp_path / "stacked")
    per_layer = G.gat_init(jax.random.key(3), 16, 3, 2)
    stacked = G.stack_params(per_layer)

    # old-world checkpoint (per-layer on disk) -> new stacked template
    save_checkpoint(d1, 1, {"params": {"gnn": per_layer}})
    like = jax.tree_util.tree_map(jnp.zeros_like, {"params": {"gnn": stacked}})
    restored, _, _ = restore_checkpoint(d1, like)
    for a, b in zip(jax.tree_util.tree_leaves(restored["params"]["gnn"]),
                    jax.tree_util.tree_leaves(stacked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # new-world checkpoint (stacked on disk) -> old per-layer template
    save_checkpoint(d2, 1, {"params": {"gnn": stacked}})
    like = jax.tree_util.tree_map(jnp.zeros_like,
                                  {"params": {"gnn": per_layer}})
    restored, _, _ = restore_checkpoint(d2, like)
    for a, b in zip(jax.tree_util.tree_leaves(restored["params"]["gnn"]),
                    jax.tree_util.tree_leaves(per_layer)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_segmented_whole_model_runs(tmp_path):
    """End-to-end: whole-model graphs -> segmented batches -> trainer loss
    is finite and checkpoints round-trip in the scan layout."""
    from repro.data.sampler import BalancedSampler
    from repro.data.synthetic import whole_model_records
    recs = whole_model_records(3, 300, seed=0)
    norm = fit_normalizer([r.kernel for r in recs])
    mcfg = CostModelConfig(hidden_dim=16, opcode_embed_dim=8,
                           reduction="column_wise", dropout=0.0,
                           adjacency="segmented", scan_layers=True,
                           max_nodes=128)
    sampler = BalancedSampler(recs, norm, batch_size=2, max_nodes=128,
                              seed=0, adjacency="segmented")
    tcfg = TrainerConfig(task="fusion", steps=2, ckpt_every=0, log_every=1,
                         ckpt_dir=str(tmp_path / "ck"))
    tr = CostModelTrainer(mcfg, tcfg, sampler)
    out = tr.run(resume=False)
    assert out["step"] == 2
    assert np.isfinite(out["loss"])


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "fixed"])
def test_compile_cache_placement(tmp_path, from_env):
    """With JAX_COMPILATION_CACHE_DIR set the persistent compile cache is
    written there, as JAX reads it; unset, it goes to the one fixed
    directory at the root of the checkout."""
    import subprocess
    import sys
    import textwrap
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from repro.launch import COMPILE_CACHE_DIR, enable_compile_cache
        used = enable_compile_cache()
        print(used)
        print(jax.config.jax_compilation_cache_dir)
        print(COMPILE_CACHE_DIR)
        if {from_env}:
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env)
    assert res.returncode == 0, res.stderr
    used, configured, fixed = res.stdout.split()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert fixed == os.path.join(root, ".jax_cache")
    want = str(tmp_path) if from_env else fixed
    assert used == configured == want
    if from_env:
        assert os.listdir(tmp_path)


def test_trainer_spans_in_a_profile(tmp_path):
    """A profile of three steps holds one `repro.train.step` span per step,
    each with its batch, inputs and dispatch spans inside it on the same
    thread, all inside one `repro.train.run`."""
    import glob

    from jax.profiler import ProfileData
    mc, tc, sampler = _tiny_setup(tmp_path, steps=3)
    tc.ckpt_dir = ""
    tr = CostModelTrainer(mc, tc, sampler)
    with jax.profiler.trace(str(tmp_path / "prof")):
        tr.run(resume=False)
    path, = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                   for ev in line.events
                   if ev.name.startswith("repro.train.")]
            if evs:
                lines.append(evs)
    assert len(lines) == 1
    evs = lines[0]
    steps = [e for e in evs if e[0] == "repro.train.step"]
    assert sorted(e[3]["step"] for e in steps) == [0, 1, 2]
    runs = [e for e in evs if e[0] == "repro.train.run"]
    assert len(runs) == 1 and runs[0][3]["steps"] == 3
    for _, s, e, _ in steps:
        assert runs[0][1] <= s and e <= runs[0][2]
        inside = sorted(n for n, cs, ce, _ in evs if s <= cs and ce <= e
                        and n != "repro.train.step")
        assert [n for n in inside if n != "repro.train.sync"] == [
            "repro.train.batch", "repro.train.dispatch",
            "repro.train.inputs"]
    assert [e[3]["graphs"] for e in evs if e[0] == "repro.train.batch"] \
        == [8, 8, 8]
