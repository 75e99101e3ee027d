"""Distribution tests that need >1 device: run in subprocesses with
--xla_force_host_platform_device_count (the main test process must keep
the real single-device view, per the assignment)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


@pytest.mark.slow
def test_smoke_archs_lower_on_mesh():
    """Every arch × {train, prefill, decode, long-decode} lowers+compiles on
    a 4×2 host mesh with the production partition rules."""
    out = _run("""
        import jax
        from jax.sharding import AxisType
        from repro.models import registry
        from repro.models.config import ShapeSpec
        from repro.launch.lowering import lower_cell
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        shapes = [ShapeSpec("t", 64, 8, "train"),
                  ShapeSpec("p", 64, 8, "prefill"),
                  ShapeSpec("d", 64, 8, "decode"),
                  ShapeSpec("l", 64, 1, "decode")]
        n = 0
        for arch in registry.list_archs():
            cfg = registry.get_smoke_config(arch)
            for shape in shapes:
                cell = lower_cell(arch, cfg, shape, mesh, "test")
                assert cell.cost_analysis.get("flops", 0) > 0, (arch, shape)
                n += 1
        print("CELLS", n)
    """)
    assert "CELLS 40" in out


@pytest.mark.slow
def test_multipod_mesh_smoke():
    """(pod, data, model) mesh lowers a train step; pod axis shards batch."""
    out = _run("""
        import jax
        from jax.sharding import AxisType
        from repro.models import registry
        from repro.models.config import ShapeSpec
        from repro.launch.lowering import lower_cell
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        cfg = registry.get_smoke_config("yi-9b")
        cell = lower_cell("yi-9b", cfg, ShapeSpec("t", 64, 8, "train"),
                          mesh, "multipod")
        coll = {k: v for k, v in cell.collective_bytes.items()
                if k != "_counts"}
        assert cell.cost_analysis["flops"] > 0
        assert sum(coll.values()) > 0   # gradient reduction crosses pods
        print("MULTIPOD OK", sorted(coll))
    """)
    assert "MULTIPOD OK" in out


@pytest.mark.slow
def test_data_parallel_training_equivalence():
    """Cost-model train step at dp=4 on four devices: its first-step loss
    is the mean of the first-step losses of four dp=1 runs, each on one
    device's record shard (dropout off, so the shards' losses depend only
    on their batches)."""
    out = _run("""
        import jax, numpy as np
        from repro.core.model import CostModelConfig
        from repro.core.simulator import TPUSimulator
        from repro.data.sampler import TileBatchSampler
        from repro.data.synthetic import generate_corpus
        from repro.data.tile_dataset import build_tile_dataset
        from repro.training.trainer import CostModelTrainer, TrainerConfig
        from repro.training.optim import AdamWConfig

        progs = generate_corpus(4, seed=0)
        tds = build_tile_dataset(progs, TPUSimulator(),
                                 max_configs_per_kernel=4)
        from repro.data.tile_dataset import fit_tile_normalizer
        norm = fit_tile_normalizer(tds.records)
        sampler = TileBatchSampler(tds.records, norm, kernels_per_batch=2,
                                   configs_per_kernel=4, max_nodes=32)
        mc = CostModelConfig(hidden_dim=16, opcode_embed_dim=4, max_nodes=32,
                             reduction="per_node", gnn_layers=1,
                             node_final_layers=1, dropout=0.0)

        def first_loss(dp, s):
            tc = TrainerConfig(task="tile", steps=1, ckpt_every=0,
                               log_every=1, dp=dp,
                               optim=AdamWConfig(lr=1e-3))
            tr = CostModelTrainer(mc, tc, s)
            assert tr.mesh.shape["data"] == dp
            return tr.run(resume=False)["loss"]

        dp4 = first_loss(4, sampler)
        shards = [first_loss(1, sampler.with_host(d, 4)) for d in range(4)]
        assert len(set(shards)) == 4, shards      # four distinct batches
        np.testing.assert_allclose(dp4, np.mean(shards), rtol=1e-6)
        print("DP EQUIV", dp4, shards)
    """)
    assert "DP EQUIV" in out


@pytest.mark.slow
def test_compressed_allreduce_multidevice():
    """int8 error-feedback all-reduce across 4 devices ≈ exact mean."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.sharding.context import shard_map_nocheck
        from repro.training.compression import compressed_allreduce

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        g = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.0

        def f(g_local):
            ef = {"g": jnp.zeros_like(g_local[0])}
            red, _ = compressed_allreduce({"g": g_local[0]}, ef, "data")
            return red["g"][None]

        red = shard_map_nocheck(f, mesh, in_specs=P("data"),
                                out_specs=P("data"))(g)
        expect = jnp.mean(g, axis=0)
        err = float(jnp.max(jnp.abs(red[0] - expect)))
        scale = float(jnp.max(jnp.abs(g))) / 127.0
        assert err <= scale + 1e-6, (err, scale)
        print("COMPRESSED OK", err)
    """)
    assert "COMPRESSED OK" in out


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    """GPipe pipeline over 4 stages == sequential layer application."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.training.pipeline import pipeline_apply, \
            pipeline_stage_split

        mesh = jax.make_mesh((4,), ("stage",),
                             axis_types=(AxisType.Auto,))
        L, D, M, mb = 8, 16, 6, 2
        key = jax.random.key(0)
        Ws = jax.random.normal(key, (L, D, D)) * 0.3

        def layer(w, x):
            return jnp.tanh(x @ w)

        def stage_fn(stage_params, x):
            def body(h, w):
                return layer(w, h), None
            h, _ = jax.lax.scan(body, x, stage_params)
            return h

        x = jax.random.normal(jax.random.key(1), (M, mb, D))
        stage_params = pipeline_stage_split(Ws, 4)
        y_pipe = pipeline_apply(stage_fn, stage_params, x, mesh=mesh,
                                axis="stage")
        y_seq = x
        for i in range(L):
            y_seq = layer(Ws[i], y_seq)
        err = float(jnp.max(jnp.abs(y_pipe - y_seq)))
        assert err < 1e-5, err
        print("PIPELINE OK", err)
    """, devices=4)
    assert "PIPELINE OK" in out


@pytest.mark.slow
def test_elastic_checkpoint_remesh():
    """Checkpoint written under a 2-device mesh restores onto 8 devices
    with different shardings (elastic scaling)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.training.checkpoint import restore_checkpoint, \
            save_checkpoint

        state = {"w": jnp.arange(64.0).reshape(8, 8)}
        d = tempfile.mkdtemp()
        m2 = Mesh(np.array(jax.devices()[:2]), ("data",))
        state2 = jax.device_put(state["w"],
                                NamedSharding(m2, P("data", None)))
        save_checkpoint(d, 1, {"w": state2})
        m8 = Mesh(np.array(jax.devices()[:8]), ("data",))
        sh = {"w": NamedSharding(m8, P(None, "data"))}
        restored, step, _ = restore_checkpoint(d, state, shardings=sh)
        assert restored["w"].sharding == sh["w"]
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(state["w"]))
        print("ELASTIC OK")
    """)
    assert "ELASTIC OK" in out
