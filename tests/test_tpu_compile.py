"""Compiles for a described TPU v5e chip: the TPU compiler is installed
with JAX, and it compiles for a topology that is described, not attached
(nothing runs). These catch what interpret mode cannot — block shapes the
chip refuses, kernels over the VMEM limit — at the widths the model runs
at: the Pallas kernels and the sparse predict function of
`chip_smoke.py`'s model.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports every test file."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("w_dtype", [jnp.float32, jnp.int8],
                         ids=["f32", "int8"])
def test_segment_aggregate_compiles_for_v5e(one_chip, w_dtype):
    from repro.kernels.segment_aggregate.ops import segment_aggregate
    M, E, D = 512, 2048, 192
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    hlo = _hlo(segment_aggregate,
               s((M, D), jnp.float32), s((D, D), w_dtype),
               s((1, D), jnp.float32), s((E,), jnp.int32),
               s((E,), jnp.int32), s((E,), jnp.float32),
               s((M,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_graph_aggregate_compiles_for_v5e(one_chip):
    from repro.kernels.graph_aggregate.ops import graph_aggregate
    B, N, D = 32, 64, 192
    s = lambda shape: _spec(one_chip, shape, jnp.float32)
    hlo = _hlo(graph_aggregate, s((B, N, N)), s((B, N, D)), s((D, D)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["segment_sum", "pallas"])
def test_sparse_predict_compiles_for_v5e(one_chip, use_pallas, monkeypatch):
    """The served predict function of chip_smoke.py's model, at the
    largest pack its service flushes (the 8 × max_nodes node budget)."""
    # the kernel call sites ask the default backend, which is the CPU
    # here; compiling for the chip, they must take the chip's branch
    monkeypatch.setattr("repro.kernels.interpret_mode", lambda: False)
    sys.path.insert(0, ROOT)
    import chip_smoke
    from repro.core.evaluate import make_predict_fn
    from repro.core.model import CostModelConfig, cost_model_init
    from repro.data.batching import BucketSpec, encode_packed
    from repro.data.synthetic import random_kernel

    cfg = CostModelConfig.from_dict(dict(
        chip_smoke.model_config().to_dict(),
        use_pallas_aggregate=use_pallas))
    budget = 8 * cfg.max_nodes
    spec = BucketSpec(node_capacity=budget, edge_capacity=2 * budget,
                      graph_capacity=64, reduce_capacity=cfg.max_nodes)
    batch = encode_packed([random_kernel(12, seed=i) for i in range(4)],
                          spec=spec)
    params = jax.eval_shape(lambda: cost_model_init(jax.random.key(0), cfg))
    on_chip = lambda x: _spec(one_chip, x.shape, x.dtype)
    hlo = make_predict_fn(cfg).lower(
        jax.tree_util.tree_map(on_chip, params),
        jax.tree_util.tree_map(on_chip, batch)).compile().as_text()
    assert ("tpu_custom_call" in hlo) == use_pallas
