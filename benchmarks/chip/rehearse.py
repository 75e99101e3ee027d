#!/usr/bin/env python3
"""Compile a serve cell's largest predict buckets for a described TPU v5e,
with no chip attached, and print each compiled program's memory analysis.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py

A compile that passes here is not a chip run: it says the program fits the
device's memory and that the TPU compiler takes it, nothing about time.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# (config, [(node, edge, graph, reduce) capacities]): the largest buckets
# each serve cell's packing allows
BUCKETS = {
    "tile-sage-lstm": [(512, 1024, 256, 64), (512, 1024, 64, 128)],
    "fusion-sage-xfmr": [(8192, 16384, 1, 8192), (8192, 16384, 2, 8192),
                         (8192, 16384, 4, 4096)],
}


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import common
    import reference
    from repro.core import features as F
    from repro.core.evaluate import make_predict_fn

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for name, buckets in BUCKETS.items():
        with open(os.path.join(HERE, "configs", name + ".json")) as f:
            cfg = json.load(f)
        mc = common.model_config(cfg)
        shapes = reference.param_shapes(cfg["model"])
        params = reference._nest({
            k: jax.ShapeDtypeStruct(v, jnp.float32, sharding=one)
            for k, v in shapes.items()})
        for m, e, g, r in buckets:
            def s(shape, dt):
                return jax.ShapeDtypeStruct(shape, dt, sharding=one)
            batch = F.SparseGraphBatch(
                s((m,), np.int32), s((m, F.NODE_FEATURE_DIM), np.float32),
                s((m,), np.float32), s((m,), np.int32), s((e,), np.int32),
                s((e,), np.int32), s((e,), np.float32),
                s((g, F.KERNEL_FEATURE_DIM), np.float32),
                s((g,), np.float32), s((g, r), np.int32),
                s((g, r), np.float32))
            compiled = make_predict_fn(mc).lower(params, batch).compile()
            mem = compiled.memory_analysis()
            print(json.dumps({
                "config": name, "bucket": [m, e, g, r],
                "temp_bytes": mem.temp_size_in_bytes,
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
