#!/usr/bin/env python3
"""The control of each cell's check: the plain reference, computed one
precision below the configuration's (bfloat16 for float32), put in the
program's place and compared with the float32 reference by the check's own
numbers. A sound check reads the control as not correct.

    python3 benchmarks/chip/control.py --workload serve-tile-search \
        --seeds 11 12 13

It reads the same kind of sample as a run does, at the cell's own sizes:
a serve cell's first requests of every client's stream, a train cell's
first three steps over as many shards as the cell has chips. With
`--program` the sample also goes through the program (a serve cell's
service, a train cell's trainer on the cell's chips), so that one process
reads the program's seeds and the control's. One JSON line per seed on
standard output. The benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def serve_control(cfg: dict, mix: dict, seed: int,
                  program: bool = False) -> dict:
    """The control's numbers on a serve cell's sample; with `program`, the
    program's beside them: its service, with the jitted predict function
    the server drives, scoring the same requests one flush each."""
    import jax.numpy as jnp
    import numpy as np

    import common
    import reference
    import serve
    import traffic
    arch = []
    if mix.get("arch_blocks"):
        from repro.core.hlo_import import import_arch_program
        arch = [import_arch_program(a).to_dict() for a in mix["arch_blocks"]]
    norm_gen = traffic.generator(mix, seed, "norm", 0, arch).requests()
    norm = reference.fit_normalizer([
        reference.featurize(g.to_dict())
        for _ in range(mix["norm_requests"]) for g in next(norm_gen)[1]])
    per_client = max(1, mix["check_requests"] // mix["clients"])
    requests = []
    for c in range(mix["clients"]):
        gen = traffic.generator(mix, seed, "run", c, arch).requests()
        requests += [next(gen)[1] for _ in range(per_client)]
    feats = [reference.featurize(g.to_dict()) for r in requests for g in r]
    params = common.make_params(cfg, seed)
    ref = reference.score(params, cfg["model"], feats, norm, jnp.float32)
    low = reference.score(params, cfg["model"], feats, norm, jnp.bfloat16)
    out = dict(serve.score_gaps(low, ref), graphs=len(feats))
    if program:
        from repro.core.evaluate import make_predict_fn
        from repro.serving import CostModelService
        mc = common.model_config(cfg)
        svc = CostModelService(
            params, mc, common.normalizer(norm),
            node_budget=mix["node_budget"],
            cache_capacity=mix["cache_capacity"],
            predict_fn=make_predict_fn(mc))
        got = np.concatenate([np.asarray(svc.predict_many(r), np.float64)
                              for r in requests])
        out["program"] = serve.score_gaps(got, ref)
    return out


def train_control(cfg: dict, mix: dict, seed: int, dp: int = 1,
                  program: bool = False) -> dict:
    """The control's numbers on a train cell's first three steps over `dp`
    shards; with `program`, the program's beside them: the cell's trainer
    at dp over the first `dp` devices, driving the same three steps
    through its own loop as a run does before its window."""
    import jax
    import jax.numpy as jnp

    import traffic
    import train
    opt = dict(mix["optim"])
    if program:
        gen, base, norm, _, trainer = train.build(cfg, mix, seed,
                                                  jax.devices()[:dp], {})
        prog = train.first_steps(trainer, cfg, seed, opt["b1"])
        del trainer
    else:
        gen = traffic.kind(mix)
        base, norm = gen.corpus(cfg, mix, seed)
    ref = train.reference_steps(cfg, norm, gen, base, seed, 3, jnp.float32,
                                opt, dp)
    low = train.reference_steps(cfg, norm, gen, base, seed, 3, jnp.bfloat16,
                                opt, dp)
    out = train.compare_steps(low, ref)
    if program:
        out["program"] = train.compare_steps(prog, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true",
                    help="the program's readings beside the control's, "
                         "on the same sample")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import common
    import run
    import traffic
    common.enable_compile_cache(ROOT)
    _, wl, cfg, mix = run.load_cell(ROOT, args.workload)
    for seed in args.seeds:
        if traffic.kind(mix).LOOP == "serve":
            out = serve_control(cfg, mix, seed, args.program)
        else:
            out = train_control(cfg, mix, seed, wl["chips"], args.program)
        print(json.dumps(dict(out, workload=args.workload, seed=seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
