#!/usr/bin/env python3
"""The control of a segmented serve cell's check (`control.py` for the
cells whose traffic the `serve_segmented` loop drives): the segmented
reference computed in bfloat16, put in the program's place and compared
with the float32 one by the check's own numbers. A sound check reads the
control as not correct.

    python3 benchmarks/chip/control_segmented.py \
        --workload serve-dsv3-whole-program --seeds 11 12 13 --program

It reads the first requests of every client's stream, at the cell's
sizes; with `--program` it also scores them through the program's service,
so one process reads the program's seeds and the control's. One JSON line
per seed on standard output. The benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


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
    import serve_segmented
    common.enable_compile_cache(ROOT)
    _, _, cfg, mix = run.load_cell(ROOT, args.workload)
    for seed in args.seeds:
        out = serve_segmented.control(cfg, mix, seed, args.program)
        print(json.dumps(dict(out, workload=args.workload, seed=seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
