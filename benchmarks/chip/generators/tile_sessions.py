"""The paper's tile-size autotuner as serve traffic.

A session tunes one program drawn uniformly from a seed-made pool:
`rounds` passes over its kernels in shuffled order, each request one
kernel's random `subset` of its tile candidates. The session logic is that
of `repro.serving.replay.build_tile_replay`, copied here so that the
traffic stays fixed when the program changes.
"""
from __future__ import annotations

from itertools import count

import numpy as np

import traffic

LOOP = "serve"


class Generator:
    def __init__(self, mix: dict, seed: int, role: str, client: int,
                 arch_blocks=()):
        from repro.data.synthetic import corpus_plan
        self.mix, self.seed, self.client = mix, int(seed), int(client)
        self.role = traffic.ROLES[role]
        # each role draws from a pool of its own
        self.pool_seed = int(traffic.seq(seed, self.role).generate_state(1)[0])
        self.plan = corpus_plan(mix["pool_programs"])

    def program(self, idx: int) -> list:
        """Tunable kernels of pool program `idx`: [(kernel, tiles)]."""
        from repro.data.fusion import apply_fusion, default_fusion
        from repro.data.synthetic import generate_program
        from repro.data.tile_dataset import enumerate_tiles
        fam, pidx = self.plan[idx]
        prog = generate_program(fam, pidx, self.pool_seed)
        out = []
        for k in apply_fusion(prog, default_fusion(prog)):
            tiles = enumerate_tiles(k, self.mix["max_configs"])
            if len(tiles) >= 2:
                k.structural_digest()    # memoized; tile variants share it
                out.append((k, tiles))
        return out

    def requests(self):
        """Endless (descriptor, graphs) stream of this client's sessions."""
        mix = self.mix
        for s in count():
            rng = np.random.default_rng(traffic.seq(self.seed, self.role,
                                                    self.client, s))
            pidx = int(rng.integers(mix["pool_programs"]))
            kernels = self.program(pidx)
            for _ in range(mix["rounds"]):
                for ki in rng.permutation(len(kernels)):
                    k, tiles = kernels[int(ki)]
                    n = max(int(round(mix["subset"] * len(tiles))), 1)
                    chosen = [int(t) for t in
                              rng.choice(len(tiles), size=n, replace=False)]
                    yield ((pidx, int(ki), chosen),
                           [k.with_tile(tiles[t]) for t in chosen])

    def rebuild(self, desc) -> list:
        pidx, ki, chosen = desc
        k, tiles = self.program(pidx)[ki]
        return [k.with_tile(tiles[t]) for t in chosen]
