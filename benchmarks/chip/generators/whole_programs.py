"""Whole-program scoring as serve traffic.

Every request is one distinct program of a log-uniform target size,
stitched from language-model blocks and synthetic-family blocks the way
`repro.data.synthetic.whole_model_graph` stitches blocks (the previous
block's root, summed to a scalar, is broadcast into the next block's first
parameter).
"""
from __future__ import annotations

import math
from itertools import count

import numpy as np

import traffic

LOOP = "serve"


class Generator:
    def __init__(self, mix: dict, seed: int, role: str, client: int,
                 arch_blocks=()):
        from repro.core.graph import KernelGraph
        self.mix, self.seed, self.client = mix, int(seed), int(client)
        self.role = traffic.ROLES[role]
        self.arch = [KernelGraph.from_dict(b) for b in arch_blocks]

    def build(self, i: int):
        from repro.core import opset
        from repro.core.graph import KernelGraph, Node
        from repro.data.synthetic import FAMILIES
        mix = self.mix
        rng = np.random.default_rng(traffic.seq(self.seed, self.role,
                                                self.client, i))
        lo, hi = math.log(mix["min_nodes"]), math.log(mix["max_nodes"])
        target = int(math.exp(rng.uniform(lo, hi)))
        label = f"whole_{self.role}_{self.client}_{i}"
        fams = list(FAMILIES)
        nodes: list = []
        prev_out = None
        bi = 0
        while len(nodes) < target:
            if self.arch and rng.random() < mix["arch_share"]:
                block = self.arch[int(rng.integers(len(self.arch)))]
            else:
                fam = fams[int(rng.integers(len(fams)))]
                block = FAMILIES[fam](rng, f"{label}_blk{bi}")
            off = len(nodes)
            if prev_out is not None:
                prev = nodes[prev_out]
                nodes.append(Node(opset.REDUCE_SUM, (1,), prev.dtype_bytes,
                                  (prev_out,), reduced_dims=prev.shape))
                off += 1
            bridged = prev_out is None
            for n in block.nodes:
                if not bridged and n.op is opset.PARAMETER:
                    nodes.append(Node(opset.BROADCAST, n.shape,
                                      n.dtype_bytes, (off - 1,)))
                    bridged = True
                    continue
                nodes.append(Node(n.op, n.shape, n.dtype_bytes,
                                  tuple(j + off for j in n.inputs), False,
                                  n.contract_dim, n.filter_size,
                                  n.reduced_dims))
            for j in range(len(nodes) - 1, -1, -1):
                if nodes[j].op is not opset.PARAMETER:
                    prev_out = j
                    break
            bi += 1
        # as `_Builder.build`: a node nothing consumes is an output
        consumed = {j for n in nodes for j in n.inputs}
        nodes = [Node(n.op, n.shape, n.dtype_bytes, n.inputs,
                      j not in consumed and n.op is not opset.PARAMETER,
                      n.contract_dim, n.filter_size, n.reduced_dims)
                 for j, n in enumerate(nodes)]
        return KernelGraph(nodes, program=label, name=label)

    def requests(self):
        for i in count():
            yield (i, [self.build(i)])

    def rebuild(self, desc) -> list:
        return [self.build(desc)]
