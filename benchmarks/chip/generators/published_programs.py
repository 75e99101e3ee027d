"""Whole programs of one architecture at its published widths, as serve
traffic for the segmented path.

Every request is one distinct program: the mix's one architecture block
(`arch_blocks[0]`, a `<arch>:published` import, whole) with seeded
synthetic-family blocks stitched before and after it that add between
`extra_min` and `extra_max` of its nodes, so that no two requests hash
alike. Blocks are bridged as `whole_programs` bridges them: the previous
block's root, summed to a scalar, is broadcast into the next block's first
parameter.
"""
from __future__ import annotations

from itertools import count

import numpy as np

import traffic

LOOP = "serve_segmented"


class Generator:
    def __init__(self, mix: dict, seed: int, role: str, client: int,
                 arch_blocks=()):
        from repro.core.graph import KernelGraph
        if len(arch_blocks) != 1:
            raise ValueError("published_programs takes one architecture "
                             f"block, got {len(arch_blocks)}")
        self.mix, self.seed, self.client = mix, int(seed), int(client)
        self.role = traffic.ROLES[role]
        self.arch = KernelGraph.from_dict(arch_blocks[0])

    def build(self, i: int):
        from repro.core import opset
        from repro.core.graph import KernelGraph, Node
        from repro.data.synthetic import FAMILIES
        mix = self.mix
        rng = np.random.default_rng(traffic.seq(self.seed, self.role,
                                                self.client, i))
        extra = int(self.arch.num_nodes
                    * rng.uniform(mix["extra_min"], mix["extra_max"]))
        before = int(extra * rng.uniform())
        label = f"published_{self.role}_{self.client}_{i}"
        fams = list(FAMILIES)
        nodes: list = []
        prev_out = None

        def stitch(block):
            nonlocal prev_out
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
            prev_out = next(j for j in range(len(nodes) - 1, -1, -1)
                            if nodes[j].op is not opset.PARAMETER)

        def synthetic(upto: int, bi: int) -> int:
            while len(nodes) < upto:
                fam = fams[int(rng.integers(len(fams)))]
                stitch(FAMILIES[fam](rng, f"{label}_blk{bi}"))
                bi += 1
            return bi

        bi = synthetic(before, 0)
        stitch(self.arch)
        synthetic(extra + self.arch.num_nodes, bi)
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
