"""Import jitted JAX functions as cost-model programs.

`import_jaxpr(fn, *args)` traces a function, walks its (flattened) jaxpr
and converts every equation into a `Node` — the same pre-fusion program
representation the synthetic generator emits. The fusion machinery and
datasets then treat imported programs exactly like synthetic ones, which is
how the 10 assigned architectures join the cost-model corpus (paper §4's
"programs from production models", here from the model zoo itself).

Control-flow primitives (scan/while/cond) are inlined one body iteration
deep — matching how the cost model sees kernels (XLA kernels never span
loop boundaries).

`import_arch_program(name)` imports an architecture two ways, chosen by
the name: `<arch>` traces its smoke config (d_model 64, float32, each
layer stack's scan inlined once, programs cut at 4 096 nodes), and
`<arch>:published` traces its published config abstractly — parameters
from `jax.eval_shape`, inputs as `ShapeDtypeStruct`s, so no weight is
ever allocated — at one 4 096-token sequence, with every layer stack
unrolled to its repeats and no node cut. `<arch>:published:<n>` keeps the
first n layers, taking whole repeats off the last stack.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.extend import core as jcore

from repro.core import opset
from repro.core.graph import KernelGraph, Node

_MAX_NODES_PER_PROGRAM = 4096
PUBLISHED = ":published"
# the published trace's one sequence: DeepSeek-V3's pre-training length
# (arXiv:2412.19437 §4.2), and every registry model's train_4k length
PUBLISHED_SEQ = 4096


def _dtype_bytes(aval) -> int:
    try:
        return max(int(np.dtype(aval.dtype).itemsize), 1)
    except Exception:                                  # noqa: BLE001
        return 4


def _shape(aval) -> tuple[int, ...]:
    shape = tuple(int(d) for d in getattr(aval, "shape", ()) or ())
    return shape[:6] if shape else (1,)


def _op_for(eqn) -> opset.OpInfo:
    return opset.JAX_PRIMITIVE_MAP.get(eqn.primitive.name, opset.CUSTOM_CALL)


def _contract_dim(eqn) -> int:
    if eqn.primitive.name != "dot_general":
        return 0
    (lc, _), _ = eqn.params["dimension_numbers"]
    lhs_aval = eqn.invars[0].aval
    d = 1
    for axis in lc:
        d *= int(lhs_aval.shape[axis])
    return d


def _conv_meta(eqn):
    if eqn.primitive.name != "conv_general_dilated":
        return 0, (0, 0)
    rhs = eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    spatial = tuple(int(rhs.shape[i]) for i in dn.rhs_spec[2:])
    in_ch = int(rhs.shape[dn.rhs_spec[1]])
    kh = spatial[0] if spatial else 1
    kw = spatial[1] if len(spatial) > 1 else 1
    return in_ch, (kh, kw)


def _reduced_dims(eqn) -> tuple[int, ...]:
    name = eqn.primitive.name
    if name.startswith("reduce_") and "axes" in eqn.params:
        in_aval = eqn.invars[0].aval
        return tuple(int(in_aval.shape[a]) for a in eqn.params["axes"])[:2]
    return ()


def jaxpr_to_program(closed_jaxpr, name: str, program: str, *,
                     max_nodes: int | None = _MAX_NODES_PER_PROGRAM
                     ) -> KernelGraph:
    """Flatten a ClosedJaxpr (inlining inner jaxprs once) to a program of
    at most `max_nodes` nodes (None: no cut)."""
    nodes: list[Node] = []
    var_to_node: dict = {}

    def add_node(n: Node):
        nodes.append(n)
        return len(nodes) - 1

    def ensure_input(v) -> int | None:
        """Map a jaxpr var/literal to a node index (parameter/constant)."""
        if isinstance(v, jcore.Literal):
            return add_node(Node(opset.CONSTANT, _shape(v.aval),
                                 _dtype_bytes(v.aval)))
        if v in var_to_node:
            return var_to_node[v]
        idx = add_node(Node(opset.PARAMETER, _shape(v.aval),
                            _dtype_bytes(v.aval)))
        var_to_node[v] = idx
        return idx

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if max_nodes is not None and len(nodes) >= max_nodes:
                return
            prim = eqn.primitive.name
            inner = None
            for key, p in eqn.params.items():
                if key == "branches" and isinstance(p, (tuple, list)) and p:
                    p = p[0]
                if isinstance(p, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                    inner = p
                    break
            if inner is not None:
                ij = getattr(inner, "jaxpr", inner)
                # bind inner invars to outer inputs where arity matches
                for iv, ov in zip(ij.invars[-len(eqn.invars):], eqn.invars):
                    if not isinstance(ov, jcore.Literal) and \
                            ov in var_to_node:
                        var_to_node[iv] = var_to_node[ov]
                walk(ij)
                for outv, innerv in zip(eqn.outvars, ij.outvars):
                    if not isinstance(innerv, jcore.Literal) and \
                            innerv in var_to_node:
                        var_to_node[outv] = var_to_node[innerv]
                continue
            op = _op_for(eqn)
            inputs = []
            for v in eqn.invars:
                idx = ensure_input(v)
                if idx is not None:
                    inputs.append(idx)
            out = eqn.outvars[0]
            contract = _contract_dim(eqn)
            filt = (0, 0)
            if prim == "conv_general_dilated":
                contract, filt = _conv_meta(eqn)
            node = Node(op, _shape(out.aval), _dtype_bytes(out.aval),
                        tuple(inputs[:3]), False, contract, filt,
                        _reduced_dims(eqn))
            idx = add_node(node)
            for ov in eqn.outvars:
                var_to_node[ov] = idx

    jaxpr = closed_jaxpr.jaxpr
    for v in jaxpr.invars:
        var_to_node[v] = add_node(
            Node(opset.PARAMETER, _shape(v.aval), _dtype_bytes(v.aval)))
    walk(jaxpr)
    # mark outputs
    for v in jaxpr.outvars:
        if not isinstance(v, jcore.Literal) and v in var_to_node:
            i = var_to_node[v]
            n = nodes[i]
            nodes[i] = Node(n.op, n.shape, n.dtype_bytes, n.inputs, True,
                            n.contract_dim, n.filter_size, n.reduced_dims)
    if not any(n.is_output for n in nodes):
        n = nodes[-1]
        nodes[-1] = Node(n.op, n.shape, n.dtype_bytes, n.inputs, True,
                         n.contract_dim, n.filter_size, n.reduced_dims)
    return KernelGraph(nodes, program=program, name=name)


def import_jaxpr(fn, *args, name: str = "imported",
                 program: str | None = None,
                 max_nodes: int | None = _MAX_NODES_PER_PROGRAM
                 ) -> KernelGraph:
    closed = jax.make_jaxpr(fn)(*args)
    return jaxpr_to_program(closed, name, program or name,
                            max_nodes=max_nodes)


def import_arch_program(arch: str, seq: int = 64, batch: int = 2
                        ) -> KernelGraph:
    """Trace one forward pass and loss of an assigned architecture into a
    cost-model program (corpus entry `arch_<name>`).

    `arch` is a registry name, traced at its smoke config with concrete
    weights at `seq` x `batch` tokens; or `<name>:published[:<layers>]`,
    traced at its published config abstractly (`_published_program`)."""
    from repro.models import registry
    from repro.models import lm
    from repro.models.config import ShapeSpec
    from repro.models.inputs import make_batch

    name, published, layers = arch.partition(PUBLISHED)
    if published:
        return _published_program(name, int(layers.lstrip(":") or 0))
    cfg = registry.get_smoke_config(arch)
    shape = ShapeSpec("import", seq, batch, "train")
    batch_data = make_batch(cfg, shape)
    params = lm.init_params(jax.random.key(0), cfg)

    def fwd(params, batch_data):
        return lm.loss_fn(params, cfg, batch_data)

    return import_jaxpr(fwd, params, batch_data,
                        name=f"arch_{arch}", program=f"arch_{arch}")


def _published_program(arch: str, layers: int = 0) -> KernelGraph:
    """The forward pass and loss of `arch`'s published config over one
    `PUBLISHED_SEQ`-token sequence, traced from abstract parameters and
    inputs (nothing allocated), each layer stack unrolled to its repeats
    (`scan_layers=False`: the model slices every layer's weights out of
    its stack, as the scanned program does each iteration), uncut. With
    `layers`, the first `layers` layers only: whole repeats of the last
    stack's pattern come off, every width stays."""
    from dataclasses import replace

    from repro.models import registry
    from repro.models import lm
    from repro.models.config import ShapeSpec
    from repro.models.inputs import input_specs

    cfg = replace(registry.get_config(arch), scan_layers=False)
    label = f"arch_{arch}{PUBLISHED}"
    if layers and layers != cfg.num_layers:
        *head, last = cfg.stacks
        cut, rest = divmod(cfg.num_layers - layers, len(last.pattern))
        if rest or not 0 < cut < last.repeats:
            raise ValueError(f"{arch}: {layers} layers is not the "
                             f"published {cfg.num_layers} less whole "
                             f"repeats of its last stack")
        cfg = replace(cfg, stacks=(*head, replace(
            last, repeats=last.repeats - cut)))
        label += f":{layers}"
    params = lm.init_abstract(cfg)
    batch = input_specs(cfg, ShapeSpec("import", PUBLISHED_SEQ, 1, "train"))

    def fwd(params, batch_data):
        return lm.loss_fn(params, cfg, batch_data)

    return import_jaxpr(fwd, params, batch, name=label, program=label,
                        max_nodes=None)
