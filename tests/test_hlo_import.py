"""jaxpr importer tests: arbitrary jitted functions become valid cost-model
programs with faithful op/shape/contract metadata."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import opset
from repro.core.hlo_import import import_arch_program, import_jaxpr
from repro.core.simulator import TPUSimulator
from repro.data.fusion import apply_fusion, default_fusion


def test_import_simple_matmul_chain():
    def f(x, w1, w2):
        return jnp.tanh(x @ w1) @ w2

    g = import_jaxpr(f, jnp.ones((8, 16)), jnp.ones((16, 32)),
                     jnp.ones((32, 4)), name="mm")
    ops = [n.op.name for n in g.nodes]
    assert ops.count("dot") == 2
    assert "tanh" in ops
    dots = [n for n in g.nodes if n.op is opset.DOT]
    assert dots[0].shape == (8, 32) and dots[0].contract_dim == 16
    assert dots[1].shape == (8, 4) and dots[1].contract_dim == 32
    assert g.nodes[-1].is_output or any(n.is_output for n in g.nodes)


def test_import_inlines_scan_bodies():
    def f(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=3)
        return h

    g = import_jaxpr(f, jnp.ones((4, 8)), jnp.ones((8, 8)))
    assert any(n.op is opset.DOT for n in g.nodes)      # body was inlined
    assert any(n.op is opset.TANH for n in g.nodes)


def test_import_reduction_metadata():
    def f(x):
        return jnp.sum(jnp.exp(x), axis=1)

    g = import_jaxpr(f, jnp.ones((8, 64)))
    red = [n for n in g.nodes if n.op.name == "reduce-sum"]
    assert red and red[0].reduced_dims == (64,)


@pytest.mark.parametrize("arch", ["yi-9b", "granite-moe-3b-a800m",
                                  "mamba2-2.7b"])
def test_arch_programs_are_simulatable(arch):
    g = import_arch_program(arch)
    assert g.num_nodes > 100
    kernels = apply_fusion(g, default_fusion(g))
    assert len(kernels) > 5
    rt = TPUSimulator().measure_program(kernels)
    assert np.isfinite(rt) and rt > 0


def test_arch_programs_differ_across_archs():
    from repro.data.corpus import kernel_hash
    a = import_arch_program("yi-9b")
    b = import_arch_program("mamba2-2.7b")
    assert kernel_hash(a) != kernel_hash(b)


# -- published widths ---------------------------------------------------------
@pytest.fixture(scope="module")
def dsv3_published():
    """DeepSeek-V3's whole published program, and the bytes of the device
    arrays its import left alive."""
    before = {id(a) for a in jax.live_arrays()}
    g = import_arch_program("deepseek-v3-671b:published")
    new = sum(a.nbytes for a in jax.live_arrays() if id(a) not in before)
    return g, new


def test_published_import_allocates_no_parameters(dsv3_published):
    """671 B parameters traced from `jax.eval_shape` and
    `ShapeDtypeStruct` inputs: nothing of their size is made."""
    _, new_bytes = dsv3_published
    assert new_bytes < 1 << 20


def test_published_import_holds_all_61_layers(dsv3_published):
    """Every layer stack unrolled, no node cut: four RMS norms a layer (the
    block's two, MLA's query and latent ones) and the final one, and three
    top-k selections in each of the 58 MoE layers (the groups' two best
    scores, the kept groups, the experts)."""
    g, _ = dsv3_published
    ops = [n.op for n in g.nodes]
    assert ops.count(opset.RSQRT) == 4 * 61 + 1
    assert ops.count(opset.TOPK) == 3 * 58
    assert g.num_nodes > 20_000
    assert g.name == "arch_deepseek-v3-671b:published"


def test_published_import_has_the_published_widths(dsv3_published):
    """The HF config's widths appear in the program's shapes: one
    4 096-token sequence, logits over the whole vocabulary, the dense and
    expert FFNs, MLA's ranks and heads, all 256 experts' scores."""
    g, _ = dsv3_published
    shapes = {n.shape for n in g.nodes}
    dims = {d for s in shapes for d in s}
    assert (1, 4096, 129280) in shapes                # vocab_size
    assert (1, 4096, 7168) in shapes                  # hidden_size
    assert (1, 4096, 18432) in shapes                 # intermediate_size
    assert (4096, 256) in shapes                      # n_routed_experts
    assert (4096, 8) in shapes                        # num_experts_per_tok
    assert (4096, 8, 32) in shapes                    # n_group x experts
    assert {2048, 1536, 576, 128, 192} <= dims        # expert, q, kv+rope,
    #                                                   heads, q head dim
    dots = [n for n in g.nodes if n.op is opset.DOT]
    assert {7168, 1536, 512, 2048, 18432} <= {n.contract_dim for n in dots}


def test_smoke_names_keep_their_programs():
    """A registry name alone still traces the smoke config, cut at 4 096
    nodes; the published path is a separate name."""
    from repro.core.hlo_import import _MAX_NODES_PER_PROGRAM
    g = import_arch_program("deepseek-v3-671b")
    assert g.name == "arch_deepseek-v3-671b"
    assert g.num_nodes <= _MAX_NODES_PER_PROGRAM
    assert max(d for n in g.nodes for d in n.shape) < 4096


def test_published_import_cuts_whole_layers_off_the_last_stack():
    """`:<layers>` keeps the first layers: two MoE layers fewer than the
    61 published, every width the same; a cut past the last stack is
    refused."""
    g = import_arch_program("deepseek-v3-671b:published:59")
    ops = [n.op for n in g.nodes]
    assert ops.count(opset.RSQRT) == 4 * 59 + 1
    assert ops.count(opset.TOPK) == 3 * 56
    assert g.name == "arch_deepseek-v3-671b:published:59"
    assert (1, 4096, 129280) in {n.shape for n in g.nodes}
    with pytest.raises(ValueError):
        import_arch_program("deepseek-v3-671b:published:3")
