"""The Transformer reduction's attention: in key blocks with an online
softmax above `DENSE_MAX_NODES` nodes, equal to the dense form, and the
dense form itself unchanged at or under it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn import transformer as T


def _qkv(n: int, seed: int = 0):
    r = np.random.default_rng(seed)
    return [jnp.asarray(r.normal(size=(2, n, 4, 8)), jnp.float32)
            for _ in range(3)]


@pytest.mark.parametrize("n,block", [(64, 16), (50, 16), (64, 64)])
@pytest.mark.parametrize("masked", [False, True])
def test_blocked_attention_equals_dense(n, block, masked):
    """Every query of a real node reads the same, masked or not, when the
    keys fill their blocks or leave the last one short."""
    q, k, v = _qkv(n)
    mask = None
    rows = np.ones((2, n), bool)
    if masked:
        m = np.ones((2, n), np.float32)
        m[1, 37:] = 0.0
        mask, rows = jnp.asarray(m), m > 0
    want = np.asarray(T.dense_attention(q, k, v, mask))
    got = np.asarray(T.blocked_attention(q, k, v, mask, block=block))
    np.testing.assert_allclose(got[rows], want[rows], rtol=1e-5, atol=1e-6)


def test_a_masked_first_block_is_forgotten():
    """A query whose first key block is all padding still reads only its
    real keys: the later real keys' rescaling zeroes the padding."""
    q, k, v = _qkv(48, seed=1)
    m = np.ones((2, 48), np.float32)
    m[:, :16] = 0.0
    mask = jnp.asarray(m)
    want = np.asarray(T.dense_attention(q, k, v, mask))
    got = np.asarray(T.blocked_attention(q, k, v, mask, block=16))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def _mha_before(params, x, mask, num_heads):
    """`mha_apply` as it was before the blocked form."""
    from repro.nn.core import dense_apply
    B, N, D = x.shape
    H = num_heads
    hd = D // H
    q = dense_apply(params["q"], x).reshape(B, N, H, hd)
    k = dense_apply(params["k"], x).reshape(B, N, H, hd)
    v = dense_apply(params["v"], x).reshape(B, N, H, hd)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    neg = jnp.finfo(logits.dtype).min
    logits = jnp.where(mask[:, None, None, :] > 0, logits, neg)
    attn = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(B, N, D)
    return dense_apply(params["o"], out)


def test_packs_at_the_dense_limit_keep_the_dense_form_bit_for_bit():
    params = T.mha_init(jax.random.key(0), 16, 4)
    n = 128
    x = jax.random.normal(jax.random.key(1), (2, n, 16))
    mask = jnp.ones((2, n)).at[1, 90:].set(0.0)
    assert n <= T.DENSE_MAX_NODES
    now = jax.jit(lambda p, x, m: T.mha_apply(p, x, m, 4))
    before = jax.jit(lambda p, x, m: _mha_before(p, x, m, 4))
    assert str(jax.make_jaxpr(now)(params, x, mask)) == \
        str(jax.make_jaxpr(before)(params, x, mask))
    assert np.array_equal(np.asarray(now(params, x, mask)),
                          np.asarray(before(params, x, mask)))


def test_longer_sequences_attend_in_blocks(monkeypatch):
    """Past the limit the encoder takes the blocked form, chosen from N
    alone, and reads what the dense form reads."""
    params = T.encoder_init(jax.random.key(2), 16, 4, 1)
    x = jax.random.normal(jax.random.key(3), (1, 96, 16))
    mask = jnp.ones((1, 96)).at[0, 70:].set(0.0)
    dense = np.asarray(T.encoder_apply(params, x, mask, 4))
    monkeypatch.setattr(T, "DENSE_MAX_NODES", 64)
    monkeypatch.setattr(T, "KEY_BLOCK", 32)
    jaxpr = str(jax.make_jaxpr(
        lambda x: T.encoder_apply(params, x, mask, 4))(x))
    assert "scan" in jaxpr
    blocked = np.asarray(T.encoder_apply(params, x, mask, 4))
    np.testing.assert_allclose(blocked[0, :70], dense[0, :70], rtol=1e-4,
                               atol=1e-5)
