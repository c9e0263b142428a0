"""Numeric parity of the Pallas flash attention kernels (fwd + bwd)
against the XLA reference path. Off-TPU these run the kernels in pallas
interpret mode, so CI covers the exact kernel code (small shapes — the
interpreter is slow)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import xla_attention
from ray_tpu.ops.pallas.flash_attention import flash_attention


def _make_qkv(b, s, h, hk, d, seed=0, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, s, h, d), dtype)
    k = jax.random.normal(k2, (b, s, hk, d), dtype)
    v = jax.random.normal(k3, (b, s, hk, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_fwd_parity(causal):
    q, k, v = _make_qkv(1, 256, 2, 2, 64)
    out_flash = flash_attention(q, k, v, causal, None, 128, 128)
    out_ref = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out_flash, out_ref, atol=2e-5, rtol=2e-5)


def test_flash_fwd_parity_gqa():
    q, k, v = _make_qkv(1, 256, 4, 2, 64, seed=1)
    out_flash = flash_attention(q, k, v, True, None, 128, 128)
    out_ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out_flash, out_ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_bwd_parity(causal):
    q, k, v = _make_qkv(1, 256, 2, 2, 64, seed=2)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal, None, 128, 128)
        return (out * jnp.cos(out)).sum()

    def loss_ref(q, k, v):
        out = xla_attention(q, k, v, causal=causal)
        return (out * jnp.cos(out)).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_bwd_parity_gqa():
    q, k, v = _make_qkv(1, 256, 4, 2, 64, seed=3)

    def loss(attn):
        def f(q, k, v):
            out = attn(q, k, v)
            return (out ** 2).sum()
        return f

    flash = loss(lambda q, k, v: flash_attention(q, k, v, True, None,
                                                 128, 128))
    ref = loss(lambda q, k, v: xla_attention(q, k, v, causal=True))
    gf = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("bq,bk", [(64, 128), (128, 64), (32, 256)])
def test_flash_parity_rectangular_blocks(bq, bk):
    """Non-square tiles (wider K blocks feed the MXU a longer
    contraction per softmax rescale) must stay exact in fwd and bwd."""
    q, k, v = _make_qkv(1, 256, 2, 2, 64, seed=5)

    out = flash_attention(q, k, v, True, None, bq, bk)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def loss(fn):
        def f(q, k, v):
            return (fn(q, k, v) * jnp.arange(
                q.shape[1], dtype=q.dtype)[None, :, None, None]).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    gf = loss(lambda q, k, v: flash_attention(q, k, v, True, None, bq, bk))
    gr = loss(lambda q, k, v: xla_attention(q, k, v, causal=True))
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)
