"""Sequence-parallelism parity: ring attention and Ulysses vs the dense
XLA path, forward AND gradients, on the 8-device virtual CPU mesh.

These are the SP correctness gates called for by SURVEY.md §2.4 — the op
is numerically subtle (online-softmax rescaling across ring steps, GQA
expansion, causal offsets per shard)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.attention import xla_attention
from ray_tpu.ops.ring_attention import ring_attention, ulysses_attention


def _mesh(devices, n):
    return Mesh(np.array(devices[:n]), ("seq",))


def _make_qkv(b, s, h, hk, d, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, s, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, s, hk, d), jnp.float32)
    v = jax.random.normal(k3, (b, s, hk, d), jnp.float32)
    return q, k, v


def _sharded_attn(attn_fn, mesh, causal):
    fn = functools.partial(attn_fn, axis_name="seq", causal=causal)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("n_shards", [2, 4])
def test_ring_attention_fwd_parity(cpu_mesh_devices, causal, n_shards):
    mesh = _mesh(cpu_mesh_devices, n_shards)
    q, k, v = _make_qkv(2, 64, 4, 4, 16)
    out_ring = jax.jit(_sharded_attn(ring_attention, mesh, causal))(q, k, v)
    out_ref = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out_ring, out_ref, atol=2e-5, rtol=2e-5)


def test_ring_attention_fwd_parity_gqa(cpu_mesh_devices):
    mesh = _mesh(cpu_mesh_devices, 4)
    q, k, v = _make_qkv(2, 64, 4, 2, 16, seed=1)
    out_ring = jax.jit(_sharded_attn(ring_attention, mesh, True))(q, k, v)
    out_ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out_ring, out_ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_grad_parity(cpu_mesh_devices, causal):
    mesh = _mesh(cpu_mesh_devices, 4)
    q, k, v = _make_qkv(1, 64, 2, 2, 16, seed=2)
    sharded = _sharded_attn(ring_attention, mesh, causal)

    def loss_ring(q, k, v):
        return (sharded(q, k, v) ** 2).mean()

    def loss_ref(q, k, v):
        return (xla_attention(q, k, v, causal=causal) ** 2).mean()

    put = lambda x: jax.device_put(x, NamedSharding(mesh, P(None, "seq")))
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(
        put(q), put(k), put(v))
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_fwd_parity(cpu_mesh_devices, causal):
    mesh = _mesh(cpu_mesh_devices, 2)
    q, k, v = _make_qkv(2, 64, 4, 4, 16, seed=3)
    out_u = jax.jit(_sharded_attn(ulysses_attention, mesh, causal))(q, k, v)
    out_ref = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out_u, out_ref, atol=2e-5, rtol=2e-5)


def test_ulysses_grad_parity(cpu_mesh_devices):
    mesh = _mesh(cpu_mesh_devices, 2)
    q, k, v = _make_qkv(1, 64, 4, 2, 16, seed=4)
    sharded = _sharded_attn(ulysses_attention, mesh, True)

    def loss_u(q, k, v):
        return (sharded(q, k, v) ** 2).mean()

    def loss_ref(q, k, v):
        return (xla_attention(q, k, v, causal=True) ** 2).mean()

    put = lambda x: jax.device_put(x, NamedSharding(mesh, P(None, "seq")))
    g_u = jax.jit(jax.grad(loss_u, argnums=(0, 1, 2)))(
        put(q), put(k), put(v))
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_u, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4,
                                   err_msg=f"d{name} mismatch")
