"""Numeric parity of the Pallas kernels (flash attention fwd + bwd, the
decode kernel) against the XLA reference paths. Off-TPU these run the
kernels in pallas interpret mode, so CI covers the exact kernel code
(small shapes — the interpreter is slow)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import cached_attention, xla_attention
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


# --------------------------------------------------------- decode kernel
_BLOCK = 128
_MAX_LEN = 512
# (start, depth before the step) per row, at the edges of the kernel's
# blocks: a slot that holds no request (depth -1, as the engine marks
# it), a row one position deep, one under a block, exactly a block, one
# over, the cache's last usable position, and a row whose left padding
# ends inside the third block
_ROWS = [(0, -1), (0, 0), (3, _BLOCK - 2), (0, _BLOCK - 1), (0, _BLOCK),
         (0, _MAX_LEN - 1), (2 * _BLOCK + 5, 3 * _BLOCK + 9)]


def _decode_case(hd, group, rope, nkv=2, layers=2, seed=0):
    b = len(_ROWS)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = jnp.bfloat16
    k_cache = jax.random.normal(ks[0], (layers, b, nkv, hd, _MAX_LEN), dt)
    v_cache = jax.random.normal(ks[1], (layers, b, nkv, _MAX_LEN, hd), dt)
    q = jax.random.normal(ks[2], (b, 1, nkv * group, hd), dt)
    kk = jax.random.normal(ks[3], (b, 1, nkv, hd), dt)
    vv = jax.random.normal(ks[4], (b, 1, nkv, hd), dt)
    start = jnp.asarray([r[0] for r in _ROWS], jnp.int32)
    cache_len = jnp.asarray([r[1] for r in _ROWS], jnp.int32)
    abs_positions = cache_len[:, None]
    if rope:
        from ray_tpu.ops.rope import rope_frequencies

        cos, sin = rope_frequencies(hd, _MAX_LEN, 1e4)
        rope = (cos, sin, jnp.maximum(abs_positions - start[:, None], 0))
    return (q, kk, vv, k_cache, v_cache, jnp.int32(layers - 1), cache_len,
            abs_positions, start), dict(scale=hd ** -0.5, rope=rope or None)


def _through_kernel(monkeypatch):
    """cached_attention takes the decode kernel (interpreted here, as
    the platform's name says) in blocks of _BLOCK positions."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "decode_block_len",
                        lambda *a: _BLOCK)


@pytest.mark.parametrize("rope", [True, False], ids=["rope", "nope"])
@pytest.mark.parametrize("hd,group", [(128, 2), (64, 4)])
def test_decode_kernel_matches_the_xla_path(monkeypatch, hd, group, rope):
    """The kernel against cached_attention's XLA path, through
    cached_attention itself: the outputs of every row that holds a
    request agree within what bf16 probabilities allow, and the caches
    returned are equal (the writes are the XLA path's own)."""
    args, kw = _decode_case(hd, group, rope)
    ref, k_ref, v_ref = jax.jit(
        lambda *a: cached_attention(*a, **kw))(*args)
    _through_kernel(monkeypatch)
    out, k_out, v_out = jax.jit(
        lambda *a: cached_attention(*a, **kw))(*args)
    assert out.dtype == ref.dtype and out.shape == ref.shape
    live = np.asarray([d >= s for s, d in _ROWS])
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[live], np.asarray(ref, np.float32)[live],
        atol=2e-2, rtol=2e-2)
    # a row that holds no request reads nothing and gets zeros
    assert not np.asarray(out, np.float32)[~live].any()
    np.testing.assert_array_equal(np.asarray(k_out, np.float32),
                                  np.asarray(k_ref, np.float32))
    np.testing.assert_array_equal(np.asarray(v_out, np.float32),
                                  np.asarray(v_ref, np.float32))


def test_decode_kernel_float32_is_exact():
    """In float32 the online softmax over blocks is the XLA path's
    softmax to rounding: the tolerance of the flash parity tests."""
    from ray_tpu.ops.pallas.decode_attention import decode_attention

    (q, _, _, k_cache, v_cache, li, cache_len, _, start), kw = _decode_case(
        128, 2, False)
    q, k_cache, v_cache = (a.astype(jnp.float32)
                           for a in (q, k_cache, v_cache))
    b, _, nh, hd = q.shape
    nkv = k_cache.shape[2]
    qg = q.reshape(b, nkv, nh // nkv, hd)
    out = decode_attention(qg, k_cache, v_cache, li, start, cache_len,
                           scale=kw["scale"], block_len=_BLOCK)
    pos = jnp.arange(_MAX_LEN)
    mask = (pos >= start[:, None]) & (pos <= cache_len[:, None])
    s = jnp.einsum("bngd,bndk->bngk", qg, k_cache[li]) * kw["scale"]
    p = jax.nn.softmax(jnp.where(mask[:, None, None], s, -1e30), axis=-1)
    ref = jnp.einsum("bngk,bnkd->bngd", p, v_cache[li])
    live = np.asarray(cache_len >= start)
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=2e-5, rtol=2e-5)


def test_decode_kernel_under_a_mesh_that_splits_kv_heads(monkeypatch):
    """Under a mesh whose tensor axis splits kv_heads the kernel runs
    per shard (a Mosaic kernel cannot be partitioned by the compiler)
    and gives what one device gives."""
    from jax.sharding import NamedSharding

    from ray_tpu.parallel.mesh import build_mesh, spec_for

    args, kw = _decode_case(128, 2, True, nkv=4)
    ref, _, _ = jax.jit(lambda *a: cached_attention(*a, **kw))(*args)
    _through_kernel(monkeypatch)
    mesh = build_mesh({"data": 1, "tensor": 2}, jax.devices()[:2])

    def put(a, axes):
        return jax.device_put(a, NamedSharding(mesh, spec_for(axes,
                                                              mesh=mesh)))

    q, kk, vv, k_cache, v_cache, *rest = args
    args = (put(q, (None, None, "heads", None)),
            put(kk, (None, None, "kv_heads", None)),
            put(vv, (None, None, "kv_heads", None)),
            put(k_cache, ("layers", "batch", "kv_heads", "head_dim", None)),
            put(v_cache, ("layers", "batch", "kv_heads", None, "head_dim")),
            *rest)

    def sharded(*a):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return cached_attention(*a, **kw)

    out, k_out, _ = jax.jit(sharded)(*args)
    assert "tensor" in str(k_out.sharding.spec)
    live = np.asarray([d >= s for s, d in _ROWS])
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[live], np.asarray(ref, np.float32)[live],
        atol=2e-2, rtol=2e-2)


def test_decode_kernel_names_a_new_block_only_for_a_live_one():
    """What the kernel copies, read off its index map: walking the grid
    row by row, block by block, the (row, block) it names changes once
    for every block that overlaps a live row's range and at no other
    step, so a row that holds no request and the steps past a row's last
    block cost no copy (a block is copied when its name changes). Empty
    rows ahead of the first live one name row 0's block, once."""
    from ray_tpu.ops.pallas.decode_attention import _named_block

    blk, nb = 128, 8
    #        empty   empty  3 blocks    empty  1 block   5 blocks    empty
    ranges = [(0, -1), (0, -1), (100, 300), (0, -1), (5, 90), (300, 800),
              (0, -1)]
    start = jnp.asarray([r[0] for r in ranges], jnp.int32)
    length = jnp.asarray([r[1] for r in ranges], jnp.int32)
    named = [tuple(int(x) for x in _named_block(
        jnp.int32(bi), jnp.int32(j), start, length, blk, nb))
        for bi in range(len(ranges)) for j in range(nb)]
    copies = [named[0]] + [b for a, b in zip(named, named[1:]) if a != b]
    live_blocks = [(r, k) for r, (s, d) in enumerate(ranges) if d >= s
                   for k in range(s // blk, d // blk + 1)]
    assert copies == [(0, 0)] + live_blocks
    assert len(live_blocks) == 3 + 1 + 5
