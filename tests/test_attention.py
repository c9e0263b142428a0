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


def _grads(attn, q, k, v):
    def loss(q, k, v):
        out = attn(q, k, v)
        return (out * jnp.cos(out)).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


# (heads, kv_heads, seq, block_q, block_k, causal, path, strip): every
# path `_flash_backward` keeps. Three tiles a side, so tiles below the
# diagonal, on it and above it (no grid step) all occur; `strip` cuts
# the square tiles on the diagonal as 256 cuts the chip's; "split" is
# what a dq scratch that does not fit falls to (forced here by a budget
# of nothing: the shapes the repo runs all fit).
_BWD_CASES = {
    "mha": (2, 2, 256, 128, 128, True, "fused", None),
    "mha-full": (2, 2, 256, 128, 128, False, "fused", None),
    "gqa2": (4, 2, 256, 128, 128, True, "fused", None),
    "gqa2-three-tiles": (4, 2, 384, 128, 128, True, "fused", None),
    "gqa4-three-tiles": (8, 2, 384, 128, 128, True, "fused", None),
    "gqa4-full": (8, 2, 384, 128, 128, False, "fused", None),
    "gqa2-strips": (4, 2, 384, 128, 128, True, "fused", 32),
    "gqa2-wide-k": (4, 2, 384, 64, 128, True, "fused", None),
    "gqa4-wide-q": (8, 2, 384, 128, 64, True, "fused", None),
    "gqa2-split": (4, 2, 384, 128, 128, True, "split", None),
    "gqa4-split-strips": (8, 2, 384, 128, 128, True, "split", 64),
    "gqa2-split-full": (4, 2, 256, 64, 128, False, "split", None),
}


@pytest.mark.parametrize("case", _BWD_CASES)
def test_flash_bwd_parity(monkeypatch, case):
    from ray_tpu.ops.pallas import flash_attention as fa

    h, hk, s, bq, bk, causal, path, strip = _BWD_CASES[case]
    if path == "split":
        monkeypatch.setattr(fa, "_DQ_VMEM_BYTES", 0)
    if strip:
        monkeypatch.setattr(fa, "_DIAG_SUB", strip)
    q, k, v = _make_qkv(1, s, h, hk, 64, seed=2)
    # the path is a function of the shape, and says which it is
    assert fa.backward_path(s, 64, h // hk, q.dtype) == path
    assert (fa._strips(s, s, bq, bk) == strip) if causal else True

    def flash(q, k, v):
        return flash_attention(q, k, v, causal, None, bq, bk)

    calls = str(jax.make_jaxpr(lambda *a: _grads(flash, *a))(q, k, v)
                ).count("pallas_call")
    assert calls == (2 if path == "fused" else 3)     # forward + backward
    ref = lambda q, k, v: xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v), atol=2e-5,
                               rtol=2e-5)
    gf, gr = _grads(flash, q, k, v), _grads(ref, q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_cross_attention_with_more_keys_than_queries():
    """sq != sk, not causal: every tile of the rectangle is live, in the
    forward's order and in the backward's."""
    q, _, _ = _make_qkv(1, 128, 4, 2, 64, seed=7)
    _, k, v = _make_qkv(1, 384, 4, 2, 64, seed=8)

    def flash(q, k, v):
        return flash_attention(q, k, v, False, None, 64, 128)

    ref = lambda q, k, v: xla_attention(q, k, v, causal=False)
    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v), atol=2e-5,
                               rtol=2e-5)
    for a, b in zip(_grads(flash, q, k, v), _grads(ref, q, k, v)):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_live_tiles_are_the_ones_causality_leaves():
    """The grid's tables at the train cells' shape, 2,048 in tiles of
    512: 10 of 16 tiles take a step, 4 of them masked; the backward
    walks them k tile by k tile over the group's heads and marks each q
    tile's last k tile, where its dq is written."""
    from ray_tpu.ops.pallas import flash_attention as fa

    def tables(*a, **kw):
        cols, kinds = fa._live_tiles(*a, **kw)
        return [np.asarray(c) for c in cols] + [kinds]

    qi, ki, flags, kinds = tables(4, 4, 512, 512, True, k_major=False)
    assert kinds == (False, True)
    assert list(zip(qi, ki)) == [(q, k) for q in range(4)
                                 for k in range(q + 1)]
    assert [bool(f & fa._MASKED) for f in flags] == [
        q == k for q in range(4) for k in range(q + 1)]
    ki, gi, qi, flags, _ = tables(4, 4, 512, 512, True, k_major=True,
                                  group=2)
    assert list(zip(ki, gi, qi)) == [(k, g, q) for k in range(4)
                                     for g in range(2) for q in range(k, 4)]
    assert [bool(f & fa._DQ_DONE) for f in flags] == [
        q == k for k in range(4) for g in range(2) for q in range(k, 4)]
    assert sum(bool(f & fa._FIRST) for f in flags) == 4
    assert sum(bool(f & fa._LAST) for f in flags) == 4
    # rectangular: a tile is masked where the diagonal crosses it
    qi, ki, flags, _ = tables(4, 2, 256, 512, True, k_major=False)
    assert [(q, k, bool(f & fa._MASKED)) for q, k, f in zip(
        qi, ki, flags)] == [(0, 0, True), (1, 0, True), (2, 0, False),
                            (2, 1, True), (3, 0, False), (3, 1, True)]
    # not causal: the whole rectangle, nothing masked
    *_, flags, kinds = tables(4, 2, 256, 512, False, k_major=True)
    assert len(flags) == 8 and kinds == (False,)
    # one tile a head, the cells' choice: only the masked body is built
    assert tables(1, 1, 2048, 2048, True, k_major=False)[-1] == (True,)


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


def _decode_case(hd, group, rope, nkv=2, layers=2, seed=0, rows=_ROWS):
    b = len(rows)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = jnp.bfloat16
    k_cache = jax.random.normal(ks[0], (layers, b, nkv, hd, _MAX_LEN), dt)
    v_cache = jax.random.normal(ks[1], (layers, b, nkv, _MAX_LEN, hd), dt)
    q = jax.random.normal(ks[2], (b, 1, nkv * group, hd), dt)
    kk = jax.random.normal(ks[3], (b, 1, nkv, hd), dt)
    vv = jax.random.normal(ks[4], (b, 1, nkv, hd), dt)
    start = jnp.asarray([r[0] for r in rows], jnp.int32)
    cache_len = jnp.asarray([r[1] for r in rows], jnp.int32)
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


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.mark.parametrize("rope", [True, False], ids=["rope", "nope"])
@pytest.mark.parametrize("hd,group", [(128, 2), (64, 4)])
def test_decode_kernel_matches_the_xla_path(monkeypatch, hd, group, rope):
    """The kernel against cached_attention's XLA path, through
    cached_attention itself: the outputs of every row that holds a
    request agree within what bf16 probabilities allow, and the caches
    returned are equal over those rows. With a head of a whole lane row
    (128) the writes are the kernel's own, and a row that holds no
    request is left as it went in (the XLA path writes it at its clamped
    depth, a row nobody reads); with 64 they are the XLA path's."""
    args, kw = _decode_case(hd, group, rope)
    ref, k_ref, v_ref = jax.jit(
        lambda *a: cached_attention(*a, **kw))(*args)
    _through_kernel(monkeypatch)
    out, k_out, v_out = jax.jit(
        lambda *a: cached_attention(*a, **kw))(*args)
    assert out.dtype == ref.dtype and out.shape == ref.shape
    live = np.asarray([d >= s for s, d in _ROWS])
    np.testing.assert_allclose(
        _f32(out)[live], _f32(ref)[live], atol=2e-2, rtol=2e-2)
    # a row that holds no request reads nothing and gets zeros
    assert not _f32(out)[~live].any()
    np.testing.assert_array_equal(_f32(k_out)[:, live], _f32(k_ref)[:, live])
    np.testing.assert_array_equal(_f32(v_out)[:, live], _f32(v_ref)[:, live])
    empty_as = (args[3], args[4]) if hd % 128 == 0 else (k_ref, v_ref)
    np.testing.assert_array_equal(_f32(k_out)[:, ~live],
                                  _f32(empty_as[0])[:, ~live])
    np.testing.assert_array_equal(_f32(v_out)[:, ~live],
                                  _f32(empty_as[1])[:, ~live])


# rows (start, depth before the step) at the edges of what the kernel
# writes: the new row alone in its block, alone in its range, in the
# cache's last position, its first and past its end, no row at all, and
# rows that hold no request around one that does
_WRITE_CASES = {
    "first-of-a-block": [(0, 2 * _BLOCK), (_BLOCK - 3, _BLOCK)],
    "length-is-start": [(37, 37), (_BLOCK, _BLOCK), (3 * _BLOCK - 1,
                                                     3 * _BLOCK - 1)],
    "last-of-the-cache": [(0, _MAX_LEN - 1), (_MAX_LEN - 1, _MAX_LEN - 1),
                          (3 * _BLOCK + 1, _MAX_LEN - 1)],
    "length-zero": [(0, 0), (0, 0)],
    # a depth past the end is the last position, as the XLA write clamps it
    "past-the-end": [(0, _MAX_LEN), (_BLOCK, _MAX_LEN + 3)],
    "every-row-empty": [(0, -1), (5, 4), (0, -1)],
    "empty-around-live": [(0, -1), (0, -1), (40, 2 * _BLOCK + 17), (0, -1),
                          (0, 15), (0, -1)],
}


@pytest.mark.parametrize("case", _WRITE_CASES)
def test_decode_kernel_writes_the_new_row_and_nothing_else(monkeypatch,
                                                          case):
    """With the step's K and V handed to it the kernel attends to them
    at `length[r]` and leaves them there: the outputs of the live rows
    are the XLA path's, and every position of the caches other than
    `length[r]` of layer `li` of the live rows is bit for bit what went
    in: the other layers, the rows that hold no request, and the rest
    of the tiles written back."""
    rows = _WRITE_CASES[case]
    args, kw = _decode_case(128, 2, False, rows=rows, layers=3, seed=3)
    q, kk, vv, k_cache, v_cache, _, cache_len, *_ = args
    li = jnp.int32(1)
    args = args[:5] + (li,) + args[6:]
    ref, _, _ = jax.jit(lambda *a: cached_attention(*a, **kw))(*args)
    _through_kernel(monkeypatch)
    out, k_out, v_out = jax.jit(
        lambda *a: cached_attention(*a, **kw))(*args)
    live = np.asarray([d >= s for s, d in rows])
    np.testing.assert_allclose(
        _f32(out)[live], _f32(ref)[live], atol=2e-2, rtol=2e-2)
    assert not _f32(out)[~live].any()
    k_want, v_want = _f32(k_cache), _f32(v_cache)
    for r in np.flatnonzero(live):
        at = min(rows[r][1], _MAX_LEN - 1)
        k_want[1, r, :, :, at] = _f32(kk)[r, 0]
        v_want[1, r, :, at, :] = _f32(vv)[r, 0]
    np.testing.assert_array_equal(_f32(k_out), k_want)
    np.testing.assert_array_equal(_f32(v_out), v_want)


def test_decode_kernel_float32_is_exact():
    """In float32 the online softmax over blocks is the XLA path's
    softmax to rounding: the tolerance of the flash parity tests. So it
    is with the new row supplied to the kernel and absent from the
    cache, which comes back holding it."""
    from ray_tpu.ops.pallas.decode_attention import decode_attention

    (q, kk, vv, k_cache, v_cache, li, cache_len, _, start), kw = _decode_case(
        128, 2, False)
    q, kk, vv, k_cache, v_cache = (a.astype(jnp.float32)
                                   for a in (q, kk, vv, k_cache, v_cache))
    b, _, nh, hd = q.shape
    nkv = k_cache.shape[2]
    qg = q.reshape(b, nkv, nh // nkv, hd)
    live = np.asarray(cache_len >= start)
    rows = np.flatnonzero(live)
    at = np.asarray(cache_len)[rows]
    k_with = k_cache.at[li, rows, :, :, at].set(kk[rows, 0])
    v_with = v_cache.at[li, rows, :, at, :].set(vv[rows, 0])

    def reference(k_cache, v_cache):
        pos = jnp.arange(_MAX_LEN)
        mask = (pos >= start[:, None]) & (pos <= cache_len[:, None])
        s = jnp.einsum("bngd,bndk->bngk", qg, k_cache[li]) * kw["scale"]
        p = jax.nn.softmax(jnp.where(mask[:, None, None], s, -1e30), axis=-1)
        return jnp.einsum("bngk,bnkd->bngd", p, v_cache[li])

    out = decode_attention(qg, k_cache, v_cache, li, start, cache_len,
                           scale=kw["scale"], block_len=_BLOCK)
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(reference(k_cache, v_cache))[live],
        atol=2e-5, rtol=2e-5)
    out, k_out, v_out = decode_attention(
        qg, k_cache, v_cache, li, start, cache_len, scale=kw["scale"],
        block_len=_BLOCK, new_kv=(kk[:, 0], vv[:, 0]))
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(reference(k_with, v_with))[live],
        atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(k_out), np.asarray(k_with))
    np.testing.assert_array_equal(np.asarray(v_out), np.asarray(v_with))


def test_decode_kernel_under_a_mesh_that_splits_kv_heads(monkeypatch):
    """Under a mesh whose tensor axis splits kv_heads the kernel runs
    per shard (a Mosaic kernel cannot be partitioned by the compiler)
    and gives what one device gives, the caches it returns too."""
    from jax.sharding import NamedSharding

    from ray_tpu.parallel.mesh import build_mesh, spec_for

    args, kw = _decode_case(128, 2, True, nkv=4)
    ref, k_ref, v_ref = jax.jit(lambda *a: cached_attention(*a, **kw))(*args)
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

    out, k_out, v_out = jax.jit(sharded)(*args)
    assert "tensor" in str(k_out.sharding.spec)
    assert "tensor" in str(v_out.sharding.spec)
    live = np.asarray([d >= s for s, d in _ROWS])
    np.testing.assert_allclose(
        _f32(out)[live], _f32(ref)[live], atol=2e-2, rtol=2e-2)
    # each shard wrote the new row of its own kv heads
    np.testing.assert_array_equal(_f32(k_out)[:, live], _f32(k_ref)[:, live])
    np.testing.assert_array_equal(_f32(v_out)[:, live], _f32(v_ref)[:, live])


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
