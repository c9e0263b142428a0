"""The masked flash kernel of a prefill chunk's latent attention
(ops/pallas/latent_attention.py), interpreted on the CPU, against the
plain form it stands in for on a TPU (`models/dots3_note._attend_block`
folded over blocks of keys, then `_attend_done`), at a small twin of
each layer kind's `AttnSizes` whose shapes are whole in the kernel's
tiles: a full layer's (a 192-wide query against a latent of 256 + 64)
and a sliding one's (256-wide, the nope part no multiple of the lanes).
Nothing here is a device number."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import dots3_note as m
from ray_tpu.ops.pallas import latent_attention as la

SIZES = {"full": m.AttnSizes(4, 128, 64, 128, 64, 256, 1e4),
         "sliding": m.AttnSizes(2, 192, 64, 128, 64, 384, 1e4)}
BF16, F32 = jnp.bfloat16, jnp.float32


def _inputs(a, s, n, dt, seed=0, density=0.3, layers=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (1, a.heads, s, a.nope + a.rope)).astype(dt)
    rows = jax.random.normal(ks[1], (layers, 1, n, a.row)).astype(dt)
    layer = {"w_kvb_k": (jax.random.normal(ks[2], (a.kv_rank, a.heads, a.nope))
                         / np.sqrt(a.kv_rank)).astype(dt),
             "w_kvb_v": (jax.random.normal(ks[3], (a.kv_rank, a.heads, a.v))
                         / np.sqrt(a.kv_rank)).astype(dt)}
    return q, rows, layer, jax.random.uniform(ks[4], (1, s, n)) < density


def _plain(a, layer, q, rows, mask, cuts):
    """`_attend_block` folded over the blocks `cuts` [(first row, rows,
    first row that is the block's own)], as `_full_layer` walks them."""
    dt = q.dtype
    state = m._attend_init(q, a)
    for at, blk, own_from in cuts:
        own = (at + jnp.arange(blk)) >= own_from
        state = m._attend_block(state, a, layer, q, rows[:, at:at + blk],
                                mask[:, :, at:at + blk] & own, dt)
    return m._attend_done(state, dt).astype(F32)


def _kernel(a, layer, q, rows, li, mask, t):
    return m._attend_kernel(a, layer, q, rows, li, mask, t).astype(F32)


def _close(got, want, dt):
    # bf16: p and the expanded keys and values are rounded to 8 bits in
    # both; the sums run in another order. float32: the order alone
    tol = 2e-2 if dt == BF16 else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dt", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("kind", list(SIZES))
def test_kernel_equals_the_plain_form_under_a_random_mask(kind, dt):
    a = SIZES[kind]
    s, n = 256, 384
    q, rows, layer, mask = _inputs(a, s, n, dt)
    t = la.tiles(a.heads, a.nope, a.rope, a.v, a.kv_rank, s, n)
    assert t == (a.heads, 256, 384)
    got = _kernel(a, layer, q, rows, 1, mask, t)
    assert got.shape == (1, s, a.heads, a.v)
    _close(got, _plain(a, layer, q, rows[1], mask, [(0, n, 0)]), dt)
    # the other layer of the stack gives another answer
    assert float(jnp.abs(got - _kernel(a, layer, q, rows, 0, mask, t)).max()) \
        > 0.1


@pytest.mark.parametrize("kind", list(SIZES))
def test_a_query_with_nothing_let_through_gives_zeros(kind):
    a = SIZES[kind]
    q, rows, layer, mask = _inputs(a, 128, 256, BF16, seed=1)
    mask = mask.at[:, 7].set(False).at[:, 100:].set(False)
    got = _kernel(a, layer, q, rows, 0, mask, la.Tiles(a.heads, 64, 128))
    assert float(jnp.abs(got[:, 7]).max()) == 0.0
    assert float(jnp.abs(got[:, 100:]).max()) == 0.0
    _close(got, _plain(a, layer, q, rows[0], mask, [(0, 256, 0)]), BF16)


@pytest.mark.parametrize("kind", list(SIZES))
def test_tiles_with_nothing_let_through_are_skipped_and_change_nothing(kind):
    """Left padding empties the first key tile, causality the tiles above
    the diagonal, the depth not yet written the last: the tables name no
    such tile and the result is the plain form's over all of them."""
    a = SIZES[kind]
    s, n = 256, 640
    t = la.Tiles(a.heads, 128, 128)
    q, rows, layer, mask = _inputs(a, s, n, BF16, seed=2, density=0.5)
    k_pos, q_pos = jnp.arange(n), 256 + jnp.arange(s)
    mask = mask & (k_pos[None, :] <= q_pos[:, None]) & (k_pos >= 130)
    live, named = la.tile_tables(mask, t)
    assert live[0].tolist() == [[0, 1, 1, 0, 0], [0, 1, 1, 1, 0]]
    # the first tile names the first live one, the last the one before it
    assert named[0].tolist() == [1, 1, 2, 3, 3]
    got = _kernel(a, layer, q, rows, 1, mask, t)
    _close(got, _plain(a, layer, q, rows[1], mask, [(0, n, 0)]), BF16)
    # what lies in a tile that is never fetched is never read
    poisoned = rows.at[1, :, :128].set(jnp.nan).at[1, :, 512:].set(jnp.nan)
    assert bool((got == _kernel(a, layer, q, poisoned, 1, mask, t)).all())
    # no position at all: zeros, from whatever tile is named
    none = jnp.zeros_like(mask)
    assert la.tile_tables(none, t)[1][0].tolist() == [0] * 5
    assert float(jnp.abs(_kernel(a, layer, q, rows, 1, none, t)).max()) == 0.0


@pytest.mark.parametrize("kind", list(SIZES))
def test_one_call_equals_the_block_walk_with_a_last_block_moved_back(kind):
    """`_full_layer` walks a depth that is no multiple of its block with
    the last block moved back and masked to the positions that are its
    own; the kernel's tiles divide the depth and need no such block."""
    a = SIZES[kind]
    s, n, blk = 128, 320, 128
    q, rows, layer, mask = _inputs(a, s, n, BF16, seed=3)
    walk = [(0, blk, 0), (128, blk, 128), (n - blk, blk, 256)]
    want = _plain(a, layer, q, rows[0], mask, walk)
    _close(_plain(a, layer, q, rows[0], mask, [(0, n, 0)]), want, BF16)
    _close(_kernel(a, layer, q, rows, 0, mask, la.Tiles(a.heads, 64, 64)),
           want, BF16)


@pytest.mark.parametrize("dt", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("kind", list(SIZES))
def test_state_carried_over_three_tiles_equals_one_over_all_keys(kind, dt):
    """The running softmax across key tiles, held in VMEM scratch, is the
    plain form's state carried over three blocks, and both equal one
    softmax over the concatenation; in float32 to rounding's order."""
    a = SIZES[kind]
    s, n = 128, 384
    q, rows, layer, mask = _inputs(a, s, n, dt, seed=4)
    # the largest scores in the LAST block: the earlier tiles' sums are
    # rescaled when it arrives
    q = q * 2
    three = _kernel(a, layer, q, rows, 0, mask, la.Tiles(a.heads, 128, 128))
    one = _kernel(a, layer, q, rows, 0, mask, la.Tiles(a.heads, 128, 384))
    carried = _plain(a, layer, q, rows[0], mask,
                     [(0, 128, 0), (128, 128, 0), (256, 128, 0)])
    _close(three, carried, dt)
    _close(one, carried, dt)
    _close(three, _plain(a, layer, q, rows[0], mask, [(0, n, 0)]), dt)
    # fewer heads a grid step, smaller query tiles: the same numbers
    split = _kernel(a, layer, q, rows, 0, mask, la.Tiles(1, 32, 128))
    assert float(jnp.abs(split - three).max()) == 0.0


def test_tiles_are_given_only_for_shapes_whole_in_them():
    cfg = m.Dots3NoteConfig()
    full, sliding = cfg.attn(m.KINDS[0]), cfg.attn(m.KINDS[1])
    of = lambda a, s, n: la.tiles(a.heads, a.nope, a.rope, a.v, a.kv_rank,
                                  s, n)
    assert of(full, 1024, 20480) == (8, 512, 1024)
    # a ring of 640 and a chunk of 1,024: one tile of keys
    assert of(sliding, 1024, cfg.ring_len + 1024) == (8, 512, 1664)
    assert of(full, 1024, 4096 + 512) == (8, 512, 512)
    assert of(full, 1024, 4096 + 64) is None       # the depth
    assert of(full, 1000, 4096) is None            # the chunk
    twin = m.AttnSizes(4, 16, 8, 16, 32, 24, 1e4)  # the rehearsal's
    assert of(twin, 1024, 4096) is None            # a head's widths


def test_chunk_keeps_the_plain_form_off_the_tpu(monkeypatch):
    """The path is chosen from the platform and the shapes: here, on the
    CPU, the plain form whatever the shapes; on a TPU the kernel for a
    chunk whole in its tiles and never for a decode step."""
    from ray_tpu.ops import attention

    a = m.Dots3NoteConfig().attn(m.KINDS[0])
    assert m._chunk_tiles(a, 1024, 20480) is None
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert m._chunk_tiles(a, 1024, 20480) == (8, 512, 1024)
    assert m._chunk_tiles(a, 1, 20480) is None
    assert m._chunk_tiles(a, 1024, 20480 + 8) is None
