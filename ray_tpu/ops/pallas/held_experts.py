"""The held routed experts as ONE grouped matmul (Pallas/TPU).

`ops/moe.held_experts_ffn` lays the token-expert pairs out by expert,
each expert's group in whole tiles of `bm` rows, and hands the layout's
integers here as prefetched scalars: the expert of each tile, the rows of
it that are tokens, the number of tiles that exist, and for every slot
the token it holds and its router weight. This kernel walks the tiles.

Grid (tile, block of f), sized for the worst case (every pair on one
held expert); SwiGLU is separable over f, so an expert too large for
VMEM is taken a block of columns of w_gate and w_up, and of rows of
w_down, at a time (`f_block`). The three stacks are read WHERE THEY LIE:
their index maps pick the blocks of `tile_expert[i]`, so the pipeline has
step i+1's blocks in flight while step i computes, and an expert no token
chose is never read. A grid step past the tiles that exist computes
nothing, and its index maps repeat the last real step's blocks, so it
moves no bytes.

x stays in VMEM for the whole call as rows of 32-bit words (`_words`: a
bfloat16 row is d/2 words, column c beside column c + d/2, because a
single row of a 32-bit array is a plain dynamic slice of its sublanes
and one of a packed array is not). A tile's rows are copied from it, as
far as the tile's tokens reach, into the tile's buffer; the three
products run on the tile's first `m` rows, `m` chosen from `products` by
the tile's own count of tokens (a group of 40 rows does not pay for 128);
the rows of the result, weighted in float32, are added into ``y [T, d]``,
float32, which stays in VMEM scratch for the whole call and is written to
HBM once, by one copy in the last grid step. Nothing of T x k rows of d
exists anywhere.

Operands of the products in x's dtype, accumulation in float32; gate and
up stay float32 through the SiLU and their product, which is cast to x's
dtype for the down product; the sum over blocks of f and the weighted
sum over a token's pairs are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash_attention as _flash
from ray_tpu.ops.pallas.flash_attention import _LANES

# What the call may ask of a v5e's 128 MiB of VMEM (`f_block` fits the
# weights' blocks into it), and what of that is left uncounted for the
# compiler's own use.
_VMEM_BYTES = 100 * 2 ** 20
_SLACK_BYTES = 4 * 2 ** 20


def _words(x: jax.Array) -> jax.Array:
    """x ``[T, d]`` as rows of uint32: a 32-bit x bit for bit, a 16-bit
    one as ``[T, d/2]``, column c in the low half of word c and column
    c + d/2 in the high half."""
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    assert x.dtype.itemsize == 2 and x.shape[1] % 2 == 0, (x.dtype, x.shape)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    half = x.shape[1] // 2
    return bits[:, :half] | (bits[:, half:] << 16)


def _columns(words: jax.Array, dtype) -> list:
    """`_words` undone for a tile: [(first column, the columns from it
    on, in `dtype`)], one piece of a 32-bit dtype and the two halves of
    a 16-bit one (a bfloat16 is the high half of a float32)."""
    if jnp.dtype(dtype).itemsize == 4:
        return [(0, jax.lax.bitcast_convert_type(words, dtype))]
    assert dtype == jnp.bfloat16, dtype
    as_f32 = lambda w: jax.lax.bitcast_convert_type(w, jnp.float32)
    return [(0, as_f32(words << 16).astype(dtype)),
            (words.shape[1],
             as_f32(words & jnp.uint32(0xFFFF0000)).astype(dtype))]


def _fixed_bytes(T: int, d: int, bm: int, x_dtype) -> int:
    """VMEM the call holds whatever the block of f: x's words and y, the
    tile's rows and its result, and as much again for the products'
    temporaries (the unpacked rows, a block's down product)."""
    x_item = jnp.dtype(x_dtype).itemsize
    return (T + 2 * bm) * d * (x_item + 4) + _SLACK_BYTES


def _block_bytes(d: int, fb: int, bm: int, w_dtype) -> int:
    """VMEM that `fb` columns of f take: the three matrices' blocks,
    twice buffered, and the tile's gate, up and their product."""
    return fb * (2 * 3 * d * jnp.dtype(w_dtype).itemsize + 3 * 4 * bm)


def f_block(T: int, d: int, f: int, bm: int, x_dtype, w_dtype) -> int:
    """Columns of f in one grid step: the most, among f and its divisors
    that are whole lane rows, that fit `_VMEM_BYTES` beside what the call
    holds anyway (Kimi's expert is 88 MB and goes in blocks of 512
    columns; Laguna's 18.9 MB is one)."""
    room = _VMEM_BYTES - _fixed_bytes(T, d, bm, x_dtype)
    blocks = [f] + [b for b in range(f - f % _LANES, 0, -_LANES)
                    if b < f and f % b == 0]
    return next((b for b in blocks
                 if _block_bytes(d, b, bm, w_dtype) <= room), blocks[-1])


def _kernel(expert_ref, rows_ref, tiles_ref, token_ref, weight_ref, x_ref,
            wg_ref, wu_ref, wd_ref, y_ref, y_scr, rows_scr, out_scr, sem, *,
            bm: int, products: tuple, dtype):
    i, j = pl.program_id(0), pl.program_id(1)
    nf = pl.num_programs(1)
    base = i * bm
    n = rows_ref[i]                      # 0 for a tile that does not exist

    @pl.when((i == 0) & (j == 0))
    def _zero():
        y_scr[...] = jnp.zeros_like(y_scr)

    @pl.when((n > 0) & (j == 0))
    def _gather():
        def row(r, _):
            rows_scr[pl.ds(r, 1), :] = x_ref[pl.ds(token_ref[base + r], 1), :]
        jax.lax.fori_loop(0, n, row, None)

    def product(m):
        pieces = _columns(rows_scr[:m], dtype)

        def into_f(w_ref):
            return sum(jnp.dot(
                xs, w_ref[0, at:at + xs.shape[1], :].astype(dtype),
                preferred_element_type=jnp.float32) for at, xs in pieces)

        h = (jax.nn.silu(into_f(wg_ref)) * into_f(wu_ref)).astype(dtype)
        part = jnp.dot(h, wd_ref[0].astype(dtype),
                       preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _first():
            out_scr[:m] = part

        @pl.when(j > 0)
        def _further():
            out_scr[:m] += part

    for below, m in zip((0,) + products, products):
        pl.when((n > below) & (n <= m))(functools.partial(product, m))

    @pl.when((n > 0) & (j == nf - 1))
    def _add():
        def row(r, _):
            at = pl.ds(token_ref[base + r], 1)
            y_scr[at, :] += weight_ref[base + r] * out_scr[pl.ds(r, 1), :]
        jax.lax.fori_loop(0, n, row, None)

    @pl.when((i == pl.num_programs(0) - 1) & (j == nf - 1))
    def _write():
        copy = pltpu.make_async_copy(y_scr, y_ref, sem)
        copy.start()
        copy.wait()


def held_experts(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                 w_down: jax.Array, tile_expert: jax.Array,
                 tile_rows: jax.Array, tiles: jax.Array,
                 slot_token: jax.Array, slot_weight: jax.Array) -> jax.Array:
    """``y[t] = sum over the slots s that hold token t of slot_weight[s]
    * SwiGLU_e(x[t])``, e the expert of s's tile. x ``[T, d]``; w_gate,
    w_up ``[held, d, f]``, w_down ``[held, f, d]``, read as they lie;
    tile_expert, tile_rows ``[n_tiles]``: each tile's expert and how many
    of its rows, from its first, are tokens (0 past the `tiles` that
    exist, tiles ``[]`` or ``[1]``); slot_token, slot_weight ``[n_tiles
    * bm]``. A token may lie in many slots, of one tile too. Returns y
    ``[T, d]`` float32."""
    T, d = x.shape
    held, _, f = w_gate.shape
    n_tiles = tile_expert.shape[0]
    bm = slot_token.shape[0] // n_tiles
    assert w_up.shape == (held, d, f) and w_down.shape == (held, f, d), (
        x.shape, w_gate.shape, w_up.shape, w_down.shape)
    assert slot_token.shape == slot_weight.shape == (n_tiles * bm,)
    # a chunk's tile of 128 rows is multiplied as 64 where no more are
    # tokens; a decode step's tile of 16 as it is
    products = (bm // 2, bm) if bm >= 128 else (bm,)
    fb = f_block(T, d, f, bm, x.dtype, w_gate.dtype)
    nf = f // fb
    words = _words(x)

    def block(i, j, expert_ref, rows_ref, tiles_ref, *_):
        """(expert, block of f) of grid step (i, j): its own while the
        tile exists, then the last real step's, which is held already."""
        n = tiles_ref[0]
        live = i < n
        return (expert_ref[jnp.where(live, i, jnp.maximum(n - 1, 0))],
                jnp.where(live, j, jnp.where(n > 0, nf - 1, 0)))

    def columns_at(i, j, *scalars):
        e, jb = block(i, j, *scalars)
        return e, 0, jb

    def rows_at(i, j, *scalars):
        e, jb = block(i, j, *scalars)
        return e, jb, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_tiles, nf),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, d, fb), columns_at),
                  pl.BlockSpec((1, d, fb), columns_at),
                  pl.BlockSpec((1, fb, d), rows_at)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((T, d), jnp.float32),
                        pltpu.VMEM((bm, words.shape[1]), jnp.uint32),
                        pltpu.VMEM((bm, d), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    call = pl.pallas_call(
        functools.partial(_kernel, bm=bm, products=products, dtype=x.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(
                _VMEM_BYTES, _fixed_bytes(T, d, bm, x.dtype)
                + _block_bytes(d, fb, bm, w_gate.dtype))),
        name="held_experts",
        interpret=_flash._interpret(),
    )
    with jax.named_scope("held_experts"):
        return call(tile_expert.astype(jnp.int32),
                    tile_rows.astype(jnp.int32),
                    jnp.reshape(tiles, (1,)).astype(jnp.int32),
                    slot_token.astype(jnp.int32),
                    slot_weight.astype(jnp.float32), words, w_gate, w_up,
                    w_down)
