"""Flash attention forward + backward kernels (Pallas/TPU).

Blockwise online-softmax attention: O(seq) memory, GQA via block-index
mapping (no KV repeat materialization), and for causal attention only
the work causality requires. A kernel's innermost grid axis does not
walk a rectangle of tiles but a LIST of the tiles that hold a visible
position (`_live_tiles`, small scalar-prefetch tables): a tile above
the diagonal takes no grid step and nothing is fetched for it, a tile
wholly below it runs a body with no mask in it, and only a tile the
diagonal crosses builds one. Square tiles are computed in strips of
`_DIAG_SUB` (`_strips`): on the diagonal a strip stops at it and masks
only its own `_DIAG_SUB` x `_DIAG_SUB` block, and what a step holds of
scores stays ``[block, strip]`` however large the tile, so the tile can
be as long as the sequence (`default_blocks`) and K and V are fetched
once a head.

Both kernels work on the TRANSPOSED tile, ``s^T = k q^T`` of
``[keys, queries]``. A query's statistics (running max and sum forward,
lse and delta backward) are then rows of lanes: reduced over sublanes,
broadcast along them, and read as ``[1, block_q]`` blocks of a ``[b, h,
s]`` array, not as 4-byte columns padded to 128 lanes; and every
product takes its operands as they lie, none a transposed score tile
(o^T takes v^T and dq^T takes k^T, which XLA hands in beside v and k).
With head_dim whole in lanes the kernels read and write a head's rows
as a band of columns of ``[b, s, h * d]`` (`_Heads`), the layout the
model's projections make and take: no transpose of q, k, v, dO, the
output, dk or dv exists on either side of a call.

Forward: grid (batch, head, live (q tile, k tile) pairs, k innermost),
the running max, sum and o^T in VMEM scratch across a q tile's k tiles.
It saves (q, k, v, out, lse).

Backward: ONE kernel (Dao 2023's two, fused). For each live (k tile,
head of the GQA group, q tile), q innermost, it builds the tile's
scores, probabilities, dP and dS once and feeds all three gradients:
five products where a dq kernel and a dkv kernel ran seven. dk and dv of
one KV head accumulate in float32 scratch over the q tiles AND the
group's heads and are written once, in the gradient's dtype; dq^T of the
whole group (``[n_rep, d, seq]`` float32, 2 MiB at 2,048 x 128 x 2)
stays in VMEM scratch for the life of one (batch, kv head) and each q
tile of it is written when its last k tile is done. `delta = rowsum(dO
* O)` stays an XLA prologue. Where that dq scratch would not fit
(`backward_path`), the same tile body runs in two calls, dk/dv without
dq and a dq kernel with k innermost ("split": seven products, nothing
per query head in memory either).

Scope names are what the benchmark's trace reduction reads
(`benchmarks/trace_spans.PARTS`: `flash_fwd`, `flash_bwd_dq`,
`flash_bwd_dkv`, whole path segments). The fused kernel is the dkv
kernel's loop order with dq added and runs under `flash_bwd_dkv`;
`flash_bwd_dq` holds device time only on the split path. Under any
other name the kernel's time would fall to the enclosing `attn` part
and the flash shares would read false; renaming is a benchmark PR's.

The reference framework has no attention kernels of its own (torch
supplies them); this is TPU-native core-op territory.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._internal.profiler import process_log

NEG_INF = -1e30
# lse sentinel for fully-masked rows: exp(s - BIG) == 0 for any finite s
_MASKED_LSE = 1e30
_LANES = 128
# a live tile's flags, the last table
_FIRST, _LAST, _MASKED, _DQ_DONE = 1, 2, 4, 8
# the strips a square tile is computed in: at 2,048 x 128, forward /
# backward, 0.77 / 1.59 ms in strips of 256, 0.82 / 1.69 of 512, and
# 1.16 / 1.63 of 128 (my chip runs, PR 41; the last on the forward's
# earlier form)
_DIAG_SUB = 256
# what a grid step may hold (v5e and v6e cores have 128 MiB, v7x 64): a
# tile taken whole, 1,024 x 1,024 by hand, keeps about 24 MiB of float32
# temporaries; in strips under 8
_VMEM_BYTES = 64 * 2 ** 20
# the fused backward's dq scratch and its two output buffers
_DQ_VMEM_BYTES = 24 * 2 ** 20
_NT = (((1,), (1,)), ((), ()))       # a b^T
_NN = (((1,), (0,)), ((), ()))       # a b


def _interpret() -> bool:
    """Interpret mode only where the CPU was asked for by name
    (JAX_PLATFORMS=cpu, as the tests set it). It is never inferred from
    the backend jax happened to end up with: a process that was meant to
    run on the chip and fell back to the CPU must fail to lower the
    kernel, not emulate it quietly."""
    return jax.config.jax_platforms == "cpu"


def default_blocks(sq: int, sk: int) -> tuple[int, int]:
    """(block_q, block_k) for these lengths where the caller names none.
    Self-attention: square tiles, the largest of up to 2,048 that the
    sequence is whole in. K and V are then fetched once for as many
    queries, the tile is computed in strips (`_strips`), and at 4 x 16
    heads (8 of KV) of 128 and 2,048 tokens, forward + backward, one
    tile a head took 2.22 ms where two a side took 2.53 and four 3.16
    (my chip runs, PR 41: tools/flash_attention_probe.py; rectangular
    tiles lose the strips and lost to the square ones around them: 512
    x 1,024 3.13, 1,024 x 512 3.09, 256 x 1,024 3.97).
    Unequal lengths: tiles taken whole, so of 512 at most."""
    def largest(n, sizes):
        return next((t for t in sizes if n % t == 0), n)
    if sq == sk:
        return (largest(sq, (2048, 1024, 512, 256, 128)),) * 2
    return largest(sq, (512, 256, 128)), largest(sk, (512, 256, 128))


def _blocks(sq, sk, block_q, block_k):
    auto = default_blocks(sq, sk)
    block_q = min(block_q or auto[0], sq)
    block_k = min(block_k or auto[1], sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"blocks ({block_q},{block_k}) must divide the "
                         f"sequence lengths ({sq},{sk})")
    return block_q, block_k


def _live_tiles(nq: int, nk: int, block_q: int, block_k: int, causal: bool,
                k_major: bool, group: int = 1):
    """The tiles that hold a visible position, in grid order, as int32
    tables. Forward and dq kernel: (q tile, k tile, flags) with k
    innermost. `k_major` (dkv and fused): (k tile, head of the group, q
    tile, flags) over the `group` heads that share the k tile, q
    innermost. _FIRST / _LAST: the first and last live step of the outer
    tile (its scratch starts and is written); _MASKED: the diagonal
    crosses the tile; _DQ_DONE (k_major): the q tile's last k tile."""
    def live(qi, ki):       # the tile's last query sees its first key
        return not causal or (qi + 1) * block_q - 1 >= ki * block_k

    def masked(qi, ki):     # its first query does not see its last key
        return causal and qi * block_q < (ki + 1) * block_k - 1

    rows = []
    if k_major:
        last_k = {qi: max(ki for ki in range(nk) if live(qi, ki))
                  for qi in range(nq)}
        for ki in range(nk):
            # a k tile no query sees (sk > sq) still writes its zeros:
            # one step, all of it masked
            steps = [(g, qi) for g in range(group) for qi in range(nq)
                     if live(qi, ki)] or [(0, nq - 1)]
            for n, (g, qi) in enumerate(steps):
                rows.append((ki, g, qi,
                             _FIRST * (n == 0)
                             | _LAST * (n == len(steps) - 1)
                             | _MASKED * masked(qi, ki)
                             | _DQ_DONE * (last_k[qi] == ki)))
    else:
        for qi in range(nq):
            steps = [ki for ki in range(nk) if live(qi, ki)]
            for n, ki in enumerate(steps):
                rows.append((qi, ki, _FIRST * (n == 0)
                             | _LAST * (n == len(steps) - 1)
                             | _MASKED * masked(qi, ki)))
    rows = np.asarray(rows, np.int32)
    kinds = tuple(sorted({bool(f & _MASKED) for f in rows[:, -1]}))
    return tuple(jnp.asarray(c) for c in rows.T), kinds


def _strips(sq: int, sk: int, block_q: int, block_k: int):
    """The width of the strips a tile is computed in, or None where it
    is taken whole. Strips keep what a step holds of scores at
    ``[block, strip]`` however large the tile that is fetched, and on
    the diagonal they stop at it: square tiles of a square problem cross
    it corner to corner, so a strip's extent there is static."""
    if sq == sk and block_q == block_k and block_q > _DIAG_SUB and (
            block_q % _DIAG_SUB == 0):
        return _DIAG_SUB
    return None


def _by_kind(flags, kinds, tile):
    """`tile(masked)` for this step's kind, of the kinds the grid has."""
    for masked in kinds:
        pl.when((flags & _MASKED) == _MASKED * masked)(
            functools.partial(tile, masked))


def _visible(shape, q_axis: int, first_q, first_k):
    """[shape] bool: the query of this row/column sees the key of this
    column/row; `first_*` the positions of the tile's first ones."""
    q_pos = first_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = first_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return q_pos >= k_pos


class _Heads:
    """How a kernel reaches `rows` positions of one head of ``[b, s, h,
    d]``. With head_dim whole in lanes they are a band of columns of
    ``[b, s, h * d]``, a block the copy engine reads and writes with a
    stride: no transpose on either side of the kernel. Otherwise
    through ``[b, h, s, d]``, which XLA makes. A block's leading
    dimensions are squeezed: the kernel sees ``[rows, d]`` either way."""

    def __init__(self, d: int):
        self.flat = d % _LANES == 0

    def view(self, x):
        b, s, h, d = x.shape
        return x.reshape(b, s, h * d) if self.flat else x.transpose(0, 2, 1, 3)

    def spec(self, rows: int, d: int, at):
        """`at(*grid indices and tables) -> (batch, head, tile)`."""
        if self.flat:
            def index(*grid):
                bi, hi, ti = at(*grid)
                return bi, ti, hi
            return pl.BlockSpec((None, rows, d), index)
        return pl.BlockSpec((None, None, rows, d), lambda *g: (*at(*g), 0))

    def shape(self, b, s, h, d, dtype):
        return jax.ShapeDtypeStruct(
            (b, s, h * d) if self.flat else (b, h, s, d), dtype)

    def unview(self, y, h: int):
        if self.flat:
            return y.reshape(*y.shape[:2], h, y.shape[2] // h)
        return y.transpose(0, 2, 1, 3)


def _tile_row(at, nq: int):
    """Index map of a q tile's block of an array ``[b, (head, q tile),
    ...]`` (lse, delta, dq^T); `at` as `_Heads.spec` takes it."""
    def index(*grid):
        bi, hi, qi = at(*grid)
        return bi, hi * nq + qi, 0, 0
    return index


def _key_cols(at):
    """Index map of a k tile's columns of ``[b, kv head, d, sk]`` (k^T,
    v^T)."""
    def index(*grid):
        bi, hi, ki = at(*grid)
        return bi, hi, 0, ki
    return index


# --------------------------------------------------------------- forward
def _flash_fwd_kernel(qi_ref, ki_ref, flag_ref, q_ref, k_ref, vt_ref,
                      o_ref, lse_ref, m_scratch, l_scratch, acc_scratch, *,
                      scale: float, kinds: tuple,
                      block_q: int, block_k: int, sub: int | None):
    """On the transposed tile, as the backward: keys down the sublanes,
    queries along the lanes, so a query's running max and sum are
    reductions over sublanes (element-wise over the tile's registers and
    one fold of eight rows) and broadcast back the same way; along the
    lanes each costs the cross-lane unit a pass a row of registers."""
    t = pl.program_id(2)
    flags = flag_ref[t]

    @pl.when((flags & _FIRST) != 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    def fold(queries, keys, first_q=None, first_k=None):
        """Queries `queries` of the tile against its keys `keys`; masked
        where `first_q` is given."""
        # Feed the MXU its native input dtype (bf16) and accumulate f32
        # via preferred_element_type — casting operands to f32 first
        # forces the multi-pass f32 matmul path (~6x slower on MXU).
        st = jax.lax.dot_general(
            k_ref[keys, :], q_ref[queries, :], _NT,
            preferred_element_type=jnp.float32) * scale   # [keys, queries]
        if first_q is not None:
            st = jnp.where(_visible(st.shape, 1, first_q, first_k), st,
                           NEG_INF)
        m_prev = m_scratch[:, queries]                # [1, queries]
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)                      # [keys, queries]
        alpha = jnp.exp(m_prev - m_new)               # [1, queries]
        l_scratch[:, queries] = (alpha * l_scratch[:, queries]
                                 + jnp.sum(pt, axis=0, keepdims=True))
        m_scratch[:, queries] = m_new
        acc_scratch[:, queries] = acc_scratch[:, queries] * alpha + (
            jax.lax.dot_general(vt_ref[:, keys], pt.astype(vt_ref.dtype),
                                _NN, preferred_element_type=jnp.float32))

    def tile(masked: bool):
        whole_q, whole_k = slice(0, block_q), slice(0, block_k)
        if sub is None:
            fold(whole_q, whole_k, *((qi_ref[t] * block_q,
                                      ki_ref[t] * block_k) if masked else ()))
            return
        for r in range(0, block_q, sub):    # a strip of queries sees
            queries = slice(r, r + sub)
            if not masked:                  # every key of a tile below,
                fold(queries, whole_k)
                continue
            if r:                           # on the diagonal those before
                fold(queries, slice(0, r))  # it, and then its own
            fold(queries, queries, 0, 0)

    _by_kind(flags, kinds, tile)

    @pl.when((flags & _LAST) != 0)
    def _finalize():
        m, l = m_scratch[:], l_scratch[:]             # [1, block_q]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scratch[:] / l_safe).T.astype(o_ref.dtype)
        lse_ref[...] = jnp.where(l > 0.0, m + jnp.log(l_safe), _MASKED_LSE)


def _flash_forward(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool, scale: float | None,
                   block_q: int | None, block_k: int | None):
    """Returns (out [b, sq, h, d], lse [b, h, sq]): a row of lanes a
    head, as the backward reads it."""
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    n_rep = h // hk
    if scale is None:
        scale = d ** -0.5
    block_q, block_k = _blocks(sq, sk, block_q, block_k)
    nq = sq // block_q
    tables, kinds = _live_tiles(nq, sk // block_k, block_q, block_k, causal,
                                k_major=False)
    heads = _Heads(d)

    def q_tile(bi, hi, t, qi, ki, flags):
        return bi, hi, qi[t]

    def k_tile(bi, hi, t, qi, ki, flags):
        return bi, hi // n_rep, ki[t]

    # each kernel call sits in a named scope (HLO metadata only), so a
    # profiler trace names its custom call whatever the compiler numbers it
    fwd = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=scale, kinds=kinds,
                          block_q=block_q, block_k=block_k,
                          sub=_strips(sq, sk, block_q, block_k)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, tables[0].shape[0]),
            in_specs=[heads.spec(block_q, d, q_tile),
                      heads.spec(block_k, d, k_tile),
                      pl.BlockSpec((None, None, d, block_k),
                                   _key_cols(k_tile))],
            out_specs=[heads.spec(block_q, d, q_tile),
                       pl.BlockSpec((None, None, 1, block_q),
                                    _tile_row(q_tile, nq))],
            scratch_shapes=[pltpu.VMEM((1, block_q), jnp.float32),
                            pltpu.VMEM((1, block_q), jnp.float32),
                            pltpu.VMEM((d, block_q), jnp.float32)]),
        out_shape=[heads.shape(b, sq, h, d, q.dtype),
                   jax.ShapeDtypeStruct((b, h * nq, 1, block_q),
                                        jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=_interpret(),
    )
    with jax.named_scope("flash_fwd"):
        out, lse = fwd(*tables, heads.view(q), heads.view(k),
                       v.transpose(0, 2, 3, 1))       # v^T [b, hk, d, sk]
    return heads.unview(out, h), lse.reshape(b, h, sq)


# -------------------------------------------------------------- backward
def _bwd_tile(q, k, v, do, lse, delta, *, scale: float, mask=None):
    """One tile, transposed. q, do ``[tq, d]``; k, v ``[tk, d]``; lse,
    delta ``[1, tq]``; mask ``[tk, tq]`` or None. Returns (p^T, dS^T /
    scale), ``[tk, tq]`` in the operands' dtype: the products that take
    dS are scaled once, on their accumulators."""
    st = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
    pt = jnp.exp(st * scale - lse)
    if mask is not None:
        pt = jnp.where(mask, pt, 0.0)
    dpt = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    return pt.astype(do.dtype), (pt * (dpt - delta)).astype(q.dtype)


def _bwd_strips(flags, kinds, first_q, first_k, block_q, block_k, sub,
                step):
    """Runs `step(keys, queries, mask_at)` over the tile. Without
    strips: once, whole, masked on the diagonal. With them (square
    tiles): a strip of keys against every query of a tile below the
    diagonal, and on it against its own queries, masked, and against
    those after them."""
    def tile(masked: bool):
        whole_q, whole_k = slice(0, block_q), slice(0, block_k)
        if sub is None:
            step(whole_k, whole_q, (first_q, first_k) if masked else None)
            return
        for r in range(0, block_k, sub):
            keys = slice(r, r + sub)
            if not masked:
                step(keys, whole_q, None)
                continue
            step(keys, keys, (0, 0))
            if r + sub < block_q:
                step(keys, slice(r + sub, block_q), None)

    _by_kind(flags, kinds, tile)


def _mask_of(keys, queries, mask_at):
    return None if mask_at is None else _visible(
        (keys.stop - keys.start, queries.stop - queries.start), 1, *mask_at)


def _flash_bwd_kernel(ki_ref, g_ref, qi_ref, flag_ref, q_ref, k_ref, kt_ref,
                      v_ref, do_ref, lse_ref, delta_ref, *refs, scale: float,
                      kinds: tuple, block_q: int, block_k: int,
                      sub: int | None, nq: int, with_dq: bool):
    """dk and dv of a KV head and, `with_dq`, dq^T of its group."""
    if with_dq:
        dq_ref, dk_ref, dv_ref, dq_scratch, dk_scratch, dv_scratch = refs
    else:
        dk_ref, dv_ref, dk_scratch, dv_scratch = refs
    t = pl.program_id(2)
    flags = flag_ref[t]
    row = g_ref[t] * nq + qi_ref[t]

    if with_dq:
        @pl.when(t == 0)
        def _init_dq():
            dq_scratch[:] = jnp.zeros_like(dq_scratch)

    @pl.when((flags & _FIRST) != 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    def step(keys, queries, mask_at):
        q, do = q_ref[queries, :], do_ref[queries, :]
        pt, dst = _bwd_tile(q, k_ref[keys, :], v_ref[keys, :], do,
                            lse_ref[:, queries], delta_ref[:, queries],
                            scale=scale, mask=_mask_of(keys, queries,
                                                       mask_at))
        dv_scratch[keys, :] += jax.lax.dot_general(
            pt, do, _NN, preferred_element_type=jnp.float32)
        dk_scratch[keys, :] += jax.lax.dot_general(
            dst, q, _NN, preferred_element_type=jnp.float32)
        if with_dq:
            dq_scratch[row, :, queries] += jax.lax.dot_general(
                kt_ref[:, keys], dst, _NN,
                preferred_element_type=jnp.float32)   # [d, queries]

    _bwd_strips(flags, kinds, qi_ref[t] * block_q, ki_ref[t] * block_k,
                block_q, block_k, sub, step)

    if with_dq:
        @pl.when((flags & _DQ_DONE) != 0)
        def _dq_done():
            dq_ref[row] = (dq_scratch[row] * scale).astype(dq_ref.dtype)

    @pl.when((flags & _LAST) != 0)
    def _finalize():
        dk_ref[...] = (dk_scratch[:] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scratch[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(qi_ref, ki_ref, flag_ref, q_ref, k_ref, kt_ref,
                         v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                         dq_scratch, *, scale: float, kinds: tuple,
                         block_q: int, block_k: int, sub: int | None):
    """dq^T of one q tile over its k tiles, k innermost: the split
    path's second call."""
    t = pl.program_id(2)
    flags = flag_ref[t]

    @pl.when((flags & _FIRST) != 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    def step(keys, queries, mask_at):
        _, dst = _bwd_tile(
            q_ref[queries, :], k_ref[keys, :], v_ref[keys, :],
            do_ref[queries, :], lse_ref[:, queries], delta_ref[:, queries],
            scale=scale, mask=_mask_of(keys, queries, mask_at))
        dq_scratch[:, queries] += jax.lax.dot_general(
            kt_ref[:, keys], dst, _NN, preferred_element_type=jnp.float32)

    _bwd_strips(flags, kinds, qi_ref[t] * block_q, ki_ref[t] * block_k,
                block_q, block_k, sub, step)

    @pl.when((flags & _LAST) != 0)
    def _finalize():
        dq_ref[...] = (dq_scratch[:] * scale).astype(dq_ref.dtype)


def backward_path(sq: int, d: int, n_rep: int, dtype) -> str:
    """"fused" where dq^T of a GQA group, in float32 scratch beside the
    two buffers of its output block, fits `_DQ_VMEM_BYTES`; else
    "split"."""
    held = n_rep * sq * d * (4 + 2 * jnp.dtype(dtype).itemsize)
    return "fused" if held <= _DQ_VMEM_BYTES else "split"


def _flash_backward(q, k, v, out, lse, g, *, causal: bool,
                    scale: float | None, block_q: int | None,
                    block_k: int | None):
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    n_rep = h // hk
    if scale is None:
        scale = d ** -0.5
    block_q, block_k = _blocks(sq, sk, block_q, block_k)
    nq, nk = sq // block_q, sk // block_k
    path = backward_path(sq, d, n_rep, q.dtype)
    fused = path == "fused"
    # the path is fixed here, as the step is traced: the process's own
    # record of its programs says which (a train worker's first step
    # record, beside `lora_step`)
    process_log().chose("flash_backward", path=path, block_q=block_q,
                        block_k=block_k, n_rep=n_rep, seq=sq, head_dim=d)

    heads = _Heads(d)
    kt = k.transpose(0, 2, 3, 1)                      # [b, hk, d, sk]
    # delta_i = rowsum(dO * O): cheap bandwidth-bound XLA prologue
    delta = jnp.einsum("bshd,bshd->bhs", g.astype(jnp.float32),
                       out.astype(jnp.float32))
    # rows of lanes, a q tile each: a block is [1, block_q]
    lse = lse.reshape(b, h * nq, 1, block_q)
    delta = delta.reshape(b, h * nq, 1, block_q)
    operands = (heads.view(q), heads.view(k), kt, heads.view(v),
                heads.view(g), lse, delta)

    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_BYTES)
    kw = dict(scale=scale, block_q=block_q, block_k=block_k,
              sub=_strips(sq, sk, block_q, block_k))

    def in_specs(q_tile, k_tile):
        """`*_tile(*grid indices and tables) -> (batch, head, tile)`."""
        row = pl.BlockSpec((None, None, 1, block_q), _tile_row(q_tile, nq))
        return [heads.spec(block_q, d, q_tile),
                heads.spec(block_k, d, k_tile),
                pl.BlockSpec((None, None, d, block_k), _key_cols(k_tile)),
                heads.spec(block_k, d, k_tile),
                heads.spec(block_q, d, q_tile), row, row]

    def q_tile(bi, hi, t, ki, gi, qi, flags):
        return bi, hi * n_rep + gi[t], qi[t]

    def k_tile(bi, hi, t, ki, gi, qi, flags):
        return bi, hi, ki[t]

    tables, kinds = _live_tiles(nq, nk, block_q, block_k, causal,
                                k_major=True, group=n_rep)
    dq_t = jax.ShapeDtypeStruct((b, h * nq, d, block_q), q.dtype)
    bwd = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, nq=nq, with_dq=fused,
                          kinds=kinds, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, hk, tables[0].shape[0]),
            in_specs=in_specs(q_tile, k_tile),
            out_specs=(
                [pl.BlockSpec((None, n_rep * nq, d, block_q),
                              lambda bi, hi, *_: (bi, hi, 0, 0))] * fused
                + [heads.spec(block_k, d, k_tile)] * 2),
            scratch_shapes=(
                [pltpu.VMEM((n_rep * nq, d, block_q), jnp.float32)] * fused
                + [pltpu.VMEM((block_k, d), jnp.float32)] * 2)),
        out_shape=([dq_t] * fused
                   + [heads.shape(b, sk, hk, d, k.dtype),
                      heads.shape(b, sk, hk, d, v.dtype)]),
        compiler_params=params,
        interpret=_interpret(),
    )
    with jax.named_scope("flash_bwd_dkv"):
        *dq, dk, dv = bwd(*tables, *operands)

    if not fused:
        def q_tile(bi, hi, t, qi, ki, flags):
            return bi, hi, qi[t]

        def k_tile(bi, hi, t, qi, ki, flags):
            return bi, hi // n_rep, ki[t]

        tables, kinds = _live_tiles(nq, nk, block_q, block_k, causal,
                                    k_major=False)
        bwd_dq = pl.pallas_call(
            functools.partial(_flash_bwd_dq_kernel, kinds=kinds, **kw),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(b, h, tables[0].shape[0]),
                in_specs=in_specs(q_tile, k_tile),
                out_specs=pl.BlockSpec((None, None, d, block_q),
                                       _tile_row(q_tile, nq)),
                scratch_shapes=[pltpu.VMEM((d, block_q), jnp.float32)]),
            out_shape=dq_t,
            compiler_params=params,
            interpret=_interpret(),
        )
        with jax.named_scope("flash_bwd_dq"):
            dq = [bwd_dq(*tables, *operands)]

    # dq^T [b, (h, q tile), d, block_q] -> [b, sq, h, d]
    dq = dq[0].reshape(b, h, nq, d, block_q).transpose(0, 2, 4, 1, 3)
    return (dq.reshape(b, sq, h, d), heads.unview(dk, hk),
            heads.unview(dv, hk))


# ------------------------------------------------------------ public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None):
    """Blocks left None are `default_blocks` of the lengths."""
    out, _ = _flash_forward(q, k, v, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k)
    return out


def _fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _flash_forward(q, k, v, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k)
    return out, (q, k, v, out, lse)


def _bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k)


flash_attention.defvjp(_fwd, _bwd)
