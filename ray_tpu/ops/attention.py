"""Attention: XLA reference implementation + Pallas flash kernel for TPU.

The reference framework has no attention op of its own (torch supplies
it); here it is a core op. Two paths:

* `dot_product_attention(..., impl="xla")` — jnp einsum path, numerically
  exact, runs anywhere (CPU tests, interpret mode).
* `impl="flash"` — Pallas TPU kernel (ray_tpu/ops/pallas/flash_attention.py),
  blockwise online-softmax, O(seq) memory, only the tiles causality
  leaves; its tile a function of the shape.

`impl="auto"` picks flash on TPU for long sequences, xla otherwise.
GQA (n_kv_heads < n_heads) handled in both paths.

Serving reads a stacked KV cache through `cached_attention` (shared by
`models/llama.py`, `granite_hybrid.py`, `evabyte.py` and, for its full
layers, `models/laguna.py`): a decode step with per-row depths on a TPU
is `decode_attention` (ops/pallas/decode_attention.py; laguna's window
layers call it over their ring in its `ring` mode), everything else the
masked XLA form, which holds a layer's scores whole. A model whose
chunks meet a cache too deep for that, or too much of it that no query
sees, calls ops/pallas/gqa_chunk_attention.py itself, which decides from
what its input says about each key column: `models/laguna.py` (a full
layer by position, a sliding one over its ring and the chunk) and
`models/evabyte.py` (the leaf as found, then the chunk's own rows and
the summaries it made: two calls and a merge). The latent models' chunks
take ops/pallas/latent_attention.py (`models/dots3_note.py`,
`models/kimi_k2.py`), which takes a mask.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.rope import apply_rope

NEG_INF = -1e30


def _on_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :],
                            (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True,
                  segment_ids: jax.Array | None = None,
                  scale: float | None = None) -> jax.Array:
    """q: [b, sq, h, d]; k/v: [b, sk, hk, d] with h % hk == 0."""
    b, sq, h, d = q.shape
    hk = k.shape[2]
    k = _repeat_kv(k, h // hk)
    v = _repeat_kv(v, h // hk)
    if scale is None:
        scale = d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    sk = k.shape[1]
    if causal:
        # offset supports sq != sk (e.g. ring attention shards / decoding)
        q_pos = jnp.arange(sq)[:, None] + (sk - sq)
        k_pos = jnp.arange(sk)[None, :]
        mask = q_pos >= k_pos
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    if segment_ids is not None:
        seg_mask = (segment_ids[:, :, None] == segment_ids[:, None, :])
        logits = jnp.where(seg_mask[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@functools.partial(jax.jit, static_argnames=("causal", "impl", "scale",
                                             "block_q", "block_k"))
def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          causal: bool = True,
                          segment_ids: jax.Array | None = None,
                          scale: float | None = None,
                          impl: str = "auto",
                          block_q: int | None = None,
                          block_k: int | None = None) -> jax.Array:
    """`block_q` / `block_k`: the flash kernel's tile; left None it is
    `flash_attention.default_blocks` of the lengths."""
    if impl == "auto":
        impl = ("flash" if _on_tpu() and q.shape[1] >= 1024
                and segment_ids is None else "xla")
    if impl == "flash":
        from ray_tpu.ops.pallas.flash_attention import flash_attention

        def kernel(q, k, v):
            return flash_attention(q, k, v, causal=causal, scale=scale,
                                   block_q=block_q, block_k=block_k)

        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty or mesh.size == 1:
            return kernel(q, k, v)
        # GSPMD cannot partition a Mosaic kernel (the chip's compiler
        # says so: "wrap the call in a shard_map"), so under a mesh the
        # kernel runs per shard. Rows and heads attend independently:
        # splitting batch and heads needs no collective. The sequence
        # stays whole; a `seq` axis is ring attention's job.
        from ray_tpu.parallel.mesh import spec_for

        q_spec = spec_for(("batch", None, "heads", None), mesh=mesh)
        kv_spec = spec_for(("batch", None, "kv_heads", None), mesh=mesh)
        return jax.shard_map(kernel, mesh=mesh,
                             in_specs=(q_spec, kv_spec, kv_spec),
                             out_specs=q_spec, check_vma=False)(q, k, v)
    return xla_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                        scale=scale)


def decode_block_len(kv_heads: int, head_dim: int, max_len: int,
                     dtype, mesh) -> int | None:
    """Positions in one block of the decode kernel
    (ops/pallas/decode_attention.py) for a cache of this shape under
    `mesh` (a mesh or an abstract one; kv heads split as the rules say),
    or None where `cached_attention` reads the whole layer on the XLA
    path. A block takes all of a device's kv heads of a row and about
    1 MiB of K (as much of V; both double-buffered, 4 MiB of VMEM): a
    live row's range is rarely under a few hundred positions and ends
    inside a block at either side, so larger blocks read more than they
    save in grid steps (0.16 us a step against 2.8 us a block, PERF.md,
    PR 31), and smaller ones gain nothing measurable."""
    if not _on_tpu():
        return None
    kv_heads //= _kv_head_shards(mesh)
    block = 128
    while (max_len % (2 * block) == 0 and 2 * block * kv_heads * head_dim
           * jnp.dtype(dtype).itemsize <= 2 ** 20):
        block *= 2
    return block if max_len % block == 0 else None


def decode_read_block(cfg, mesh) -> int | None:
    """A model module's `decode_read_block` where every attention layer
    caches K and V of `cfg.n_kv_heads` x `cfg.head_dim`: positions in a
    block of the decode step's K and V reads under `mesh`, or None where
    a step reads a layer's whole depth: what serve/llm.py counts
    `decode_kv_positions_read` in."""
    return decode_block_len(cfg.n_kv_heads, cfg.head_dim, cfg.max_seq_len,
                            cfg.dtype, mesh)


def _kv_head_shards(mesh) -> int:
    if mesh.empty:
        return 1
    from ray_tpu.parallel.mesh import spec_for

    axes = spec_for(("kv_heads",), mesh=mesh)[0] or ()
    axes = (axes,) if isinstance(axes, str) else axes
    return math.prod(mesh.shape[a] for a in axes)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     li, start: jax.Array, length: jax.Array, *,
                     scale: float, block_len: int,
                     new_kv: tuple[jax.Array, jax.Array] | None = None,
                     ring: bool = False):
    """The decode kernel on this device's kv heads: q ``[b, kv_heads,
    group, hd]`` against layer `li` of the stacked caches, row r over
    positions ``[start[r], length[r]]`` read in blocks of `block_len`.
    With `new_kv`, the step's key and value ``[b, kv_heads, hd]``, the
    kernel attends to them at ``length[r]`` and leaves them written
    there: the return is then (out, k_cache, v_cache), the stacks
    updated in place, and the caller writes nothing. With `ring` the
    caches are a window layer's ring, `block_len` deep (the kernel's
    docstring).
    With head_dim under a lane row (64, the hybrid model) the compiler
    holds V with positions minor, ``[hd, len]`` like K, and a kernel
    that takes V as declared costs a re-laid copy of the whole stack a
    step (1.07 GB for 32 slots x 4096; sandbox compile, PR 31): the
    kernel is then given V in that order, which is no operation (and
    takes no `new_kv`: a swapped view is not the stack to alias).
    Under a mesh the call runs per shard, as the flash kernel does
    (`dot_product_attention`): kv heads attend independently, and each
    shard writes its own."""
    from ray_tpu.ops.pallas.decode_attention import (
        decode_attention as kernel)

    v_positions_minor = q.shape[-1] < 128
    if v_positions_minor:
        assert new_kv is None, q.shape
        v_cache = jnp.swapaxes(v_cache, 3, 4)

    def call(q, k_cache, v_cache, li, start, length, *new_kv):
        return kernel(q, k_cache, v_cache, li, start, length, scale=scale,
                      block_len=block_len,
                      v_positions_minor=v_positions_minor,
                      new_kv=new_kv or None, ring=ring)

    new_kv = new_kv or ()
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return call(q, k_cache, v_cache, li, start, length, *new_kv)
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import spec_for

    q_spec = spec_for(("batch", "kv_heads", None, None), mesh=mesh)
    k_spec = spec_for(("layers", "batch", "kv_heads", "head_dim", None),
                      mesh=mesh)
    v_spec = k_spec if v_positions_minor else spec_for(
        ("layers", "batch", "kv_heads", None, "head_dim"), mesh=mesh)
    rows = spec_for(("batch",), mesh=mesh)
    new_spec = spec_for(("batch", "kv_heads", None), mesh=mesh)
    return jax.shard_map(
        call, mesh=mesh,
        in_specs=(q_spec, k_spec, v_spec, P(), rows, rows)
        + (new_spec,) * len(new_kv),
        out_specs=(q_spec, k_spec, v_spec) if new_kv else q_spec,
        check_vma=False)(q, k_cache, v_cache, li, start, length, *new_kv)


def _write_rows(k_cache, v_cache, kk, vv, li, cache_len):
    """The s new rows kk, vv ``[b, s, kv_heads, hd]`` written at
    positions [cache_len[row], cache_len[row] + s) of layer `li` of the
    stacked caches, in place."""
    def write(k_cache, v_cache, kk, vv, row, at):
        # kk, vv [rows, s, nkv, hd] -> the cache's orders, at position
        # `at` of rows [row, row + rows) of layer li
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, kk.transpose(0, 2, 3, 1)[None], (li, row, 0, 0, at))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, vv.transpose(0, 2, 1, 3)[None], (li, row, 0, at, 0))
        return k_cache, v_cache

    if jnp.ndim(cache_len) == 0:
        # whole batch advances together (left-padded batched decode,
        # batch-1 prefill): one block of the carry
        return write(k_cache, v_cache, kk, vv, 0, cache_len)
    # per-row write offsets (continuous-batching slots: each row is an
    # independent request at its own depth, vLLM-style). One small
    # in-place write per row, the row cut out BEFORE it is transposed:
    # as one scatter, as a vmap of dynamic_update_slice over the batch
    # axis, or cut from the transposed batch, the compiler re-lays the
    # carry out for the update's layout and copies the whole cache into
    # and out of the loop (PERF.md, PR 25; tests/test_chip_compile.py
    # holds the step to it).
    for r in range(kk.shape[0]):
        k_cache, v_cache = write(k_cache, v_cache, kk[r:r + 1], vv[r:r + 1],
                                 r, cache_len[r])
    return k_cache, v_cache


def cached_attention(q: jax.Array, kk: jax.Array, vv: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array, li,
                     cache_len, abs_positions: jax.Array, start, *,
                     scale: float, rope: tuple | None = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Attention of s new tokens against layer `li` of a stacked KV
    cache, for a decode step (s = 1) or a prefill chunk (s > 1).

    q: [b, s, heads, hd]; kk, vv: [b, s, kv_heads, hd], as projected.
    k_cache ``[layers, b, kv_heads, hd, len]`` and v_cache ``[layers, b,
    kv_heads, len, hd]`` are the STACKED cache, carried through the
    caller's layer loop: the s new rows are written at positions
    [cache_len[row], cache_len[row] + s) of layer `li` in place and that
    layer is read once; nothing else of the cache is read, written or
    copied (one exception, below: a row that holds no request).
    `cache_len` is a scalar (the batch in lock-step) or [b] (per-row
    depths). `abs_positions` [b, s] are the slots the new rows
    land in, used for masking; `start` [b] (or None) hides the left-pad
    slots of each row. `scale` multiplies the scores. `rope` is (cos,
    sin, positions) for rotary embeddings on q and k, or None for a
    model without position embeddings.

    A decode step with per-row depths on a TPU reads, for each row, only
    the blocks of K and V that overlap ``[start[row], cache_len[row]]``
    (`decode_attention`; a row with ``cache_len < start``, which is how
    the engine marks a slot that holds no request, reads nothing and
    gets zeros). Where the head is a whole lane row (``hd % 128 == 0``)
    that kernel is also the step's write (PR 48): it is handed the new
    row, attends to it at ``cache_len[row]`` and leaves it written in
    the block it holds, so this function issues no write of its own; a
    row that holds no request is then not written at all (the XLA
    writes put a row computed from a token no request owns at its
    clamped depth, position 0: nobody reads it, and `insert_row` lays a
    request's own rows into a slot before the slot is read again).
    With a head under a lane row (64, the hybrid model: the kernel is
    handed V in K's order, not the stack itself), for prefill chunks
    (s > 1), the lock-step batch (a scalar `cache_len`), on every other
    platform and for the shapes `decode_block_len` turns down, the rows
    are written by `_write_rows`, one small `dynamic_update_slice` of K
    and of V a row; the last four of those read the layer whole and
    mask, below. Which it is follows from the shapes and the platform:
    nothing selects it.
    Returns (attn [b, s, heads * hd], k_cache, v_cache)."""
    b, s, nh, hd = q.shape
    nkv = kk.shape[2]
    group = nh // nkv
    if rope is not None:
        with jax.named_scope("attn_qkv"):
            cos, sin, positions = rope
            q = apply_rope(q, cos, sin, positions)
            kk = apply_rope(kk, cos, sin, positions)
    # A decode step with per-row depths on a TPU: the decode kernel, in
    # blocks of `block` positions. Where the head is a whole lane row the
    # kernel also writes the new row, into the block it holds anyway.
    block = decode_block_len(
        nkv, hd, v_cache.shape[3], v_cache.dtype,
        jax.sharding.get_abstract_mesh()) if (
            s == 1 and jnp.ndim(cache_len) == 1) else None
    kernel_writes = block is not None and hd % 128 == 0
    if not kernel_writes:
        with jax.named_scope("kv_update"):
            k_cache, v_cache = _write_rows(k_cache, v_cache, kk, vv, li,
                                           cache_len)
    with jax.named_scope("attn"):
        # Over kv-head groups, K and V as they lie in the cache: the
        # group's query heads are rows of one matmul per kv head, so no
        # GQA repeat of K or V exists anywhere.
        if block is not None:
            new_kv = None
            if kernel_writes:
                # v held as projected, as the caller holds q and k: left
                # free, the compiler makes wv's product give it a kv head
                # at a time for the kernel's operand and copies the layer
                # of wv transposed for that, 4 MiB a layer
                # (tests/test_chip_compile.py::
                # test_serve_step_reads_weights_where_they_lie)
                new_kv = (kk[:, 0], jax.lax.optimization_barrier(
                    vv.reshape(b, nkv * hd)).reshape(b, nkv, hd))
            attn = decode_attention(
                q.reshape(b, nkv, group, hd), k_cache, v_cache, li,
                jnp.zeros_like(cache_len) if start is None else start,
                cache_len, scale=scale, block_len=block, new_kv=new_kv)
            if kernel_writes:
                attn, k_cache, v_cache = attn
            return attn.reshape(b, s, nh * hd), k_cache, v_cache
        k_l = jax.lax.dynamic_index_in_dim(k_cache, li, 0, keepdims=False)
        v_l = jax.lax.dynamic_index_in_dim(v_cache, li, 0, keepdims=False)
        max_len = v_l.shape[2]
        qg = q.reshape(b, s, nkv, group, hd).transpose(0, 2, 1, 3, 4)
        logits = jnp.einsum("bnqgd,bndk->bnqgk", qg, k_l,
                            preferred_element_type=jnp.float32) * scale
        # mask: key slot j visible iff start <= j <= query slot
        k_pos = jnp.arange(max_len)[None, :]
        mask = k_pos[:, None, :] <= abs_positions[..., None]  # [b, s, max_len]
        if start is not None:
            mask = mask & (k_pos[:, None, :] >= start[:, None, None])
        logits = jnp.where(mask[:, None, :, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(v_l.dtype)
        attn = jnp.einsum("bnqgk,bnkd->bqngd", probs, v_l).reshape(
            b, s, nh * hd)
    return attn, k_cache, v_cache
