"""Attention: XLA reference implementation + Pallas flash kernel for TPU.

The reference framework has no attention op of its own (torch supplies
it); here it is a core op. Two paths:

* `dot_product_attention(..., impl="xla")` — jnp einsum path, numerically
  exact, runs anywhere (CPU tests, interpret mode).
* `impl="flash"` — Pallas TPU kernel (ray_tpu/ops/pallas/flash_attention.py),
  blockwise online-softmax, O(seq) memory, causal-block skipping.

`impl="auto"` picks flash on TPU for long sequences, xla otherwise.
GQA (n_kv_heads < n_heads) handled in both paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


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
                          block_q: int = 512, block_k: int = 512) -> jax.Array:
    if impl == "auto":
        on_tpu = any(d.platform == "tpu" for d in jax.devices())
        impl = ("flash" if on_tpu and q.shape[1] >= 1024
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
