"""Rotary position embeddings (RoPE): the table form every llama-shaped
model reads (`rope_frequencies`, `apply_rope`), YaRN's frequencies, and
the form for a model whose layers do not all turn alike
(`apply_partial_rope`: a leading part of each head, at frequencies and
under a factor the caller gives; models/laguna.py turns half a head
under YaRN in its full layers and the whole head, plain, in its sliding
ones)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rope_frequencies(head_dim: int, max_len: int,
                     theta: float = 10000.0) -> tuple[jax.Array, jax.Array]:
    """Precompute cos/sin tables, shape [max_len, head_dim // 2], fp32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature for a context stretched `factor`
    times: ``0.1 * mscale * ln(factor) + 1`` (1 where nothing is
    stretched). DeepSeek-V3's family multiplies the softmax scale by its
    square, taken at `mscale_all_dim`, and cos and sin by the ratio of
    the two it is given."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, original_max_len: int,
                          beta_fast: float = 32.0,
                          beta_slow: float = 1.0) -> tuple[int, int]:
    """(low, high): the rotated pairs between which YaRN blends. A pair
    below `low` turns more than `beta_fast` times inside the original
    context and keeps its frequency; one above `high` turns less than
    `beta_slow` times and is interpolated whole."""
    def pair(turns: float) -> float:
        return (dim * math.log(original_max_len / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max_len: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """Inverse frequencies [dim // 2], float32, of RoPE under YaRN
    (Peng et al. 2023, as DeepSeek-V3's modeling code has it): each
    pair's plain frequency ``theta^(-2i/dim)`` blended with the same
    divided by `factor`, by a linear ramp over the pairs from 0 at `low`
    to 1 at `high` (`yarn_correction_range`). Constants of the shapes
    alone, so computed on the host."""
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    low, high = yarn_correction_range(dim, theta, original_max_len,
                                      beta_fast, beta_slow)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / (high - low if high > low else 0.001), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: jax.Array | None = None) -> jax.Array:
    """Rotate pairs (split-half convention, llama-style).

    x: [..., seq, heads, head_dim]; cos/sin: [max_len, head_dim//2] or
    already gathered [..., seq, head_dim//2]. positions: [..., seq] int32
    (defaults to arange, which is the common pre-fill case).
    """
    seq = x.shape[-3]
    if positions is None and cos.ndim == 2:
        cos = cos[:seq]
        sin = sin[:seq]
    elif positions is not None:
        cos = jnp.take(cos, positions, axis=0)
        sin = jnp.take(sin, positions, axis=0)
    # broadcast over heads: [..., seq, 1, head_dim//2]
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    dtype = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(dtype)


def apply_partial_rope(x: jax.Array, positions: jax.Array, inv_freq,
                       factor: float = 1.0) -> jax.Array:
    """Rotate the FIRST ``2 * len(inv_freq)`` numbers of each head
    (rotated halves inside that part: its first half against its
    second) and pass the rest as projected. x: [..., seq, heads,
    head_dim]; positions: [..., seq]; `inv_freq` [n] float32 (plain
    ``theta^(-2i/dim)`` or `yarn_inv_freq`). cos and sin are multiplied
    by `factor`: YaRN's `attention_factor` where a config puts the
    temperature there and not on the softmax scale. Angles are computed
    from the positions in float32, so no table bounds the depth."""
    inv_freq = jnp.asarray(inv_freq, jnp.float32)
    half = inv_freq.shape[0]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = (jnp.cos(ang) * factor)[..., None, :]
    sin = (jnp.sin(ang) * factor)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    return jnp.concatenate(
        [t.astype(x.dtype) for t in turned] + [x[..., 2 * half:]], axis=-1)
