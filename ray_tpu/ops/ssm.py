"""Mamba-2 (SSD) operators in plain jax.numpy: the causal depthwise
convolution with a carried tail, the one-token state update, and the
chunked scan with a carried state.

One recurrence, per head h with a scalar decay (Dao & Gu 2024, "state
space duality"):

    H_t = exp(dt_t * A_h) * H_{t-1} + dt_t * x_t (x) B_t     H: [p, n]
    y_t = H_t C_t + D_h * x_t

`ssm_step` is that line for one token; `ssd_scan` is the same recurrence
over s tokens in its chunked matrix form (quadratic inside a chunk,
a short scan over chunk states between them), and takes and returns H,
so a prompt may arrive in pieces. B and C are one group shared by all
heads. The state and everything that feeds an exponential are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def causal_conv(x: jax.Array, tail: jax.Array, w: jax.Array, b: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution. x: [b, s, c]; tail: [b, k-1, c], the
    k-1 inputs that preceded x (zeros at the start of a sequence);
    w: [k, c], w[k-1] on the current input; b: [c].
    Returns (y [b, s, c], the new tail: the last k-1 inputs seen)."""
    k, s = w.shape[0], x.shape[1]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = b.astype(F32)
    for i in range(k):
        y = y + full[:, i:i + s].astype(F32) * w[i].astype(F32)
    return y.astype(x.dtype), full[:, s:]


def ssm_step(state: jax.Array, x: jax.Array, dt: jax.Array, a: jax.Array,
             b: jax.Array, c: jax.Array, d: jax.Array
             ) -> tuple[jax.Array, jax.Array]:
    """One token. state: [b, h, p, n]; x: [b, h, p]; dt: [b, h] (after
    softplus); a: [h] (negative); b, c: [b, n]; d: [h].
    Returns (y [b, h, p] float32, state)."""
    x, dt, b, c = (t.astype(F32) for t in (x, dt, b, c))
    decay = jnp.exp(dt * a.astype(F32))
    new = (state.astype(F32) * decay[..., None, None]
           + (dt[..., None] * x)[..., None] * b[:, None, None, :])
    y = jnp.einsum("bhpn,bn->bhp", new, c) + d.astype(F32)[:, None] * x
    return y, new.astype(state.dtype)


def ssd_scan(state: jax.Array, x: jax.Array, dt: jax.Array, a: jax.Array,
             b: jax.Array, c: jax.Array, d: jax.Array, chunk: int
             ) -> tuple[jax.Array, jax.Array]:
    """s tokens. state: [b, h, p, n]; x: [b, s, h, p]; dt: [b, s, h];
    a: [h]; b, c: [b, s, n]; d: [h]. Returns (y [b, s, h, p] float32,
    the state after the last token). s is cut into chunks of `chunk`
    (of s itself when s is shorter); a last partial chunk is filled with
    tokens of dt = 0, which leave the state as it is."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    pad = -s % q
    x, dt, b, c = (t.astype(F32) for t in (x, dt, b, c))
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // q
    x, dt, b, c = (t.reshape(bsz, nc, q, *t.shape[2:]) for t in (x, dt, b, c))

    log_decay = jnp.cumsum(dt * a.astype(F32), axis=2)       # [b, nc, q, h]
    dtx = dt[..., None] * x                                  # [b, nc, q, h, p]
    # inside a chunk: y_i += sum_{j <= i} exp(l_i - l_j) (C_i . B_j) dtx_j
    diff = log_decay[:, :, :, None, :] - log_decay[:, :, None, :, :]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    within = jnp.exp(jnp.where(causal, diff, -jnp.inf))      # [b, nc, i, j, h]
    cb = jnp.einsum("bcin,bcjn->bcij", c, b)
    y = jnp.einsum("bcijh,bcjhp->bcihp", cb[..., None] * within, dtx)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(log_decay[:, :, -1:, :] - log_decay)    # [b, nc, q, h]
    chunk_state = jnp.einsum("bcjh,bcjhp,bcjn->bchpn", to_end, dtx, b)
    chunk_decay = jnp.exp(log_decay[:, :, -1, :])            # [b, nc, h]

    def carry(hstate, inp):
        s_c, g_c = inp
        return hstate * g_c[..., None, None] + s_c, hstate   # emits H before

    final, before = jax.lax.scan(
        carry, state.astype(F32),
        (chunk_state.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    before = before.swapaxes(0, 1)                           # [b, nc, h, p, n]
    # what the state carried into a chunk gives each of its tokens
    y = y + jnp.einsum("bcin,bchpn->bcihp", c, before) \
        * jnp.exp(log_decay)[..., None]
    y = y + d.astype(F32)[:, None] * x
    return y.reshape(bsz, nc * q, h, p)[:, :s], final.astype(state.dtype)
