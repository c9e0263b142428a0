"""Flash attention forward + backward kernels (Pallas/TPU).

Blockwise online-softmax attention: O(seq) memory, causal block skipping,
GQA via block-index mapping (no KV repeat materialization). Grid is
(batch, heads, q_blocks, k_blocks) with the k axis innermost so the
accumulator lives in VMEM scratch across k steps (see
/opt/skills/guides/pallas_guide.md, double-buffering pattern — pallas
pipelines the HBM->VMEM block copies automatically).

Backward is the standard two-kernel flash bwd (Dao 2023): the forward
saves only (q, k, v, out, lse); `delta = rowsum(dO * O)` is an XLA
prologue; one kernel accumulates dQ with k innermost, a second
accumulates dK/dV with q innermost, so no O(s^2) tensor is ever
materialized (the previous fallback re-ran dense XLA attention).

The reference framework has no attention kernels of its own (torch
supplies them); this is TPU-native core-op territory.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# lse sentinel for fully-masked rows: exp(s - BIG) == 0 for any finite s
_MASKED_LSE = 1e30
_LANES = 128


def _interpret() -> bool:
    """Interpret mode only where the CPU was asked for by name
    (JAX_PLATFORMS=cpu, as the tests set it). It is never inferred from
    the backend jax happened to end up with: a process that was meant to
    run on the chip and fell back to the CPU must fail to lower the
    kernel, not emulate it quietly."""
    return jax.config.jax_platforms == "cpu"


# --------------------------------------------------------------- forward
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scratch, l_scratch, acc_scratch, *,
                      scale: float, causal: bool,
                      block_q: int, block_k: int, num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        # Feed the MXU its native input dtype (bf16) and accumulate f32
        # via preferred_element_type — casting operands to f32 first
        # forces the multi-pass f32 matmul path (~6x slower on MXU).
        q = q_ref[0, 0]                              # [block_q, d]
        k = k_ref[0, 0]                              # [block_k, d]
        v = v_ref[0, 0]                              # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [block_q, block_k]
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scratch[:, 0:1]                    # [block_q, 1]
        l_prev = l_scratch[:, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)    # [block_q, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # [block_q, block_k]
        alpha = jnp.exp(m_prev - m_new)               # [block_q, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scratch[:, 0:1] = m_new
        l_scratch[:, 0:1] = l_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [block_q, d]
        acc_scratch[:] = acc_scratch[:] * alpha + pv

    if causal:
        # skip blocks strictly above the diagonal
        @pl.when(q_start + block_q - 1 >= k_start)
        def _run():
            _body()
    else:
        _body()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        m = m_scratch[:, 0:1]
        l = l_scratch[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m + jnp.log(l_safe), _MASKED_LSE)
        lse_ref[0, 0] = lse


def _flash_forward(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool, scale: float | None,
                   block_q: int, block_k: int):
    """Returns (out [b, sq, h, d], lse [b, h, sq])."""
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    n_rep = h // hk
    if scale is None:
        scale = d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (
        f"seq lengths ({sq},{sk}) must divide blocks ({block_q},{block_k})")
    num_q_blocks = sq // block_q
    num_k_blocks = sk // block_k
    # layout: [b, h, s, d] so the head dim is a grid axis
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (b, h, num_q_blocks, num_k_blocks)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k_blocks=num_k_blocks)
    # each kernel call sits in a named scope (HLO metadata only), so a
    # profiler trace names its custom call whatever the compiler numbers it
    fwd = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // n_rep, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
    )
    with jax.named_scope("flash_fwd"):
        out, lse = fwd(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


# -------------------------------------------------------------- backward
def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scratch, *,
                         scale: float, causal: bool,
                         block_q: int, block_k: int, num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        q = q_ref[0, 0]                               # [bq, d]
        k = k_ref[0, 0]                               # [bk, d]
        v = v_ref[0, 0]                               # [bk, d]
        do = do_ref[0, 0]                             # [bq, d]
        lse = lse_ref[0, 0]                           # [bq, 1]
        delta = delta_ref[0, 0]                       # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                          # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_scratch[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, d]

    if causal:
        @pl.when(q_start + block_q - 1 >= k_start)
        def _run():
            _body()
    else:
        _body()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scratch[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scratch, dv_scratch, *,
                          scale: float, causal: bool,
                          block_q: int, block_k: int, num_q_blocks: int):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        q = q_ref[0, 0]                               # [bq, d]
        k = k_ref[0, 0]                               # [bk, d]
        v = v_ref[0, 0]                               # [bk, d]
        do = do_ref[0, 0]                             # [bq, d]
        lse = lse_ref[0, 0]                           # [bq, 1]
        delta = delta_ref[0, 0]                       # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                          # [bq, bk]
        dv_scratch[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scratch[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, d]

    if causal:
        @pl.when(q_start + block_q - 1 >= k_start)
        def _run():
            _body()
    else:
        _body()

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scratch[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, *, causal: bool,
                    scale: float | None, block_q: int, block_k: int):
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    n_rep = h // hk
    if scale is None:
        scale = d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    num_q_blocks = sq // block_q
    num_k_blocks = sk // block_k

    qt = q.transpose(0, 2, 1, 3)                      # [b, h, sq, d]
    kt = k.transpose(0, 2, 1, 3)                      # [b, hk, sk, d]
    vt = v.transpose(0, 2, 1, 3)
    do_t = g.transpose(0, 2, 1, 3)                    # [b, h, sq, d]
    # delta_i = rowsum(dO * O): cheap bandwidth-bound XLA prologue
    delta = jnp.einsum("bshd,bshd->bhs", g.astype(jnp.float32),
                       out.astype(jnp.float32))[..., None]  # [b, h, sq, 1]

    interp = _interpret()
    bwd_dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, num_k_blocks=num_k_blocks),
        grid=(b, h, num_q_blocks, num_k_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interp,
    )
    with jax.named_scope("flash_bwd_dq"):
        dq = bwd_dq(qt, kt, vt, do_t, lse, delta)

    # dk/dv are accumulated per *query* head, then reduced over the GQA
    # group outside the kernel (grid programs may not share an output).
    bwd_dkv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, num_q_blocks=num_q_blocks),
        grid=(b, h, num_k_blocks, num_q_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interp,
    )
    with jax.named_scope("flash_bwd_dkv"):
        dk_h, dv_h = bwd_dkv(qt, kt, vt, do_t, lse, delta)

    dq = dq.transpose(0, 2, 1, 3)
    if n_rep > 1:
        dk_h = dk_h.reshape(b, hk, n_rep, sk, d).sum(axis=2)
        dv_h = dv_h.reshape(b, hk, n_rep, sk, d).sum(axis=2)
    dk = dk_h.transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv_h.transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


# ------------------------------------------------------------ public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, scale: float | None = None,
                    block_q: int = 512, block_k: int = 512):
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
