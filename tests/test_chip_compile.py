"""The main path's kernels and decode step, held to the chip's compiler.

The TPU compiler is installed where the tests run and compiles for a chip
that is described, not attached (on-chip-measurement guide §2). Interpret
mode, which every other kernel test uses, cannot see what it refuses:
a slice off the tiling, too much VMEM, a program that does not fit HBM.
Nothing runs here, so these say nothing about results or times; each
compile takes a second or two. Skipped where the topology cannot be
described.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import llama
from ray_tpu.ops.pallas import flash_attention as fa

HBM_BYTES = 16 * 2**30  # one v5e chip

# (batch, seq, heads, kv_heads, head_dim) of the attention call in a
# train step of each preset, at the batch the chip has run
SHAPES = {"1b": (4, 2048, 16, 8, 128), "410m": (8, 2048, 16, 16, 64)}


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip. The persistent compile cache is off around
    these compiles: an executable built for a described device is written
    to it but cannot be read back without the chip, and warns."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it cannot describe a v5e
            pytest.skip(f"cannot describe a v5e topology here: {e!r}")
        yield topo.devices[0]
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture
def compiled_not_interpreted(monkeypatch):
    """The tests run with JAX_PLATFORMS=cpu, which is what selects
    interpret mode; these compile the kernel itself."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)


def _flash_fwd(q, k, v, o, lse, do, bq, bk):
    return fa._flash_forward(q, k, v, causal=True, scale=None,
                             block_q=bq, block_k=bk)


def _flash_bwd_dq(q, k, v, o, lse, do, bq, bk):
    return fa._flash_backward(q, k, v, o, lse, do, causal=True, scale=None,
                              block_q=bq, block_k=bk)[0]


def _flash_bwd_dkv(q, k, v, o, lse, do, bq, bk):
    return fa._flash_backward(q, k, v, o, lse, do, causal=True, scale=None,
                              block_q=bq, block_k=bk)[1:]


@pytest.mark.parametrize("kernel", [_flash_fwd, _flash_bwd_dq,
                                    _flash_bwd_dkv],
                         ids=["fwd", "bwd_dq", "bwd_dkv"])
@pytest.mark.parametrize("preset,block_q,block_k", [
    ("1b", 512, 512),
    ("1b", 256, 1024),   # rectangular: the tile-retune axis
    ("410m", 512, 512),
])
def test_flash_kernel_compiles_for_the_chip(chip, compiled_not_interpreted,
                                            kernel, preset, block_q,
                                            block_k):
    b, s, h, hk, d = SHAPES[preset]
    on = SingleDeviceSharding(chip)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    q, o, do = (arg((b, s, h, d)) for _ in range(3))
    k, v = (arg((b, s, hk, d)) for _ in range(2))
    lse = arg((b, h, s, 1), jnp.float32)
    compiled = jax.jit(
        lambda *a: kernel(*a, block_q, block_k)).lower(
            q, k, v, o, lse, do).compile()
    # one Mosaic kernel each: the unused half of the backward is dropped
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_1b_decode_step_compiles_for_the_chip(chip):
    """The serving engine's steady-state program: one token for each of 4
    slots against a 2048-deep cache with per-row depths."""
    cfg = llama.config_for("1b", max_seq_len=2048)
    on = SingleDeviceSharding(chip)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=on), tree)

    params = place(jax.eval_shape(
        lambda key: llama.init_params(cfg, key), jax.random.PRNGKey(0)))
    cache = dict(jax.eval_shape(lambda: llama.init_kv_cache(cfg, 4)))
    cache["length"] = jax.ShapeDtypeStruct((4,), jnp.int32)
    tokens = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=on)
    compiled = jax.jit(
        lambda p, c, t: llama.decode_step(p, c, t, cfg),
        donate_argnums=(1,)).lower(params, place(cache), tokens).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < HBM_BYTES)
