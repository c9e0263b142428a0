"""ops/pallas/ssm_update.py, interpreted, against the plain form it
stands in for (ops/ssm.ssm_step on the layer cut from the stack): y and
the written layer to float32's rounding, every other layer of the stack
bit for bit as it was, and the plain form kept for what the kernel does
not take."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import granite_hybrid
from ray_tpu.ops import attention
from ray_tpu.ops.pallas import ssm_update as su
from ray_tpu.ops.ssm import ssm_step

F32 = jnp.float32

# (layers, rows, heads, p, n)
SIZES = {"cell-32x64x64x128": (3, 32, 64, 64, 128),
         "48-heads": (3, 2, 48, 64, 128),
         "p-16": (4, 3, 16, 16, 128),
         "n-256-heads-4": (2, 2, 4, 8, 256)}


def _inputs(layers, rows, heads, p, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        states=jax.random.normal(ks[0], (layers, rows, heads, p, n), F32),
        x=jax.random.normal(ks[1], (rows, heads, p), F32),
        dt=jax.nn.softplus(jax.random.normal(ks[2], (rows, heads), F32)),
        a=-jnp.exp(jax.random.uniform(ks[3], (heads,), F32, 0.0, 2.77)),
        b=jax.random.normal(ks[4], (rows, n), F32),
        c=jax.random.normal(ks[5], (rows, n), F32),
        d=jax.random.normal(ks[6], (heads,), F32))


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("size", list(SIZES))
def test_kernel_equals_the_plain_form_and_touches_one_layer(size, where):
    layers = SIZES[size][0]
    li = {"first": 0, "middle": layers // 2, "last": layers - 1}[where]
    t = _inputs(*SIZES[size])
    hb = su.heads_per_block(*SIZES[size][2:], F32)
    assert hb and SIZES[size][2] % hb == 0
    small = (t["x"], t["dt"], t["a"], t["b"], t["c"], t["d"])
    want_y, want_state = ssm_step(t["states"][li], *small)
    # the layer index as the engine's loop gives it: a traced scalar
    y, states = jax.jit(lambda s, i: su.ssm_update(
        s, i, *small, heads_block=hb))(t["states"], jnp.int32(li))
    assert y.shape == want_y.shape and y.dtype == F32
    assert states.shape == t["states"].shape and states.dtype == F32
    # one rounding of a sum of n products of magnitude |new| |c|
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=2e-4)
    np.testing.assert_allclose(states[li], want_state, rtol=1e-6, atol=1e-6)
    others = [i for i in range(layers) if i != li]
    assert others
    np.testing.assert_array_equal(states[jnp.array(others)],
                                  t["states"][jnp.array(others)])


def test_blocks_are_chosen_from_the_shape():
    """The largest whole-sublane divisor of the heads within 2 MiB, all
    heads where they are few, and None for what the kernel does not
    take: a state below float32, p off the sublanes, n off the lanes,
    heads that neither fit one block nor divide into whole sublanes."""
    assert su.heads_per_block(64, 64, 128, F32) == 64
    assert su.heads_per_block(128, 64, 128, F32) == 64
    assert su.heads_per_block(48, 64, 128, F32) == 48
    assert su.heads_per_block(24, 128, 256, F32) == 8
    assert su.heads_per_block(4, 8, 256, F32) == 4
    assert su.heads_per_block(64, 64, 128, jnp.bfloat16) is None
    assert su.heads_per_block(64, 60, 128, F32) is None
    assert su.heads_per_block(64, 64, 64, F32) is None
    assert su.heads_per_block(7, 256, 512, F32) is None


@pytest.mark.parametrize("shape,kernel", [
    ((2, 2, 16, 16, 128), True),      # a shape the kernel takes
    ((2, 2, 16, 16, 64), False),      # n off the lanes: the plain form
    ((2, 2, 16, 12, 128), False),     # p off the sublanes
], ids=["taken", "n-64", "p-12"])
def test_the_model_asks_the_kernel_only_for_what_it_takes(monkeypatch, shape,
                                                          kernel):
    """`granite_hybrid._state_update` on a TPU (here: told it is on one,
    the kernel interpreted) takes the kernel for a shape it takes and
    the plain form for any other, with the same result; off a TPU it
    never asks."""
    t = _inputs(*shape, seed=3)
    small = (t["x"], t["dt"], t["a"], t["b"], t["c"], t["d"])
    calls = []
    real = su.ssm_update
    monkeypatch.setattr(su, "ssm_update", lambda *a, **kw: (
        calls.append(kw["heads_block"]), real(*a, **kw))[1])
    want_y, want = granite_hybrid._state_update(t["states"], 1, *small)
    assert not calls
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    y, states = granite_hybrid._state_update(t["states"], 1, *small)
    assert bool(calls) == kernel
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=2e-4)
    np.testing.assert_allclose(states, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(states[0], t["states"][0])
