"""ops/pallas/held_experts.py through ops/moe.held_experts_ffn, in
interpret mode on the CPU, against a plain float32 sum over the pairs:
every routing the function has to sum, each side of the tile's and the
product's row counts, and an expert taken in several blocks of f. What
interpret mode cannot see (VMEM, tiling) is tests/test_chip_compile.py's;
a time is the chip's (tools/held_experts_probe.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe
from ray_tpu.ops.pallas import held_experts as he

E, FIRST, HELD = 16, 4, 4       # the router's width; experts 4..7 are held
OUT = 0                         # an expert some other holder has


def _weights(d, f, dtype):
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    return ((jax.random.normal(k[0], (E, d, f)) / np.sqrt(d)).astype(dtype),
            (jax.random.normal(k[1], (E, d, f)) / np.sqrt(d)).astype(dtype),
            (jax.random.normal(k[2], (E, f, d)) / np.sqrt(f)).astype(dtype))


def _routing(kind, T, k, x):
    """(chosen [T, k], weights [T, k]) of one case."""
    weights = jax.random.uniform(jax.random.PRNGKey(2), (T, k), jnp.float32,
                                 0.1, 1.0)
    if kind == "routed":
        router = jax.random.normal(jax.random.PRNGKey(5), (x.shape[1], E))
        _, chosen, weights = moe.route_sigmoid_topk(
            x, router / np.sqrt(x.shape[1]), jnp.zeros((E,)), k)
        return chosen, weights
    if kind == "one-expert":        # every pair of every token
        return jnp.full((T, k), FIRST + 1, jnp.int32), weights
    if kind == "twice":             # a token names expert 5 twice, and 6
        return jnp.tile(jnp.array([FIRST + 1, FIRST + 1, FIRST + 2],
                                  jnp.int32)[:k], (T, 1)), weights
    if kind == "none":
        return jnp.full((T, k), OUT, jnp.int32), weights
    rows = int(kind)                # a group of exactly `rows` rows
    chosen = np.full((T * k,), OUT, np.int32)
    chosen[:rows] = FIRST + 2       # pairs 0..rows-1: tokens in order
    return jnp.asarray(chosen.reshape(T, k)), weights


def _per_pair_sum(x, chosen, weights, w, first, held, valid):
    """The layer's definition, a pair at a time in float32, and the three
    counts, for tiles of `bm` rows."""
    x = np.asarray(x, np.float32)
    wg, wu, wd = (np.asarray(a, np.float32) for a in w)
    T, k = chosen.shape
    y = np.zeros(x.shape, np.float32)
    counts = np.zeros((held,), int)
    silu = lambda a: a / (1.0 + np.exp(-a))
    for t in range(T):
        for j in range(k):
            e = int(chosen[t, j]) - first
            if 0 <= e < held and (valid is None or bool(valid[t])):
                y[t] += float(weights[t, j]) * (
                    (silu(x[t] @ wg[e]) * (x[t] @ wu[e])) @ wd[e])
                counts[e] += 1
    bm = 16 if T <= 64 else 128
    return y, counts.sum(), (counts > 0).sum(), sum(-(-c // bm)
                                                    for c in counts)


CASES = [
    # id, T, k, routing, dtype, d, f, first, held, every, blocks of f
    ("routed-decode-32", 32, 3, "routed", "float32", 32, 16, FIRST, HELD,
     0, 1),
    ("routed-chunk-200", 200, 3, "routed", "float32", 32, 16, FIRST, HELD,
     0, 1),
    ("routed-bf16-decode", 32, 3, "routed", "bfloat16", 32, 16, FIRST, HELD,
     0, 1),
    ("routed-bf16-chunk", 200, 3, "routed", "bfloat16", 32, 16, FIRST, HELD,
     0, 1),
    ("one-expert-decode", 32, 3, "one-expert", "float32", 32, 16, FIRST,
     HELD, 0, 1),
    ("one-expert-chunk", 150, 3, "one-expert", "bfloat16", 32, 16, FIRST,
     HELD, 0, 1),
    ("twice", 40, 3, "twice", "float32", 32, 16, FIRST, HELD, 0, 1),
    ("twice-bf16-chunk", 70, 3, "twice", "bfloat16", 32, 16, FIRST, HELD,
     0, 1),
    ("no-pair-held", 50, 3, "none", "float32", 32, 16, FIRST, HELD, 0, 1),
    ("no-pair-held-chunk", 100, 3, "none", "bfloat16", 32, 16, FIRST, HELD,
     0, 1),
    ("valid-mask", 50, 3, "routed", "float32", 32, 16, FIRST, HELD, 3, 1),
    ("valid-mask-chunk", 130, 3, "routed", "bfloat16", 32, 16, FIRST, HELD,
     5, 1),
    ("no-row-valid", 50, 3, "routed", "float32", 32, 16, FIRST, HELD, 1, 1),
    ("first-0-all-held", 50, 4, "routed", "float32", 32, 16, 0, E, 0, 1),
    ("first-12-last-held", 50, 4, "routed", "float32", 32, 16, 12, 4, 0, 1),
    ("first-16-never-chosen", 50, 4, "routed", "float32", 32, 16, 16, 4, 0,
     1),
    ("f-in-2-blocks", 32, 3, "routed", "float32", 32, 256, FIRST, HELD, 0, 2),
    ("f-in-3-blocks-chunk", 200, 3, "routed", "bfloat16", 32, 384, FIRST,
     HELD, 0, 3),
    ("f-in-2-blocks-one-expert", 150, 2, "one-expert", "float32", 32, 256,
     FIRST, HELD, 0, 2),
    ("group-1-decode", 32, 3, "1", "float32", 32, 16, FIRST, HELD, 0, 1),
    ("group-16-decode", 32, 3, "16", "float32", 32, 16, FIRST, HELD, 0, 1),
    ("group-17-decode", 32, 3, "17", "float32", 32, 16, FIRST, HELD, 0, 1),
    ("group-1-chunk", 100, 3, "1", "float32", 32, 16, FIRST, HELD, 0, 1),
    ("group-17-chunk", 100, 3, "17", "bfloat16", 32, 16, FIRST, HELD, 0, 1),
    ("group-64-chunk", 100, 3, "64", "float32", 32, 16, FIRST, HELD, 0, 1),
    ("group-65-chunk", 100, 3, "65", "float32", 32, 16, FIRST, HELD, 0, 1),
    ("group-65-bf16-chunk", 100, 3, "65", "bfloat16", 32, 16, FIRST, HELD,
     0, 1),
    ("group-128-chunk", 100, 3, "128", "float32", 32, 16, FIRST, HELD, 0, 1),
    ("group-129-chunk", 100, 3, "129", "float32", 32, 16, FIRST, HELD, 0, 1),
    ("group-129-bf16-chunk", 100, 3, "129", "bfloat16", 32, 16, FIRST, HELD,
     0, 1),
]


@pytest.mark.parametrize(
    "T,k,routing,dtype,d,f,first,held,every,f_blocks",
    [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_kernel_equals_the_per_pair_sum(monkeypatch, T, k, routing, dtype, d,
                                        f, first, held, every, f_blocks):
    """(y, pairs, hit, tiles) of `held_experts_ffn` are the plain sum's:
    y to float32's rounding where x is float32, and to the one rounding
    the kernel makes of its own (SiLU(gate) * up to bfloat16 ahead of the
    down product) where x is bfloat16."""
    bm = 16 if T <= 64 else 128
    if f_blocks > 1:    # room for 128 columns of f and no more
        monkeypatch.setattr(he, "_VMEM_BYTES", he._fixed_bytes(
            T, d, bm, dtype) + he._block_bytes(d, 128, bm, dtype))
    assert f // he.f_block(T, d, f, bm, dtype, dtype) == f_blocks
    w = _weights(d, f, dtype)
    x = jax.random.normal(jax.random.PRNGKey(4), (T, d)).astype(dtype)
    chosen, weights = _routing(routing, T, k, x)
    valid = (jnp.arange(T) % every != 0) if every else None
    cut = slice(first, first + held) if first < E else slice(0, held)
    y, pairs, hit, tiles = jax.jit(
        lambda *a: moe.held_experts_ffn(*a, first, valid=valid))(
            x, chosen, weights, *(a[cut] for a in w))
    want, n, n_hit, n_tiles = _per_pair_sum(
        x, chosen, weights, [a[cut] for a in w], first, held, valid)
    assert y.shape == (T, d) and y.dtype == jnp.float32
    assert (int(pairs), int(hit), int(tiles)) == (n, n_hit, n_tiles)
    err = float(np.abs(np.asarray(y) - want).max())
    if dtype == "float32":
        assert err < 2e-5, err
    else:
        assert err < 0.01 * max(float(np.abs(want).max()), 1.0), err
    if routing == "none" or every == 1 or first == 16:
        assert n == 0 and int(tiles) == 0 and not bool(np.asarray(y).any())
    if routing.isdigit():
        assert n == int(routing) and n_hit == 1
    if routing == "twice":
        assert n == T * k and n_hit == 2


def test_words_and_columns_are_inverse():
    """x as rows of 32-bit words and back: bit for bit, both widths."""
    for dtype in (jnp.bfloat16, jnp.float32):
        x = jax.random.normal(jax.random.PRNGKey(0), (7, 256)).astype(dtype)
        words = he._words(x)
        assert words.dtype == jnp.uint32
        assert words.shape == (7, 256 * x.dtype.itemsize // 4)
        back = jnp.zeros_like(x)
        for at, piece in he._columns(words, x.dtype):
            back = back.at[:, at:at + piece.shape[1]].set(piece)
        assert bool((back == x).all())
