"""Model-layer tests: llama forward/loss/decode parity, MLP, sharded
train step on the 8-device virtual CPU mesh (SURVEY.md §4 implications:
CPU-device JAX fake backend stands in for pod slices)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import llama
from ray_tpu.models.mlp import MLPConfig, mlp_init, mlp_loss
from ray_tpu.parallel.mesh import MeshConfig
from ray_tpu.parallel.spmd import build_train_step, shard_batch


@pytest.fixture(scope="module")
def cfg():
    return llama.config_for("debug", remat=False, attn_impl="xla")


@pytest.fixture
def params(cfg):
    # function-scoped: train steps donate state buffers, and device_put
    # memoization can alias them across build_train_step calls
    return llama.init_params(cfg, jax.random.PRNGKey(0))


def test_forward_shape(cfg, params):
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_loss_decreases_under_sgd(cfg, params):
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                     cfg.vocab_size),
    }
    batch["targets"] = jnp.roll(batch["tokens"], -1, axis=1)
    opt = optax.adam(1e-2)
    state = opt.init(params)

    @jax.jit
    def step(params, state, batch):
        (loss, _), g = jax.value_and_grad(
            llama.loss_fn, has_aux=True)(params, batch, cfg)
        updates, state = opt.update(g, state)
        return optax.apply_updates(params, updates), state, loss

    p = params
    losses = []
    for _ in range(10):
        p, state, loss = step(p, state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def _positions_last(cache):
    """cache["k"], cache["v"] as numpy with the cache positions last."""
    return {key: np.moveaxis(np.asarray(cache[key]), axis, -1)
            for key, axis in llama.KV_LEN_AXIS.items()}


_GQA = [(4, 4), (4, 2), (8, 2)]
_DECODE_CASES = [
    pytest.param(h, kv, lengths, s, False, jnp.float32,
                 id=f"h{h}kv{kv}-{lengths}-s{s}")
    for h, kv in _GQA for lengths in ("scalar", "per_row") for s in (1, 8)
] + [
    pytest.param(4, 2, "per_row", 1, True, jnp.float32, id="lora"),
    pytest.param(4, 2, "scalar", 1, False, jnp.bfloat16, id="bf16"),
    # the prefill chunk's program with adapters in the loop, and in the
    # serve cells' precision (PR 45: q and k are held as projected, the
    # low-rank path summed in before they are)
    pytest.param(4, 2, "scalar", 8, True, jnp.float32, id="lora-chunk"),
    pytest.param(4, 1, "per_row", 8, True, jnp.float32,
                 id="lora-chunk-per-row"),
    pytest.param(4, 2, "scalar", 8, False, jnp.bfloat16, id="bf16-chunk"),
]


@pytest.mark.parametrize("heads,kv_heads,lengths,s,lora,dtype",
                         _DECODE_CASES)
def test_decode_matches_forward(heads, kv_heads, lengths, s, lora, dtype):
    """KV-cache decode must agree with the dense forward pass: for every
    GQA group size, with the cache length a scalar (lock-step rows, left
    padded) or per row (slots at their own depths), appending one token
    at a time or a chunk of 8 to a partly filled cache, adapters in the
    loop or not. And a step writes the new rows and nothing else: every
    other position of every layer is bit-equal before and after it."""
    cfg = llama.config_for("debug", remat=False, attn_impl="xla",
                           n_heads=heads, n_kv_heads=kv_heads, dtype=dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    if lora:
        from ray_tpu.models import lora as lora_mod

        ad = lora_mod.init_lora_params(cfg, lora_mod.LoraConfig(rank=4),
                                       jax.random.PRNGKey(5))["layers"]
        params["lora"] = {"layers": {
            k: (0.3 * jax.random.normal(jax.random.PRNGKey(i), v.shape,
                                        v.dtype) if k.endswith("_b") else v)
            for i, (k, v) in enumerate(sorted(ad.items()))}}
    b, first, more, max_len = 3, 8, 8, 32
    starts = np.array([0, 2, 3], np.int32)      # first real slot per row
    real = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (b, first + more), 1, cfg.vocab_size))
    dense = [np.asarray(llama.forward(params, jnp.asarray(real[r:r + 1]),
                                      cfg)[0]) for r in range(b)]
    step = jax.jit(lambda p, c, t: llama.decode_step(p, c, t, cfg))

    cache = llama.init_kv_cache(cfg, b, max_len=max_len)
    cache["start"] = jnp.asarray(starts)
    fed = np.zeros((b, first), np.int32)
    if lengths == "scalar":
        # rows in lock-step: the first call holds each row's pad slots
        seen = first - starts
        for r in range(b):
            fed[r, starts[r]:] = real[r, :seen[r]]
    else:
        # each row at its own depth: row r's first token lands in slot
        # starts[r], a chunk of 8 real tokens per row
        cache["length"] = jnp.asarray(starts)
        seen = np.full((b,), first)
        fed[:] = real[:, :first]

    def check(logits):
        for r in range(b):
            np.testing.assert_allclose(
                np.asarray(logits[r]), dense[r][seen[r] - 1],
                rtol=tol, atol=tol, err_msg=f"row {r} after {seen[r]}")

    logits, cache = step(params, cache, jnp.asarray(fed))
    check(logits)
    for _ in range(more // s):
        nxt = np.stack([real[r, seen[r]:seen[r] + s] for r in range(b)])
        before = _positions_last(cache)
        at = np.broadcast_to(np.asarray(cache["length"]), (b,))
        logits, cache = step(params, cache, jnp.asarray(nxt))
        seen = seen + s
        check(logits)
        after = _positions_last(cache)
        for key in before:
            for r in range(b):
                new = np.zeros(max_len, bool)
                new[at[r]:at[r] + s] = True
                np.testing.assert_array_equal(
                    after[key][:, r][..., ~new], before[key][:, r][..., ~new])
                assert (after[key][:, r][..., new] != 0).any(axis=(1, 2)).all()
    assert np.array_equal(np.broadcast_to(cache["length"], (b,)),
                          at + s)


def test_remat_matches(cfg, params):
    tokens = jnp.ones((1, 8), jnp.int32)
    base = llama.forward(params, tokens, cfg)
    import dataclasses

    cfg_r = dataclasses.replace(cfg, remat=True)
    rem = llama.forward(params, tokens, cfg_r)
    np.testing.assert_allclose(base, rem, rtol=1e-5, atol=1e-5)


def test_sharded_train_step_dp_fsdp_tp(cfg, params):
    """Full GSPMD train step over data=2 × fsdp=2 × tensor=2 on the
    virtual CPU mesh — the multi-chip path the driver dry-runs."""
    mesh = MeshConfig(data=2, fsdp=2, tensor=2).build()
    opt = optax.adamw(1e-3)
    step, state = build_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, params,
        llama.param_logical_axes(cfg), mesh)
    batch = {
        "tokens": jnp.zeros((8, 16), jnp.int32),
        "targets": jnp.zeros((8, 16), jnp.int32),
    }
    batch = shard_batch(batch, mesh)
    state, aux = step(state, batch)
    state, aux = step(state, batch)
    assert int(state["step"]) == 2
    assert np.isfinite(float(aux["loss"]))
    # param sharding survived the update
    wq = state["params"]["layers"]["wq"]
    assert wq.sharding.spec == jax.sharding.PartitionSpec(
        None, "fsdp", "tensor")


def test_grad_accum_matches_big_batch(cfg, params):
    mesh = MeshConfig(data=2).build(jax.devices()[:2])
    opt = optax.sgd(1e-2)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                     cfg.vocab_size),
    }
    batch["targets"] = jnp.roll(batch["tokens"], -1, 1)

    params2 = llama.init_params(cfg, jax.random.PRNGKey(0))
    step1, state1 = build_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, params,
        llama.param_logical_axes(cfg), mesh)
    step2, state2 = build_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, params2,
        llama.param_logical_axes(cfg), mesh, grad_accum=4)
    s1, _ = step1(state1, shard_batch(batch, mesh))
    s2, _ = step2(state2, shard_batch(batch, mesh))
    a = jax.tree.leaves(s1["params"])[0]
    b = jax.tree.leaves(s2["params"])[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-5)


def test_mlp_trains():
    cfg = MLPConfig(in_dim=16, hidden=(32,), n_classes=4)
    params = mlp_init(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    y = jax.random.randint(jax.random.PRNGKey(2), (64,), 0, 4)
    opt = optax.adam(1e-2)
    state = opt.init(params)

    @jax.jit
    def step(p, s):
        (loss, _), g = jax.value_and_grad(mlp_loss, has_aux=True)(
            p, {"x": x, "y": y})
        u, s = opt.update(g, s)
        return optax.apply_updates(p, u), s, loss

    losses = []
    for _ in range(20):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5


# ------------------------------------------------------------------ LoRA
def test_lora_zero_init_is_identity(cfg, params):
    """B=0 at init: forward with adapters matches the base model exactly
    (models/lora.py init contract)."""
    from ray_tpu.models import lora

    lcfg = lora.LoraConfig(rank=4, targets=("wq", "wo", "w_up"))
    lp = lora.init_lora_params(cfg, lcfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0,
                                cfg.vocab_size)
    base = llama.forward(params, tokens, cfg)
    with_lora = llama.forward({**params, "lora": lp}, tokens, cfg)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(with_lora))


def test_lora_merge_matches_lowrank_path(cfg, params):
    """After training-style perturbation of A/B, folding the adapters into
    the base weights (merge_lora) reproduces the low-rank forward."""
    from ray_tpu.models import lora

    cfg_l = llama.LlamaConfig(**{**cfg.__dict__, "lora_alpha": 8.0})
    lcfg = lora.LoraConfig(rank=4, alpha=8.0, targets=("wq", "wv"))
    lp = lora.init_lora_params(cfg_l, lcfg, jax.random.PRNGKey(5))
    # make the adapters non-trivial
    lp = jax.tree.map(
        lambda x: x + 0.02 * jax.random.normal(
            jax.random.PRNGKey(6), x.shape, x.dtype), lp)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 16), 0,
                                cfg.vocab_size)
    low_rank = llama.forward({**params, "lora": lp}, tokens, cfg_l)
    merged = lora.merge_lora({**params, "lora": lp}, cfg_l)
    assert "lora" not in merged
    folded = llama.forward(merged, tokens, cfg_l)
    # bf16 low-rank path vs f32-folded delta: per-layer rounding compounds
    np.testing.assert_allclose(np.asarray(low_rank), np.asarray(folded),
                               atol=0.15, rtol=0.1)


def test_lora_train_step_freezes_base(cfg, params):
    """build_train_step(trainable_keys=("lora",)): loss falls, adapters
    move, and every frozen base leaf stays bit-identical (VERDICT r3 #2)."""
    from ray_tpu.models import lora
    from ray_tpu.parallel.mesh import MeshConfig

    lcfg = lora.LoraConfig(rank=4, targets=("wq", "wk", "wv", "wo"))
    lp = lora.init_lora_params(cfg, lcfg, jax.random.PRNGKey(8))
    full = {**params, "lora": lp}
    axes = {**llama.param_logical_axes(cfg),
            "lora": lora.lora_logical_axes(cfg, lcfg)}
    mesh = MeshConfig(data=2, fsdp=2, tensor=2).build(
        jax.devices("cpu")[:8])
    loss = lambda p, b: llama.loss_fn(p, b, cfg)
    step, state = build_train_step(
        loss, optax.adamw(1e-2), full, axes, mesh,
        trainable_keys=("lora",))
    base_before = jax.tree.map(np.asarray, state["frozen"])
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(9), (4, 32), 0,
                                     cfg.vocab_size),
        "targets": jax.random.randint(jax.random.PRNGKey(10), (4, 32), 0,
                                      cfg.vocab_size),
    }
    batch = shard_batch(batch, mesh)
    losses = []
    for _ in range(8):
        state, aux = step(state, batch)
        losses.append(float(aux["loss"]))
    assert losses[-1] < losses[0], losses
    # adapters moved
    b_leaf = np.asarray(state["params"]["lora"]["layers"]["wq_b"])
    assert np.abs(b_leaf).max() > 0
    # base params bit-identical
    jax.tree.map(
        lambda before, after: np.testing.assert_array_equal(
            before, np.asarray(after)),
        base_before, state["frozen"])


def test_remat_policies_match():
    """All remat policies are numerically identical (they only trade
    memory for recompute)."""
    params = llama.init_params(
        llama.config_for("debug", attn_impl="xla"), jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                     256),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                      256),
    }

    def loss_for(policy):
        c = llama.config_for("debug", attn_impl="xla", remat=True,
                             remat_policy=policy)
        val, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(p, batch, c)[0])(params)
        return float(val), grads

    l_dots, g_dots = loss_for("dots")
    l_none, g_none = loss_for("nothing")
    assert l_dots == l_none
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5),
        g_dots, g_none)
