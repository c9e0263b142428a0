"""The `laguna` decoder (models/laguna.py: grouped-query attention of
two kinds under a per-head gate, ops/moe.py's dropless expert layer) on
the CPU, at the rehearsal twin's sizes: five layers in the published
pattern (full, three sliding, full; one dense, four of experts), 12 and
18 query heads over 2 kv heads of 16 (the published groups of 6 and 9),
a window and ring of 8, experts 4-7 of 16 held, top-3, YaRN over half a
head, left-padded rows. Held against the plain reference
(benchmarks/reference/laguna_ref.py), which imports nothing of the
program. Nothing here is a device number."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import gqa_moe_model, gqa_moe_ops
from benchmarks import manifest as manifest_mod
from benchmarks import rehearsal
from benchmarks.reference import laguna_ref as ref
from ray_tpu import models
from ray_tpu.models import laguna as m
from ray_tpu.ops import attention, rope
from ray_tpu.ops.pallas import gqa_chunk_attention as gqa
from ray_tpu.serve.llm import LLMEngine

ROOT = manifest_mod.ROOT
F32 = {"param_dtype": "float32", "compute_dtype": "float32"}


def _published() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "Laguna-S-2.1.json")) as f:
        return json.load(f)


def _twin(**over) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "rehearsal", "configs",
                           "Laguna-S-2.1.json")) as f:
        twin = rehearsal.overlay(_published(), json.load(f))
    return {**twin, "held_as": {"serve": F32}, **over}


@pytest.fixture(scope="module")
def model():
    """(config file, program config, params, reference hp) of the twin
    in float32: this holder has experts 4-7 of 16."""
    twin = _twin()
    cfg = gqa_moe_model.program_config(twin, "serve", max_seq_len=96)
    params = m.init_params(cfg, jax.random.PRNGKey(0))
    return twin, cfg, params, gqa_moe_model.reference_hp(twin)


def _tokens(n, seed=1, batch=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, n), 1, 256)


def _ref_logits(params, toks, hp, **kw):
    rows = jnp.arange(toks.shape[1])
    return jax.jit(lambda p, t: ref.logits_and_choices(p, t, hp, rows, **kw))(
        params, toks)


def test_twin_is_served_by_this_module_and_holds_a_share(model):
    twin, cfg, params, hp = model
    assert models.module_for(cfg) is m and not m.TENSOR_PARALLEL
    assert not [n for n in models.REQUIRED if not hasattr(m, n)]
    assert cfg.layer_types == (m.KINDS[0],) + (m.KINDS[1],) * 3 + (
        m.KINDS[0],)
    assert (cfg.heads_full, cfg.heads_sliding, cfg.n_kv_heads,
            cfg.head_dim) == (12, 18, 2, 16)
    assert (cfg.moe_layers, cfg.mlp_only_layers) == ((1, 2, 3, 4), (0,))
    assert (cfg.n_routed_experts, cfg.experts_first, cfg.experts_held,
            cfg.experts_per_tok) == (16, 4, 4, 3)
    assert (cfg.sliding_window, cfg.ring_len) == (8, 8)
    assert "router" not in params["layers"][0]
    assert params["layers"][1]["we_gate"].shape == (4, 64, 32)
    assert params["layers"][1]["router"].shape == (64, 16)
    assert params["layers"][1]["router_bias"].dtype == jnp.float32
    # each kind's own count of query heads, one gate number a head
    assert params["layers"][0]["wq"].shape == (64, 12 * 16)
    assert params["layers"][1]["wq"].shape == (64, 18 * 16)
    assert params["layers"][1]["w_gate_attn"].shape == (64, 18)
    # two leaves as deep as the cache, two rings that are not
    shapes = jax.eval_shape(lambda: m.init_cache(cfg, 3, 48))
    assert shapes["k"].shape == (2, 3, 2, 16, 48)
    assert shapes["v"].shape == (2, 3, 2, 48, 16)
    assert shapes["window_k"].shape == (3, 3, 2, 16, 8)
    assert shapes["window_v"].shape == (3, 3, 2, 8, 16)
    assert m.CACHE_LEN_AXIS == {"k": 4, "v": 3}
    assert set(m.CACHE_KIND.values()) == {"kv", "window"}
    assert set(m.cache_logical_axes(cfg)) == set(shapes)


def test_forward_agrees_with_the_reference(model):
    _, cfg, params, hp = model
    toks = _tokens(48)
    got, seen = jax.jit(lambda p, t: m.forward(p, t, cfg, collect=True))(
        params, toks)
    want, theirs = _ref_logits(params, toks, hp)
    assert float(jnp.abs(got[0] - want).max()) < 2e-4
    assert len(seen["chosen"]) == 4
    for mine, own in zip(seen["chosen"], theirs["chosen"]):
        assert bool((jnp.sort(mine[0], -1) == jnp.sort(own, -1)).all())


def test_rope_of_both_kinds_at_the_published_numbers():
    """`rope_parameters` of the published file against hand-computed
    values. Full layers: 64 rotated numbers, d(r) = 64 ln(8192 / (2 pi
    r)) / (2 ln 500000) is 9.05 at 32 turns and 17.50 at 1, so the ramp
    runs over pairs 9 to 18; below it the plain frequency, above it a
    128th; cos and sin times 0.1 ln 128 + 1. Sliding layers: all 128
    numbers, theta 10,000, plain."""
    pub = _published()
    full = pub["rope_parameters"]["full_attention"]
    assert (full["factor"], full["original_max_position_embeddings"],
            full["partial_rotary_factor"]) == (128, 8192, 0.5)
    assert rope.yarn_correction_range(64, 5e5, 8192, 32, 1) == (9, 18)
    cfg = m.from_published(pub)
    inv, factor = m.rope_of(cfg, m.KINDS[0])
    plain = 5e5 ** (-np.arange(32) / 32)
    assert inv.dtype == np.float32 and inv.shape == (32,)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[18:], plain[18:] / 128, rtol=1e-6)
    assert factor == pytest.approx(0.1 * np.log(128) + 1, rel=1e-9)
    assert factor == pytest.approx(1.4852030263919618)
    inv_s, factor_s = m.rope_of(cfg, m.KINDS[1])
    np.testing.assert_allclose(inv_s, 1e4 ** (-np.arange(64) / 64),
                               rtol=1e-6)
    assert inv_s.shape == (64,) and factor_s == 1.0
    # the reference computes the same from the file's group, on its own
    for kind, (mine, f) in ((m.KINDS[0], (inv, factor)),
                            (m.KINDS[1], (inv_s, factor_s))):
        theirs, f_ref = ref.rope_of(pub["rope_parameters"][kind], 128)
        np.testing.assert_allclose(np.asarray(theirs), mine, rtol=2e-6)
        assert f_ref == pytest.approx(f)
    # half a head turns, the other half passes as projected
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 128))
    y = rope.apply_partial_rope(x, jnp.arange(5)[None] + 100, inv, factor)
    assert bool((y[..., 64:] == x[..., 64:]).all())
    assert float(jnp.abs(y[..., :64] - x[..., :64]).max()) > 0.1
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(y[..., :64], axis=-1)),
        np.asarray(jnp.linalg.norm(x[..., :64], axis=-1)) * factor,
        rtol=1e-5)


def _prefill_then_decode(cfg, params, toks, start, bucket, chunk, steps,
                         depth, graft=None):
    """Rows of `toks` [b, >= bucket - start + steps], row r left-padded
    by start[r] to `bucket`, prefilled in chunks, then `steps` cached
    decode steps with per-row depths. -> logits after the prompt and
    after each step, [steps + 1, b, vocab]."""
    b = toks.shape[0]
    padded = np.zeros((b, bucket), np.int32)
    for r in range(b):
        padded[r, start[r]:] = np.asarray(toks[r, :bucket - start[r]])
    cache = m.init_cache(cfg, b, max_len=depth)
    cache["start"] = jnp.asarray(start, jnp.int32)
    step = jax.jit(lambda p, c, t: m.decode_step(p, c, t, cfg))
    for pos in range(0, bucket, chunk):
        logits, cache = step(params, cache, jnp.asarray(
            padded[:, pos:pos + chunk]))
    outs = [logits]
    cache["length"] = jnp.full((b,), bucket, jnp.int32)
    if graft is not None:
        cache = graft(cache)
    for i in range(steps):
        nxt = jnp.stack([toks[r, bucket - start[r] + i] for r in range(b)])
        logits, cache = step(params, cache, nxt[:, None])
        outs.append(logits)
    return jnp.stack(outs), cache


def _worst(cfg, params, hp, toks, start, bucket, chunk, steps, depth,
           graft=None):
    """Largest logit error of the cached path against the reference's
    forward of each row's own tokens, over every step and row."""
    got, _ = _prefill_then_decode(cfg, params, toks, start, bucket, chunk,
                                  steps, depth, graft)
    worst = 0.0
    for r in range(toks.shape[0]):
        n = bucket - start[r]
        want, _ = _ref_logits(params, toks[r:r + 1, :n + steps], hp)
        worst = max(worst, float(jnp.abs(
            got[:, r] - want[n - 1:n + steps]).max()))
    return worst


@pytest.fixture(scope="module")
def model128():
    """The twin with heads of 128 (a whole lane row, as published) in
    groups of 2 and 3 and a window and ring of 128: the shapes the
    decode kernel writes for and the ring mode takes."""
    twin = _twin(head_dim=128, sliding_window=128,
                 num_attention_heads_per_layer=[4, 6, 6, 6, 4])
    cfg = gqa_moe_model.program_config(twin, "serve", max_seq_len=512)
    params = m.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, gqa_moe_model.reference_hp(twin)


@pytest.fixture
def kernels(monkeypatch):
    """Every kernel interpreted, as a TPU takes them for shapes whole in
    their tiles: a chunk's attention of both kinds through
    ops/pallas/gqa_chunk_attention.py in tiles of 32 queries x 64 keys,
    a decode step's through ops/pallas/decode_attention.py over the full
    stack in blocks of 128, with its write, and over the ring in its one
    block."""
    calls = []
    tiles = gqa.tiles
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(gqa, "_Q_TILES", (32,))
    monkeypatch.setattr(gqa, "_K_TILES", (64,))
    monkeypatch.setattr(gqa, "tiles", lambda *a: calls.append(a) or tiles(*a))
    monkeypatch.setattr(attention, "decode_block_len",
                        lambda nkv, hd, n, dt, mesh: 128)
    return calls


@pytest.fixture
def twin_tiles(monkeypatch):
    """What the counters follow on a TPU, at the twin's sizes: tiles of
    8 x 8, blocks of 16, the ring read once a live row."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(gqa, "_Q_TILES", (8,))
    monkeypatch.setattr(gqa, "_K_TILES", (8,))
    monkeypatch.setattr(m, "_ring_block", lambda cfg, per_row: cfg.ring_len)
    monkeypatch.setattr(
        attention, "decode_block_len",
        lambda nkv, hd, n, dt, mesh: 16 if n % 16 == 0 else None)


@pytest.mark.parametrize("chunk", [16, 4])
def test_chunked_prefill_then_cached_decode_agree_with_the_reference(
        model, chunk):
    """Prefill in chunks (longer than the ring: a whole turn written at
    once; and shorter), then 20 cached decode steps with per-row depths,
    past two and a half turns of the ring of 8; row 0 unpadded, row 1
    left-padded by 11 (so that it is shallower than the ring when its
    first chunk ends), row 2 by 27."""
    _, cfg, params, hp = model
    toks = _tokens(64, seed=3, batch=3)
    assert _worst(cfg, params, hp, toks, [0, 11, 27], 32, chunk, 20,
                  64) < 2e-4


def test_the_kernels_path_agrees_with_the_reference(model128, kernels):
    """The same through every kernel, interpreted: chunks of 128 against
    a ring of 128 and a cache 512 deep, then 24 decode steps; row 2's
    request (56 tokens) is shallower than the ring."""
    cfg, params, hp = model128
    toks = _tokens(300, seed=3, batch=3)
    assert _worst(cfg, params, hp, toks, [0, 37, 200], 256, 128, 24,
                  512) < 2e-4
    # both kinds of chunk went the kernel's way: groups of 2 and 3
    assert {(2, 128, 128, 512), (3, 128, 128, 256)} <= set(kernels)


def test_a_request_shallower_than_the_ring_wraps_it(model128, kernels):
    """Prompts of 6 and 3 tokens left-padded to 256, two turns of the
    ring: their visible ring rows lie on both sides of the ring's end,
    which no range of rows describes; the kernel's ring mode hides a row
    by the position it holds."""
    cfg, params, hp = model128
    toks = _tokens(32, seed=5, batch=2)
    assert _worst(cfg, params, hp, toks, [250, 253], 256, 128, 12,
                  512) < 2e-4


@pytest.mark.parametrize("fault", ["window-7", "window-9", "ring-one-off",
                                   "ring-half-turn", "no-gate",
                                   "whole-head-rope", "no-attention-factor"])
def test_a_broken_mechanism_is_seen(model, monkeypatch, fault):
    """What the comparison holds: the window's width to the position, the
    ring's rows to their slots, the gate, the rotated half and YaRN's
    factor. Each fault moves the logits by more than a hundred times the
    sound path's error."""
    _, cfg, params, hp = model
    toks = _tokens(64, seed=3, batch=2)
    graft = None
    if fault.startswith("window-"):
        cfg = dataclasses.replace(cfg, sliding_window=int(fault[-1]))
        # the ring stays 8 rows: only whom a query may see changes
        monkeypatch.setattr(m.LagunaConfig, "ring_len", property(lambda c: 8))
    elif fault.startswith("ring-"):
        turn = 1 if fault == "ring-one-off" else 4
        graft = lambda c: {**c, "window_k": jnp.roll(c["window_k"], turn, 4),
                           "window_v": jnp.roll(c["window_v"], turn, 3)}
    elif fault == "no-gate":
        monkeypatch.setattr(
            m._parts, "_gate_out", lambda cfg, layer, h, attn: attn.reshape(
                attn.shape[:2] + (-1,)) @ layer["w_o"])
    elif fault == "whole-head-rope":
        cfg = dataclasses.replace(cfg, rope_partial=1.0)
    else:
        cfg = dataclasses.replace(cfg, rope_attention_factor=1.0)
    assert _worst(cfg, params, hp, toks, [0, 5], 32, 16, 12, 64,
                  graft) > 2e-2


def test_window_nine_reads_a_row_the_ring_no_longer_holds(model):
    """The fault above for a window of 9 over a ring of 8 is seen because
    the ninth key is gone; with a ring of 9 rows the same config is a
    sound model again (the reference told the same window)."""
    twin, cfg, params, hp = model
    cfg9 = dataclasses.replace(cfg, sliding_window=9)
    toks = _tokens(64, seed=3, batch=1)
    assert _worst(cfg9, params, {**hp, "sliding_window": 9}, toks, [3], 32,
                  16, 12, 64) < 2e-4


def test_the_four_shares_and_the_shared_expert_once_are_the_whole_layer(
        model):
    """The cut to a share of the experts, tied to the model: the routed
    parts that the four holders of 4 experts each compute (the program's
    `held_experts_ffn` through `_ffn`, told `experts_first`), with what
    every holder computes alike, the shared expert, counted once, add up
    to what the UNCUT reference gives for the whole expert layer."""
    twin, cfg, _, hp = model
    whole = dataclasses.replace(cfg, experts_first=0, experts_held=16)
    lp = m.init_params(whole, jax.random.PRNGKey(7))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 24, cfg.dim))
    with jax.default_matmul_precision("highest"):
        h = ref._rms(x[0], lp["mlp_norm"], hp["norm_eps"])
        want, _, _ = ref._ffn(lp, h, {**hp, "experts_first": 0}, None, 2048)
        shared = ref._swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        total = jnp.zeros_like(want)
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, experts_first=first,
                                        experts_held=4)
            part = {**lp, **{k: lp[k][first:first + 4]
                             for k in ("we_gate", "we_up", "we_down")}}
            y, moe, _ = m._parts._ffn(share, part, x, None, False)
            total = total + (y[0] - x[0]) - shared
            assert int(moe[0]) <= 24 * 3
    assert float(jnp.abs(total + shared - want).max()) < 1e-4
    assert float(jnp.abs(want - shared).max()) > 0.1


@pytest.mark.parametrize("name,params,tol", [
    ("published", 117.6e9, 0.05e9), ("cut", 3.002e9, 0.0005e9)])
def test_parameters_counted_from_the_programs_shapes(name, params, tol):
    """The uncut model by the program's shapes (117.6 B), and the cell's
    cut: 5 layers, 64 of 256 experts, a quarter of the vocabulary (3.002
    B, 6.00 GB in bfloat16), with the parts the configuration file's
    `deployment` states."""
    pub = _published()
    if name == "published":
        pub = {**pub, "num_hidden_layers": 48, "num_experts": 256,
               "vocab_size": 100352,
               "layer_types": [m.KINDS[0] if i % 4 == 0 else m.KINDS[1]
                               for i in range(48)],
               "mlp_layer_types": ["dense"] + ["sparse"] * 47,
               "gating_types": ["per_head"] * 48,
               "num_attention_heads_per_layer": [
                   48 if i % 4 == 0 else 72 for i in range(48)]}
        pub.pop("router_experts")
    cfg = m.from_published(pub)
    assert cfg.num_params() == pytest.approx(params, abs=tol)
    if name == "cut":
        assert cfg.num_params() == 3_002_017_792
        assert f"{cfg.num_params():,}" in pub["deployment"]
        assert gqa_moe_ops.attention_params(pub, m.KINDS[0]) == 44_187_648
        assert gqa_moe_ops.attention_params(pub, m.KINDS[1]) == 63_135_744
        assert gqa_moe_ops.expert_params(pub) == 9_437_184
        shapes = jax.eval_shape(lambda: m.init_cache(
            dataclasses.replace(cfg, max_seq_len=24576), 32))
        nbytes = lambda *names: sum(
            shapes[n].size * shapes[n].dtype.itemsize for n in names)
        assert nbytes("k", "v") == 6_442_450_944
        assert nbytes("window_k", "window_v") == 201_326_592
        assert f"{nbytes('k', 'v'):,}" in pub["deployment"]


def test_engine_serves_it_and_counts_both_kinds(model):
    """LLMEngine with this model: greedy tokens equal the reference's
    argmax path, `cache_bytes` files K/V and rings apart, the prefix
    store stays off (a ring is no prefix), and the module's counters
    reach `stats()`."""
    import asyncio

    _, cfg, params, hp = model
    eng = LLMEngine(cfg, tp=1, max_batch=3, prompt_buckets=(16, 32),
                    prefill_chunk=16, prefix_cache_entries=4, params=params)
    assert eng.prefix_cache_entries == 0
    prompt = [int(t) for t in np.asarray(_tokens(21, seed=9)[0])]

    async def run():
        return [t async for t in eng.generate(prompt, max_new_tokens=12)]

    out = asyncio.run(run())
    toks = jnp.asarray([prompt + out])
    want, _ = _ref_logits(params, toks, hp)
    assert out == [int(t) for t in np.asarray(
        want[len(prompt) - 1:-1].argmax(-1))]
    stats = eng.stats()
    per_slot = 2 * 2 * 2 * 16 * 4              # layers x (k, v) x kv x hd x 4 B
    assert stats["cache_bytes"] == {
        "kv": 3 * 96 * per_slot, "state": 0, "window": 3 * 3 * 8 * 2 * 2 * 16 * 4}
    for name in ("decode_full_positions_attended",
                 "decode_window_positions_attended",
                 "decode_full_positions_read", "decode_window_positions_read",
                 "prefill_full_keys_visited", "prefill_full_keys_visible",
                 "prefill_window_keys_visited", "prefill_window_keys_visible",
                 "moe_expert_rows", "moe_experts_hit", "moe_expert_tiles"):
        assert stats[name] > 0, name
    # 11 decode steps of one row 32 + i deep, start 11: a full layer
    # attends to all of them, a sliding one to 8
    depths = [32 - 11 + 1 + i for i in range(11)]
    assert stats["decode_full_positions_attended"] == 2 * sum(depths)
    assert stats["decode_window_positions_attended"] == 3 * 8 * 11
    # two chunks of 16 queries, the first 5 real: visible keys
    real = np.arange(1, 22)
    assert stats["prefill_full_keys_visible"] == 2 * int(real.sum())
    assert stats["prefill_window_keys_visible"] == 3 * int(
        np.minimum(real, 8).sum())


def test_counters_of_the_kernels_tiles(twin_tiles, model):
    """`prefill_counters` under the kernel: the visited keys are the live
    tiles' (8 x 8 here), by the same rule the kernel's tables follow."""
    _, cfg, _, _ = model
    c = m.prefill_counters(cfg, 11, 16, 16, 32)
    # full: queries 16-31 in two tiles; keys 8-15 (from start 11), then
    # to the tile's own diagonal: 2 + 3 tiles of 64 pairs, 2 layers
    assert c["prefill_full_keys_visited"] == 2 * 5 * 64
    assert c["prefill_full_keys_visible"] == 2 * sum(range(6, 22))
    # sliding: ring (positions 8-15) + chunk: query tile 0 sees the ring
    # and itself, tile 1 chunk tiles 0 and 1: 4 tiles, 3 layers
    assert c["prefill_window_keys_visited"] == 3 * 4 * 64
    d = m.decode_counters(cfg, [(11, 40), (0, 70)], 3)
    assert d["decode_full_positions_attended"] == 2 * (30 + 71)
    assert d["decode_window_positions_attended"] == 3 * 16
    # blocks of 16 that overlap [11, 40] and [0, 70]: 3 + 5; two rings
    assert d["decode_full_positions_read"] == 2 * 16 * 8
    assert d["decode_window_positions_read"] == 3 * 8 * 2


def test_chunk_kernel_against_the_plain_form():
    """ops/pallas/gqa_chunk_attention.py interpreted, heads of 128 in
    groups of 3, against `_attend_plain` under the same rule: a cache by
    position with a window, left padding and depth still unwritten."""
    b, nkv, g, s, hd, n = 2, 2, 3, 32, 128, 96
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, nkv * g, hd))
    k = jax.random.normal(ks[1], (1, b, nkv, hd, n))
    v = jax.random.normal(ks[2], (1, b, nkv, n, hd))
    start, pos0 = jnp.asarray([0, 37]), 32
    k_pos = jnp.broadcast_to(jnp.arange(n), (b, n))
    for window in (None, 10):
        t = gqa.Tiles(16, 32)
        seen = gqa.seen_by_position(jnp, k_pos, start, window)
        got = m._by_head(gqa.gqa_chunk_attention(
            m._by_group(q, nkv), k, v, 0, seen, pos0, scale=hd ** -0.5,
            t=t))
        q_pos = pos0 + jnp.arange(s)
        dist = q_pos[None, :, None] - k_pos[:, None, :]
        mask = ((dist >= 0) & (dist < (window or n + s))
                & (k_pos >= start[:, None])[:, None, :])
        want = m._attend_plain(q, k[0], v[0], mask, hd ** -0.5)
        assert float(jnp.abs(got - want).max()) < 2e-5
        # row 1's queries before its start attend to nothing: zeros
        assert float(jnp.abs(got[1, :5]).max()) == 0.0
        live, named = gqa.tile_tables(seen, jnp.full((b,), pos0), s, t)
        # keys past the chunk's end are never live; with the window, nor
        # are those more than 10 behind the second query tile's first
        assert int(live[:, :, 2].sum()) == 0
        assert int(live[0, 1, 0]) == (0 if window else 1)
        assert bool((jnp.take_along_axis(live, named, 2) == 1).all())


def test_ring_kernel_writes_the_new_row_and_nothing_else():
    """The decode kernel's ring mode interpreted at heads of 128: against
    the XLA form for rows deeper than the ring, shallower, wrapped, and
    holding no request; the rings equal bit for bit but for the new
    row."""
    b, nkv, g, hd, ring = 4, 2, 3, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (b, 1, nkv * g, hd))
    kk = jax.random.normal(ks[1], (b, 1, nkv, hd))
    vv = jax.random.normal(ks[2], (b, 1, nkv, hd))
    rk = jax.random.normal(ks[3], (2, b, nkv, hd, ring))
    rv = jax.random.normal(ks[4], (2, b, nkv, ring, hd))
    start = jnp.asarray([0, 250, 3, 0])
    length = jnp.asarray([700, 300, 40, -1])
    cfg = m.LagunaConfig(n_layers=4, n_kv_heads=nkv, heads_full=4,
                         heads_sliding=nkv * g, sliding_window=ring,
                         dtype=jnp.float32)
    want, wk, wv = m._ring_decode(cfg, 1, q, kk, vv, rk, rv, length, start)
    got, gk, gv = attention.decode_attention(
        q.reshape(b, nkv, g, hd), rk, rv, 1, start, length,
        scale=hd ** -0.5, block_len=ring, new_kv=(kk[:, 0], vv[:, 0]),
        ring=True)
    live = np.asarray(length) >= 0
    assert float(jnp.abs(got.reshape(want.shape) - want)[live].max()) < 2e-5
    assert float(jnp.abs(got[3]).max()) == 0.0
    assert bool((gk[:, live] == wk[:, live]).all())
    assert bool((gv[:, live] == wv[:, live]).all())
    # the row that holds no request is not written at all
    assert bool((gk[:, 3] == rk[:, 3]).all())
    assert bool((gk[0] == rk[0]).all()) and bool((gk[1, 0] != rk[1, 0]).any())
