"""The `kimi_k2` decoder (models/kimi_k2.py: DeepSeek-V3's layer, dense
latent attention under YaRN, ops/moe.py's dropless expert layer) on the
CPU, at the rehearsal twin's sizes: five layers (one dense, four of
experts), experts 4-11 of 16 held, top-4, YaRN stretching an original
context of 16 eight-fold, a left-padded row. Held against the plain
reference (benchmarks/reference/kimi_k2_ref.py), which imports nothing
of the program. Nothing here is a device number."""

import asyncio
import json
import math
import os
import types
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import latent_moe_model
from benchmarks import manifest as manifest_mod
from benchmarks import rehearsal
from benchmarks.reference import kimi_k2_ref as ref
from ray_tpu.models import kimi_k2 as m
from ray_tpu.models import module_for
from ray_tpu.ops import moe, rope
from ray_tpu.serve.llm import LLMEngine

ROOT = manifest_mod.ROOT
F32 = {"param_dtype": "float32", "compute_dtype": "float32"}
BF16 = {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"}


def _published() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "Kimi-K2.6.json")) as f:
        return json.load(f)


def _twin(held=F32, **over) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "rehearsal", "configs",
                           "Kimi-K2.6.json")) as f:
        twin = rehearsal.overlay(_published(), json.load(f))
    return {**twin, "held_as": {"serve": held}, **over}


@pytest.fixture(scope="module")
def model():
    """(config file, program config, params, reference hp) of the twin
    in float32: this holder has experts 4-11 of 16."""
    twin = _twin()
    cfg = latent_moe_model.program_config(twin, "serve", max_seq_len=96)
    params = m.init_params(cfg, jax.random.PRNGKey(0))
    return twin, cfg, params, latent_moe_model.reference_hp(twin)


def _tokens(n, seed=1, batch=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, n), 1, 256)


def _ref_logits(params, toks, hp, **kw):
    rows = jnp.arange(toks.shape[1])
    return jax.jit(lambda p, t: ref.logits_and_choices(p, t, hp, rows, **kw))(
        params, toks)


def test_twin_is_served_by_this_module_and_holds_a_share(model):
    twin, cfg, params, hp = model
    assert module_for(cfg) is m and not m.TENSOR_PARALLEL
    assert (cfg.n_layers, cfg.first_k_dense, cfg.n_moe_layers) == (5, 1, 4)
    assert (cfg.n_routed_experts, cfg.experts_first, cfg.experts_held) == \
        (16, 4, 8)
    assert (cfg.rope_factor, cfg.rope_original_len) == (8.0, 16)
    assert "router" not in params["layers"][0]
    assert params["layers"][1]["we_gate"].shape == (8, 64, 32)
    assert params["layers"][1]["router"].shape == (64, 16)
    assert params["layers"][1]["router_bias"].dtype == jnp.float32
    assert "w_gate_attn" not in params["layers"][1]       # no gate here
    # one leaf of rows, filled to whole lanes, as deep as the cache
    shapes = jax.eval_shape(lambda: m.init_cache(cfg, 2, 48))
    assert shapes["latent"].shape == (5, 2, 48, 128)
    assert m.CACHE_LEN_AXIS == {"latent": 2}
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


def test_yarn_frequencies_and_mscale_at_the_published_numbers():
    """`rope_scaling` of the published file, against hand-computed
    values: d(r) = 64 ln(4096 / (2 pi r)) / (2 ln 50000) is 8.91 at 32
    turns and 19.16 at 1, so the ramp runs over pairs 8 to 20; below it
    the plain frequency, above it a 64th, halfway (pair 14) 1/2 of each;
    m = 0.1 ln 64 + 1."""
    sc = _published()["rope_scaling"]
    assert (sc["factor"], sc["original_max_position_embeddings"],
            sc["beta_fast"], sc["beta_slow"]) == (64, 4096, 32, 1)
    assert rope.yarn_correction_range(64, 5e4, 4096, 32, 1) == (8, 20)
    inv = rope.yarn_inv_freq(64, 5e4, 64, 4096, 32, 1)
    plain = 5e4 ** (-np.arange(32) / 32)
    assert inv.dtype == np.float32 and inv.shape == (32,)
    np.testing.assert_allclose(inv[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(inv[20:], plain[20:] / 64, rtol=1e-6)
    np.testing.assert_allclose(inv[14], plain[14] * (0.5 + 0.5 / 64),
                               rtol=1e-6)
    assert inv[14] == pytest.approx(5e4 ** (-14 / 32) * 0.5078125, rel=1e-6)
    assert rope.yarn_mscale(64, 1) == pytest.approx(1.4158883, rel=1e-6)
    assert rope.yarn_mscale(1.0) == 1.0
    cfg = m.from_published(_published())
    a = cfg.attn
    assert a.scale == pytest.approx(2.0047397 / math.sqrt(192), rel=1e-6)
    assert a.yarn == (64.0, 4096, 32.0, 1.0) and a.row == 640
    # the reference computes the same from the file's group, on its own
    inv_ref, m2 = ref.yarn(latent_moe_model.reference_hp(_published()))
    np.testing.assert_allclose(np.asarray(inv_ref), inv, rtol=2e-6)
    assert m2 == pytest.approx(2.0047397, rel=1e-6)
    # and without the group: plain RoPE, no temperature
    plain_cfg = m.from_published({**_published(), "rope_scaling": None})
    assert plain_cfg.attn.yarn is None and plain_cfg.attn.mscale == 1.0


def _prefill_then_decode(cfg, params, toks, start, bucket, chunk, steps,
                         depth):
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
    for i in range(steps):
        nxt = jnp.stack([toks[r, bucket - start[r] + i] for r in range(b)])
        logits, cache = step(params, cache, nxt[:, None])
        outs.append(logits)
    return jnp.stack(outs), cache


@pytest.fixture
def kernels(monkeypatch):
    """Both kernels interpreted, as a TPU takes them for shapes whole in
    their tiles: a chunk's attention through
    ops/pallas/latent_attention.py in tiles of 8 queries x 4 keys, a
    decode step's through ops/pallas/latent_decode_attention.py in
    blocks of 16 positions."""
    from ray_tpu.models import dots3_note
    from ray_tpu.ops import attention
    from ray_tpu.ops.pallas import latent_attention as la

    calls = []

    def tiles(heads, nope, rope_, v, kv_rank, queries, keys):
        if queries % 8 or keys % 4:
            return None
        calls.append(("chunk", heads, queries, keys))
        return la.Tiles(2, 8, 4)

    def block_len(depth, row, dtype):
        calls.append(("decode", depth))
        return 16 if depth % 16 == 0 else None

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(dots3_note, "tiles", tiles)
    monkeypatch.setattr(m._ldec, "block_len", block_len)
    return calls


@pytest.mark.parametrize("chunk", [8, 16, 32, "16-kernels"])
def test_chunked_prefill_then_cached_decode_agree_with_the_reference(
        model, chunk, request):
    """Two rows in one batch, one left-padded by 5, to depths of 33 to
    38: past YaRN's original 16 positions twice over. The last case
    takes the chunks' and the steps' attention through the kernels."""
    _, cfg, params, hp = model
    calls = None
    if chunk == "16-kernels":
        calls = request.getfixturevalue("kernels")
        chunk = 16
    toks = _tokens(48, seed=5, batch=2)
    start, bucket, steps = [5, 0], 32, 6
    got, cache = _prefill_then_decode(cfg, params, toks, start, bucket,
                                      chunk, steps, depth=48)
    for r in range(2):
        want, _ = _ref_logits(params, toks[r:r + 1], hp)
        first = bucket - start[r] - 1
        err = jnp.abs(got[:, r] - want[first:first + steps + 1]).max()
        assert float(err) < 2e-4, (r, float(err))
    # the step's own count of what it sent to the held experts
    pairs, hit, tiles = (int(n) for n in cache["aux"])
    assert 0 < hit <= tiles <= pairs and hit <= 4 * cfg.experts_held
    if calls is not None:
        # one trace of the chunk program and one of the decode step:
        # every layer against the whole depth
        assert calls.count(("chunk", 4, 16, 48)) == 5
        assert ("decode", 48) in calls


def test_decode_kernel_reads_no_row_outside_its_range(kernels):
    """ops/pallas/latent_decode_attention.py against the absorbed plain
    form over rows with ranges of their own, one of them empty: rows
    outside [start, length] are poisoned and change nothing."""
    from ray_tpu.models import dots3_note
    from ray_tpu.ops.pallas import latent_decode_attention as ldec

    a = m.AttnSizes(4, 16, 8, 16, 32, 24, 1e4, None, 1.3)
    b, depth = 4, 64
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    layer = {"w_kvb_k": jax.random.normal(key[0], (24, 4, 16)) / 5,
             "w_kvb_v": jax.random.normal(key[1], (24, 4, 16)) / 5}
    q_nope = jax.random.normal(key[2], (b, 4, 16))
    q_rope = jax.random.normal(key[3], (b, 4, 8))
    rows = jax.random.normal(key[4], (2, b, depth, a.row))
    rows = rows.at[..., 32:].set(0.0)
    start = jnp.asarray([0, 7, 20, 3], jnp.int32)
    last = jnp.asarray([63, 18, 19, 40], jnp.int32)      # row 2: empty
    k_pos = jnp.arange(depth)[None, :]
    live = (k_pos >= start[:, None]) & (k_pos <= last[:, None])
    want = dots3_note._absorbed(a, layer, q_nope, q_rope, rows[1], live,
                                jnp.float32)
    q_abs = jnp.einsum("bhn,chn->bhc", q_nope, layer["w_kvb_k"])
    q_cat = jnp.concatenate([q_abs, q_rope,
                             jnp.zeros((b, 4, a.row - 32))], -1)
    poisoned = jnp.where(live[None, :, :, None], rows, 1e4)
    for stack in (rows, poisoned):
        o_lat = ldec.latent_decode_attention(
            q_cat, stack, 1, start, last, kv_rank=24, scale=a.scale,
            block_len=16)
        got = jnp.einsum("bhc,chv->bhv", o_lat, layer["w_kvb_v"])
        ok = np.asarray([0, 1, 3])
        assert float(jnp.abs(got[ok] - want[ok]).max()) < 1e-5
        assert float(jnp.abs(got[2]).max()) == 0.0
    assert ldec.block_len(24576, 640, jnp.bfloat16) == 16  # the fixture's
    kernels.clear()


def test_block_len_of_the_decode_kernel():
    from ray_tpu.ops.pallas import latent_decode_attention as ldec

    assert ldec.block_len(24576, 640, jnp.bfloat16) == 1024
    assert ldec.block_len(8192, 640, jnp.bfloat16) == 1024
    assert ldec.block_len(13312, 640, jnp.bfloat16) == 1024
    assert ldec.block_len(384, 640, jnp.bfloat16) == 128
    assert ldec.block_len(100, 640, jnp.bfloat16) is None


def test_absorbed_decode_equals_the_expanded_form(model):
    """A decode step scores and sums against the cached latent rows
    (absorbed); `forward` expands keys and values per head."""
    _, cfg, params, _ = model
    toks = _tokens(40, seed=7)
    want = jax.jit(lambda p, t: m.forward(p, t, cfg))(params, toks)
    got, _ = _prefill_then_decode(cfg, params, toks, [0], 32, 32, 7, 48)
    assert float(jnp.abs(got[:, 0] - want[0, 31:39]).max()) < 2e-4


# ------------------------------------------------------------- the experts
def test_shares_of_a_layer_add_up_to_the_uncut_layer():
    """model-configs section 4's share test: each of 8 holders computes
    its 2 of 16 experts' part for the same tokens; the parts and the
    shared expert, counted once, are the uncut reference's layer, the
    routed part times routed_scaling_factor 2.827."""
    twin = _twin(n_routed_experts=16, experts_first=0)
    cfg = latent_moe_model.program_config(twin, "serve", max_seq_len=64)
    assert cfg.experts_held == cfg.n_routed_experts == 16
    assert cfg.routed_scaling == 2.827
    layer = m.init_params(cfg, jax.random.PRNGKey(2))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(3), (40, cfg.dim))
    _, chosen, weights = moe.route_sigmoid_topk(
        h, layer["router"], layer["router_bias"], cfg.experts_per_tok,
        scaling=cfg.routed_scaling)
    assert float(jnp.abs(weights.sum(-1) - 2.827).max()) < 1e-5
    total = jnp.zeros_like(h)
    pairs = 0
    for share in range(8):
        cut = slice(2 * share, 2 * share + 2)
        y, n, *_ = moe.held_experts_ffn(
            h, chosen, weights, layer["we_gate"][cut], layer["we_up"][cut],
            layer["we_down"][cut], 2 * share)
        total, pairs = total + y, pairs + int(n)
    assert pairs == 40 * cfg.experts_per_tok      # every pair, once
    shared = (jax.nn.silu(h @ layer["ws_gate"]) * (h @ layer["ws_up"])
              ) @ layer["ws_down"]
    hp = latent_moe_model.reference_hp(twin)
    want, _, _ = ref._ffn(layer, h, hp, None, 2048)
    assert float(jnp.abs(total + shared - want).max()) < 2e-4
    # and the model's own layer, given one share, is its part of the sum
    from ray_tpu.models import dots3_note

    one = latent_moe_model.program_config(
        {**twin, "n_routed_experts": 2, "router_experts": 16,
         "experts_first": 6}, "serve", max_seq_len=64)
    mine = {**layer, **{k: layer[k][6:8] for k in ("we_gate", "we_up",
                                                   "we_down")}}
    x = jnp.zeros((1, 40, cfg.dim))
    out, (n, hit, _), _ = dots3_note._ffn(one, mine, x, None, False)
    assert out.shape == (1, 40, cfg.dim) and int(hit) <= 2


def test_cut_configuration_counts_its_parameters_and_cache_from_shapes():
    """benchmarks/configs/Kimi-K2.6.json as the program reads it: the
    published widths, 5 of 61 layers, 12 of 384 experts, an eighth of
    the vocabulary: 3.50 B parameters (6.99 GB in bfloat16) and 5.03 GB
    of latent rows for 32 slots x 24,576, from shapes alone."""
    full = _published()
    cfg = latent_moe_model.program_config(full, "serve", max_seq_len=24576)
    assert (cfg.dim, cfg.n_heads, cfg.hidden_dim, cfg.moe_hidden_dim) == \
        (7168, 64, 18432, 2048)
    assert (cfg.q_rank, cfg.kv_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (cfg.n_layers, cfg.n_routed_experts, cfg.experts_held,
            cfg.experts_first, cfg.vocab_size) == (5, 384, 12, 0, 20480)
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    shapes = jax.eval_shape(lambda: m.init_params(cfg, jax.random.PRNGKey(0)))
    count = lambda tree: sum(math.prod(a.shape)
                             for a in jax.tree.leaves(tree))
    layers = [count(layer) for layer in shapes["layers"]]
    attention = sum(math.prod(shapes["layers"][1][k].shape) for k in (
        "w_qa", "w_qb", "w_kva", "w_kvb_k", "w_kvb_v", "w_o"))
    assert attention == 101_122_048                       # 101.1 M
    assert layers == [497_500_160] + [676_413_824] * 4
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == 146_800_640
    assert cfg.num_params() == 3_496_763_904              # 3.50 B
    cache = jax.eval_shape(lambda: m.init_cache(cfg, 32, 24576))
    assert cache["latent"].shape == (5, 32, 24576, 640)
    assert math.prod(cache["latent"].shape) * 2 == 5_033_164_800
    # the uncut model, from the same code: 1.03 T
    uncut = m.from_published({**full, **full["published"],
                              "router_experts": 384})
    assert 1.02e12 < uncut.num_params() < 1.05e12


# -------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def served(model):
    """Seven greedy requests over three slots, prompts through every
    bucket, chunked at 8, the prefix store on."""
    _, cfg, params, hp = model
    eng = LLMEngine(cfg, tp=1, max_batch=3, prompt_buckets=(16, 32, 64),
                    prefill_chunk=8, params=params, prefix_cache_entries=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, size=n).tolist()
               for n in (5, 20, 33, 50, 12, 60, 7)]

    async def one(p):
        return [t async for t in eng.generate(p, max_new_tokens=10)]

    async def run():
        return await asyncio.gather(*[one(p) for p in prompts])

    return eng, prompts, asyncio.run(run())


def test_engine_streams_the_greedy_references_tokens(served, model):
    _, cfg, params, hp = model
    eng, prompts, outs = served
    for p, o in zip(prompts, outs):
        seq = p + o
        toks = np.zeros((1, 96), np.int32)
        toks[0, :len(seq)] = seq
        want, _ = _ref_logits(params, jnp.asarray(toks), hp)
        assert np.asarray(want)[len(p) - 1:len(seq) - 1].argmax(-1).tolist() \
            == o, len(p)
    stats = eng.stats()
    assert stats["prefills"] == 7 and stats["prefill_chunks"] > 7
    assert stats["decode_overlapped"] > 0
    assert stats["cache_bytes"] == {"kv": 0, "state": 0,
                                    "latent": 5 * 3 * 96 * 128 * 4}


def test_engines_own_steps_give_the_references_logits(served, model):
    """Chunked prefill, the engine's own `insert_row` into its own
    slots, cached decode with per-row depths and `retire`, for two of
    the streams the engine served: the logits, not the tokens, against
    the reference's full forward (the cell's check, at the twin's
    limits)."""
    twin, cfg, params, _ = model
    eng, prompts, outs = served
    samples = [{"tokens": prompts[i], "generated": outs[i]} for i in (3, 1)]
    service = types.SimpleNamespace(config=twin, engine=eng)
    checks = latent_moe_model.reference_check(service, samples, 64, 8)
    assert latent_moe_model.correct({"checks": checks}, twin["tolerances"])
    for c in checks:
        assert c["logits_rel_rms_forced"] < 1e-5 and c["router_margin"] == 0
        assert c["token_margin_program"] == 0 == c["token_margin_logits"]
    counted = checks[0]["engine_since_start"]
    assert counted["decode_rounds_per_chunk"] > 0
    assert counted["decode_latent_read_over_live"] > 1
    # streams swapped between the rows are not correct
    swapped = [{"tokens": prompts[3], "generated": outs[1]},
               {"tokens": prompts[1], "generated": outs[3]}]
    bad = latent_moe_model.reference_check(service, swapped, 64, 8)
    assert not latent_moe_model.correct({"checks": bad}, twin["tolerances"])


def test_engine_counts_live_and_read_positions_and_routed_rows(served, model):
    _, cfg, _, _ = model
    eng, prompts, _ = served
    stats = eng.stats()
    live = stats["decode_kv_positions_live"]
    assert stats["decode_latent_positions_live"] == 5 * live > 0
    # nothing bounds the read on the CPU: every layer whole, every step
    assert stats["decode_latent_positions_read"] == \
        5 * stats["batches"] * 3 * 96
    assert stats["decode_kv_positions_read"] == stats["batches"] * 3 * 96
    # 63 token steps x 4 of 16 experts a token, half of them held, in 4
    # expert layers: the expectation is 504
    assert 300 < stats["moe_expert_rows"] < 700
    assert 0 < stats["moe_experts_hit"] <= 4 * 8 * stats["batches"]
    assert stats["moe_experts_hit"] <= stats["moe_expert_tiles"] \
        <= stats["moe_expert_rows"]
    # every prompt token is a query once, in five layers, and sees every
    # position from its row's first to itself
    assert stats["prefill_latent_keys_visible"] == 5 * sum(
        n * (n + 1) // 2 for n in map(len, prompts))
    assert stats["prefill_latent_keys_visited"] > \
        stats["prefill_latent_keys_visible"]


def test_decode_counters_from_row_ranges(model, monkeypatch):
    """The formulas: live positions are the ranges' lengths, read
    positions the blocks that overlap them (the whole cache where no
    kernel bounds the read), both times the layers."""
    _, cfg, _, _ = model
    spans = [(0, 40), (5, 17), (30, 95)]
    got = m.decode_counters(cfg, spans, 3)
    assert got == {"decode_latent_positions_live": 5 * (41 + 13 + 66),
                   "decode_latent_positions_read": 5 * 3 * 96}
    assert m.decode_read_block(cfg, None) is None
    assert m.decode_counters(cfg, [], 3)["decode_latent_positions_live"] == 0
    monkeypatch.setattr(m, "_read_block", lambda cfg, depth: 16)
    got = m.decode_counters(cfg, spans, 3)
    # blocks 0-2, 0-1 and 1-5 of 16 positions
    assert got["decode_latent_positions_read"] == 5 * 16 * (3 + 2 + 5)
    assert got["decode_latent_positions_read"] >= \
        got["decode_latent_positions_live"]
    assert m.decode_counters(cfg, [], 3)["decode_latent_positions_read"] == 0


def test_prefill_counters_from_where_the_row_and_the_chunk_lie(model,
                                                              kernels):
    _, cfg, _, _ = model
    # plain form: blocks as deep as the cache (under 1,024), whole
    from ray_tpu.ops import attention

    with mock.patch.object(attention, "_on_tpu", lambda: False):
        got = m.prefill_counters(cfg, 5, 0, 16, 48)
    assert got == {"prefill_latent_keys_visited": 5 * 16 * 48,
                   "prefill_latent_keys_visible": 5 * sum(range(1, 12))}
    # the kernel's tiles of 8 x 4: queries 16..31 of a row from 5, keys
    # 4..31 in tiles (the tile that holds position 5 from its start)
    got = m.prefill_counters(cfg, 5, 16, 16, 48)
    visible = sum(range(12, 28))
    assert got["prefill_latent_keys_visible"] == 5 * visible
    tiles = sum(8 * 4 * len([k for k in range(0, 48, 4)
                             if k + 3 >= 5 and k <= t0 + 7])
                for t0 in (16, 24))
    assert got["prefill_latent_keys_visited"] == 5 * tiles
    assert visible < tiles < 16 * 48


# -------------------------------------------------------- the prefix store
def test_prefix_store_holds_this_models_rows_and_grafts_them(model):
    """The first model besides llama whose every cache leaf has a
    position axis: a prompt whose block-aligned prefix is grafted from a
    stored row gives the logits of the same prompt prefilled whole."""
    _, cfg, params, _ = model
    eng = LLMEngine(cfg, tp=1, max_batch=2, prompt_buckets=(32, 64),
                    prefill_chunk=0, params=params, prefix_cache_entries=4)
    assert eng.stats()["prefix_cache_entries"] == 4
    rng = np.random.default_rng(3)
    shared = rng.integers(1, 256, size=37).tolist()
    first = shared + rng.integers(1, 256, size=9).tolist()     # bucket 64
    second = shared + rng.integers(1, 256, size=20).tolist()   # bucket 64

    async def one(p, eng=eng):
        return [t async for t in eng.generate(p, max_new_tokens=6)]

    out_first = asyncio.run(one(first))
    assert eng.stats()["prefix_entries"] == 1 and len(out_first) == 6
    entry, matched = eng._prefix_lookup(second)
    assert matched == 32 and entry["row"]["latent"].shape == (5, 1, 64, 128)
    # the graft, then the tail: the logits of the whole prompt's prefill
    bucket, start = 64, 64 - len(second)
    step = jax.jit(lambda p, c, t: m.decode_step(p, c, t, cfg))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, start:] = second
    small = eng._prefill_cache(bucket, start, 0, (entry, matched))
    assert int(small["length"]) == start + matched
    got, _ = step(params, small, jnp.asarray(padded[:, start + matched:]))
    whole = eng._prefill_cache(bucket, start, 0, None)
    want, _ = step(params, whole, jnp.asarray(padded))
    assert float(jnp.abs(got - want).max()) < 2e-4
    # and through the engine: a hit, and the stream of an engine that
    # keeps no store
    out_second = asyncio.run(one(second))
    stats = eng.stats()
    assert (stats["prefix_hits"], stats["prefix_hit_tokens"]) == (1, 32)
    cold = LLMEngine(cfg, tp=1, max_batch=2, prompt_buckets=(32, 64),
                     prefill_chunk=0, params=params,
                     prefix_cache_entries=0)
    assert asyncio.run(one(second, cold)) == out_second
    assert cold.stats()["prefix_cache_entries"] == 0


@pytest.mark.parametrize("name", ["dots3_note", "granite_hybrid", "evabyte"])
def test_prefix_store_stays_empty_for_a_model_with_a_recurrent_leaf(name):
    """A ring, a state or a window's rows cannot be cut by position:
    asked for 8 entries, those engines keep none."""
    import importlib

    mod = importlib.import_module("ray_tpu.models." + name)
    cfg = {
        "dots3_note": lambda: mod.Dots3NoteConfig(
            vocab_size=64, dim=32, hidden_dim=48, moe_hidden_dim=16,
            n_routed_experts=4, experts_per_tok=2, n_heads=2,
            qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8, q_rank=16,
            kv_rank=16, swa_n_heads=2, swa_qk_nope_dim=8,
            swa_qk_rope_dim=8, swa_v_head_dim=8, swa_q_rank=16,
            swa_kv_rank=16, sliding_window=5, index_n_heads=2,
            index_head_dim=8, index_topk=4, ring_multiple=4,
            layer_types=("full_attention", "sliding_attention"),
            max_seq_len=64, dtype=jnp.float32, param_dtype=jnp.float32),
        "granite_hybrid": lambda: mod.GraniteHybridConfig(
            vocab_size=64, dim=32, layer_types=("mamba", "attention"),
            n_heads=2, n_kv_heads=2, hidden_dim=48, mamba_n_heads=4,
            mamba_d_head=16, mamba_d_state=16, max_seq_len=64,
            dtype=jnp.float32, param_dtype=jnp.float32),
        "evabyte": lambda: mod.EvaByteConfig(
            vocab_size=32, dim=32, n_layers=1, n_heads=2, hidden_dim=48,
            window_size=8, chunk_size=2, n_pred_heads=2, max_seq_len=64,
            dtype=jnp.float32, param_dtype=jnp.float32),
    }[name]()
    eng = LLMEngine(cfg, tp=1, max_batch=2, prompt_buckets=(16,),
                    prefix_cache_entries=8)
    assert eng.stats()["prefix_cache_entries"] == 0


# ------------------------------------------------------------ the precision
def _check(twin, cfg, params, seed=0, step_cfg=None, step_params=None):
    """latent_moe_model.reference_check on two prompts of the twin, at
    an engine of three slots; the program steps with `step_params` and
    `step_cfg` where given (the reference keeps `params` and the file)."""
    rng = np.random.default_rng(seed)
    service = types.SimpleNamespace(config=twin, engine=LLMEngine(
        step_cfg or cfg, tp=1, max_batch=3, prompt_buckets=(64,),
        prefill_chunk=8, params=params))
    samples = [{"tokens": rng.integers(1, 256, size=n).tolist(),
                "generated": rng.integers(1, 256, size=8).tolist()}
               for n in (20, 50)]
    if step_params is None:
        return latent_moe_model.reference_check(service, samples, 64, 8)
    step = m.decode_step
    # the check keeps its jitted steps by config: traced anew under the
    # patch, and again without it
    latent_moe_model._collect_steps.cache_clear()
    try:
        with mock.patch.object(m, "decode_step", lambda p, *a, **kw: step(
                step_params, *a, **kw)):
            return latent_moe_model.reference_check(service, samples, 64, 8)
    finally:
        latent_moe_model._collect_steps.cache_clear()


# the two limits that read the arithmetic. The others are the
# rehearsal's, which serves the twin in float32, and the stream's own
# (test_engines_own_steps_give_the_references_logits): the samples here
# continue with random tokens, not with the greedy stream
_LIMITS = ("logits_rel_rms_forced", "router_margin")
BF16_TOL = {"logits_rel_rms_forced": 0.05, "router_margin": 0.25}


def test_lower_precision_and_plain_rope_fail_the_forced_comparison():
    """The twin in bfloat16, as the configuration states, stays under
    the limits the bfloat16 twin is given here; with the program's
    matrices rounded to fp8's mantissa or to multiples of 1/8 (the
    reference's not) it passes both; and a program that leaves YaRN out
    (plain RoPE, no temperature on the scores) fails the logits' limit
    at the twin's depths, eight times the original context, though it
    chooses the experts well enough."""
    twin = _twin(BF16)
    cfg = latent_moe_model.program_config(twin, "serve", max_seq_len=96)
    params = m.init_params(cfg, jax.random.PRNGKey(4))
    stated = _check(twin, cfg, params)
    assert all(c["finite"] for c in stated)
    for name in _LIMITS:
        assert max(c[name] for c in stated) <= BF16_TOL[name], (name, stated)
    for how in (lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype),
                lambda a: (jnp.round(a * 8) / 8).astype(a.dtype)):
        coarse = jax.tree.map(lambda a: how(a) if a.ndim > 1 else a, params)
        bad = _check(twin, cfg, params, step_params=coarse)
        for name in _LIMITS:
            assert min(c[name] for c in bad) > BF16_TOL[name], (name, bad)
    plain = latent_moe_model.program_config(
        {**twin, "rope_scaling": None}, "serve", max_seq_len=96)
    latent_moe_model._collect_steps.cache_clear()
    bad = _check(twin, cfg, params, step_cfg=plain)
    assert min(c["logits_rel_rms_forced"] for c in bad) > \
        BF16_TOL["logits_rel_rms_forced"], bad


def test_published_configuration_is_refused_where_it_asks_for_more():
    full = _published()
    for over in ({"n_group": 8, "topk_group": 4}, {"scoring_func": "softmax"},
                 {"num_nextn_predict_layers": 1},
                 {"tie_word_embeddings": True},
                 {"rope_scaling": {**full["rope_scaling"], "mscale": 0.707}},
                 {"num_key_value_heads": 8}):
        with pytest.raises(ValueError):
            m.from_published({**full, **over})
    with pytest.raises(ValueError, match="held experts"):
        m.from_published({**full, "experts_first": 380})
