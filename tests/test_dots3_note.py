"""The `dots3_note` decoder (models/dots3_note.py, ops/moe.py's dropless
expert layer) on the CPU, at the rehearsal twin's sizes: five layers
(full and dense, full, three sliding), index_topk 12 and a window of 9
far under the context, so that both select, a ring of 12 rows that
turns, and a left-padded row. Held against the plain reference
(benchmarks/reference/dots3_note_ref.py), which imports nothing of the
program. Nothing here is a device number."""

import asyncio
import json
import os
import types
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest as manifest_mod
from benchmarks import rehearsal, sparse_moe_model
from benchmarks.reference import dots3_note_ref as ref
from ray_tpu.models import dots3_note as m
from ray_tpu.models import module_for
from ray_tpu.ops import moe
from ray_tpu.serve.llm import LLMEngine

ROOT = manifest_mod.ROOT
F32 = {"param_dtype": "float32", "compute_dtype": "float32"}
BF16 = {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"}


def _twin(held=F32, **over) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "dots3-note-prev.json")) as f:
        full = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "rehearsal", "configs",
                           "dots3-note-prev.json")) as f:
        twin = rehearsal.overlay(full, json.load(f))
    return {**twin, "held_as": {"serve": held}, **over}


@pytest.fixture(scope="module")
def model():
    """(config file, program config, params, reference hp) of the twin
    in float32: this holder has experts 4-11 of 16."""
    twin = _twin()
    cfg = sparse_moe_model.program_config(twin, "serve", max_seq_len=96)
    params = m.init_params(cfg, jax.random.PRNGKey(0))
    return twin, cfg, params, sparse_moe_model.reference_hp(twin)


def _tokens(n, seed=1, batch=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, n), 1, 256)


def _ref_logits(params, toks, hp, **kw):
    rows = jnp.arange(toks.shape[1])
    return jax.jit(lambda p, t: ref.logits_and_choices(p, t, hp, rows, **kw))(
        params, toks)


def test_twin_has_both_kinds_of_layer_and_selects(model):
    twin, cfg, params, hp = model
    assert module_for(cfg) is m and not m.TENSOR_PARALLEL
    assert cfg.layer_types == ("full_attention",) * 2 + (
        "sliding_attention",) * 3
    assert (cfg.index_topk, cfg.sliding_window, cfg.ring_len) == (12, 9, 12)
    assert (cfg.n_routed_experts, cfg.experts_first, cfg.experts_held) == \
        (16, 4, 8)
    assert "router" not in params["layers"][0]
    assert params["layers"][1]["we_gate"].shape == (8, 64, 32)
    assert params["layers"][1]["router"].shape == (64, 16)
    assert params["layers"][1]["router_bias"].dtype == jnp.float32
    assert float(jnp.abs(params["layers"][1]["router_bias"]).max()) > 0


def test_forward_agrees_with_the_reference(model):
    _, cfg, params, hp = model
    toks = _tokens(48)
    got, seen = jax.jit(lambda p, t: m.forward(p, t, cfg, collect=True))(
        params, toks)
    want, theirs = _ref_logits(params, toks, hp)
    assert float(jnp.abs(got[0] - want).max()) < 2e-4
    for mine, own in zip(seen["selected"], theirs["selected"]):
        assert bool((mine[0] == own).all())
    for mine, own in zip(seen["chosen"], theirs["chosen"]):
        assert bool((jnp.sort(mine[0], -1) == jnp.sort(own, -1)).all())


def test_indexer_takes_the_references_set_and_everything_under_topk(model):
    _, cfg, params, hp = model
    toks = _tokens(40, seed=3)
    _, seen = jax.jit(lambda p, t: m.forward(p, t, cfg, collect=True))(
        params, toks)
    _, theirs = _ref_logits(params, toks, hp)
    for mine, own, scores in zip(seen["selected"], theirs["selected"],
                                 theirs["index_scores"]):
        counts = np.asarray(mine[0].sum(-1))
        # every position while there are no more than index_topk
        assert counts.tolist() == [min(t + 1, 12) for t in range(40)]
        assert bool((mine[0] == own).all())
        # and they are the largest scores: none left out beats one kept
        kept = jnp.where(own, scores, jnp.inf).min(-1)
        left = jnp.where(~own & jnp.isfinite(scores), scores, -jnp.inf).max(-1)
        assert bool((left <= kept).all())


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
    for p0 in range(0, bucket, chunk):
        logits, cache = step(params, cache, jnp.asarray(padded[:, p0:p0 + chunk]))
    outs = [logits]
    cache["length"] = jnp.full((b,), bucket, jnp.int32)
    for i in range(steps):
        nxt = jnp.stack([toks[r, bucket - start[r] + i] for r in range(b)])
        logits, cache = step(params, cache, nxt[:, None])
        outs.append(logits)
    return jnp.stack(outs), cache


@pytest.fixture
def chunk_kernel(monkeypatch):
    """A chunk's attention through ops/pallas/latent_attention.py
    (interpreted), as a TPU takes it for shapes whole in the kernel's
    tiles: here in tiles of 8 queries x 4 keys, which the twin's chunk
    of 16, its depth of 48 and its ring of 12 are whole in."""
    from ray_tpu.ops import attention
    from ray_tpu.ops.pallas import latent_attention as la

    calls = []

    def tiles(heads, nope, rope, v, kv_rank, queries, keys):
        if queries % 8 or keys % 4:
            return None
        calls.append((heads, queries, keys))
        return la.Tiles(2, 8, 4)

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(m, "tiles", tiles)
    return calls


@pytest.mark.parametrize("chunk", [8, 16, 32, "16-kernel"])
def test_chunked_prefill_then_cached_decode_agree_with_the_reference(
        model, chunk, request):
    """Two rows in one batch, one left-padded by 5; the ring (12 rows)
    turns twice over the prompt and again during decode; the selection
    picks 12 of up to 38 positions. The last case takes the chunks'
    attention through the kernel."""
    _, cfg, params, hp = model
    kernel_calls = None
    if chunk == "16-kernel":
        kernel_calls = request.getfixturevalue("chunk_kernel")
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
    if kernel_calls is not None:
        # one trace of the chunk program: two full layers against the
        # depth, three sliding ones against ring + chunk; no decode step
        assert kernel_calls == [(4, 16, 48)] * 2 + [(2, 16, 28)] * 3


def test_absorbed_decode_equals_the_expanded_form(model):
    """A decode step scores and sums against the cached latent rows
    (absorbed); `forward` expands keys and values per head."""
    _, cfg, params, _ = model
    toks = _tokens(40, seed=7)
    want = jax.jit(lambda p, t: m.forward(p, t, cfg))(params, toks)
    got, _ = _prefill_then_decode(cfg, params, toks, [0], 32, 32, 7, 48)
    assert float(jnp.abs(got[:, 0] - want[0, 31:39]).max()) < 2e-4


def test_ring_holds_the_last_positions_whatever_the_depth_or_the_chunks(
        model):
    _, cfg, params, _ = model
    shapes = {d: jax.eval_shape(lambda: m.init_cache(cfg, 2, d))
              for d in (32, 96)}
    assert shapes[32]["window"].shape == shapes[96]["window"].shape == \
        (3, 2, 12, 128)
    assert shapes[96]["latent"].shape == (2, 2, 96, 128)
    assert shapes[96]["index"].shape == (2, 2, 96, 16)
    # 64 positions in chunks of 16 (the ring turns under them, a chunk
    # wraps it) and in one call of 64 (only the last 12 rows are kept):
    # position p lies in row p mod 12 either way
    toks = _tokens(80, seed=9)
    _, by16 = _prefill_then_decode(cfg, params, toks, [3], 64, 16, 0, 64)
    _, by64 = _prefill_then_decode(cfg, params, toks, [3], 64, 64, 0, 64)
    assert float(jnp.abs(by16["window"]).min(-1).max()) == 0  # the fill
    assert float(jnp.abs(by16["window"]).sum()) > 0
    assert float(jnp.abs(by16["window"] - by64["window"]).max()) < 1e-4


# ------------------------------------------------------------- the experts
def _expert_weights(key, e, d, f):
    k = jax.random.split(key, 3)
    return (jax.random.normal(k[0], (e, d, f)) / np.sqrt(d),
            jax.random.normal(k[1], (e, d, f)) / np.sqrt(d),
            jax.random.normal(k[2], (e, f, d)) / np.sqrt(f))


@pytest.mark.parametrize("choices", [(2, 9), (2,) * 8],
                         ids=["once", "every-pair"])
def test_all_tokens_to_one_expert_lose_none(choices):
    """Expert 9 is not held; with every choice on expert 2 a token's
    pairs lie eight times in one group, which no router gives and the
    layer still sums."""
    wg, wu, wd = _expert_weights(jax.random.PRNGKey(0), 4, 32, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (300, 32))
    chosen = jnp.stack([jnp.full((300,), e) for e in choices], -1)
    weights = jax.random.uniform(jax.random.PRNGKey(2), chosen.shape)
    y, pairs, hit, tiles = jax.jit(lambda *a: moe.held_experts_ffn(*a))(
        x, chosen, weights, wg, wu, wd)
    on_two = jnp.where(chosen == 2, weights, 0).sum(-1, keepdims=True)
    want = ((jax.nn.silu(x @ wg[2]) * (x @ wu[2])) @ wd[2]) * on_two
    assert float(jnp.abs(y - want).max()) < 1e-5
    n = 300 * choices.count(2)
    assert (int(pairs), int(hit), int(tiles)) == (n, 1, -(-n // 128))


@pytest.mark.parametrize("tokens,top_k,first,held,every", [
    (50, 3, 4, 4, 7),
    (64, 3, 4, 4, 7),         # the last T whose tiles are 16 rows
    (65, 3, 4, 4, 7),         # the first whose tiles are 128
    (50, 3, 16, 4, 7),        # the router never chooses 16..19
    (50, 3, 4, 4, 1),         # no row is a token
    (32, 8, 4, 4, 7),         # a decode step's rows: tiles of 16
    (50, 8, 0, 16, 7),        # all eight choices of every token held
    (200, 3, 4, 4, 7),        # a chunk's rows: tiles of 128
    (50, 3, 4, 1, 7),         # ONE held expert: a plain SwiGLU FFN
], ids=["tiles-default", "tiles-16-at-64", "tiles-128-at-65", "no-pair-held",
        "every-row-invalid", "decode-32", "all-choices-held", "chunk-200",
        "one-expert-held"])
def test_held_experts_part_equals_the_dense_sum(tokens, top_k, first, held,
                                                every):
    wg, wu, wd = _expert_weights(jax.random.PRNGKey(3), 16, 32, 16)
    x = jax.random.normal(jax.random.PRNGKey(4), (tokens, 32))
    router = jax.random.normal(jax.random.PRNGKey(5), (32, 16)) / np.sqrt(32)
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    _, chosen, weights = moe.route_sigmoid_topk(x, router, bias, top_k)
    valid = jnp.arange(tokens) % every != 0
    y, pairs, hit, tiles = moe.held_experts_ffn(
        x, chosen, weights, wg[:held], wu[:held], wd[:held], first,
        valid=valid)
    want = jnp.zeros_like(x)
    counts = []
    for e in range(held):
        on_e = (chosen == first + e) & valid[:, None]
        g = jnp.where(on_e, weights, 0).sum(-1)
        want += ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]) * g[:, None]
        counts.append(int(on_e.sum()))
    assert y.shape == x.shape and y.dtype == jnp.float32
    assert float(jnp.abs(y - want).max()) < 1e-5
    assert int(pairs) == sum(counts)
    assert int(hit) == sum(c > 0 for c in counts)
    # the tiles the kernel walks: each expert's pairs in whole tiles, and
    # no tile for an expert nobody chose
    bm = 16 if tokens <= 64 else 128
    assert int(tiles) == sum(-(-c // bm) for c in counts)
    if first == 16 or every == 1:
        assert sum(counts) == 0 and not bool(y.any())
    if held == 16:              # the whole router: every choice is held
        assert sum(counts) == top_k * int(valid.sum())


def test_bias_moves_the_choice_and_not_the_weight():
    x = jax.random.normal(jax.random.PRNGKey(7), (64, 32))
    router = jax.random.normal(jax.random.PRNGKey(8), (32, 16)) / np.sqrt(32)
    zero = jnp.zeros((16,))
    push = zero.at[5].set(10.0)           # expert 5 is always chosen
    s0, c0, w0 = moe.route_sigmoid_topk(x, router, zero, 4)
    s1, c1, w1 = moe.route_sigmoid_topk(x, router, push, 4)
    assert bool((s0 == s1).all())
    assert bool((c1 == 5).any(-1).all()) and not bool((c0 == 5).any(-1).all())
    # weights are the unbiased scores of whoever was chosen, over their sum
    picked = jnp.take_along_axis(s1, c1, -1)
    assert float(jnp.abs(w1 - picked / picked.sum(-1, keepdims=True)).max()) \
        < 1e-6
    assert float(jnp.abs(w1.sum(-1) - 1).max()) < 1e-6


def test_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """model-configs section 4's share test: each of 8 holders computes
    its 2 of 16 experts' part for the same tokens; the parts and the
    shared expert, counted once, are the uncut reference's layer."""
    twin = _twin(n_routed_experts=16, experts_first=0)
    cfg = sparse_moe_model.program_config(twin, "serve", max_seq_len=64)
    assert cfg.experts_held == cfg.n_routed_experts == 16
    layer = m.init_params(cfg, jax.random.PRNGKey(2))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(3), (40, cfg.dim))
    _, chosen, weights = moe.route_sigmoid_topk(
        h, layer["router"], layer["router_bias"], cfg.experts_per_tok)
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
    hp = sparse_moe_model.reference_hp(twin)
    want, _, _ = ref._ffn(layer, h, hp, None)
    assert float(jnp.abs(total + shared - want).max()) < 1e-4
    # and one share through the model's own layer is its part of that sum
    one = sparse_moe_model.program_config(
        {**twin, "n_routed_experts": 2, "router_experts": 16,
         "experts_first": 6}, "serve", max_seq_len=64)
    mine = {**layer, **{k: layer[k][6:8] for k in ("we_gate", "we_up",
                                                   "we_down")}}
    out, (n, hit, _), _ = m._ffn(one, mine, jnp.zeros((1, 40, cfg.dim)),
                                 None, False)
    assert out.shape == (1, 40, cfg.dim) and int(hit) <= 2


# -------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def served(model):
    """Seven greedy requests over three slots, prompts through every
    bucket, chunked at 8."""
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


def test_cache_bytes_by_kind_and_a_ring_that_ignores_max_seq_len(served,
                                                                 model):
    _, cfg, params, _ = model
    eng = served[0]
    # latent row 24 + 8 filled to 128; index keys 16; ring 12 rows of
    # 40 + 8 filled to 128; float32 here
    assert eng.stats()["cache_bytes"] == {
        "kv": 0, "state": 0,
        "latent": 2 * 3 * 96 * 128 * 4, "index": 2 * 3 * 96 * 16 * 4,
        "window": 3 * 3 * 12 * 128 * 4}
    import dataclasses
    deeper = LLMEngine(dataclasses.replace(cfg, max_seq_len=192), tp=1,
                       max_batch=3, prompt_buckets=(16,), params=params)
    assert deeper.stats()["cache_bytes"]["window"] == 3 * 3 * 12 * 128 * 4
    assert deeper.stats()["cache_bytes"]["latent"] == 2 * 3 * 192 * 128 * 4


def test_prefix_store_holds_nothing_for_a_model_with_a_ring(served):
    eng = served[0]
    assert eng.stats()["prefix_cache_entries"] == 0
    assert eng.stats()["prefix_entries"] == 0 and eng.stats()["prefix_hits"] == 0


def test_engine_counts_what_the_steps_scored_attended_and_routed(served,
                                                                 model):
    _, cfg, _, _ = model
    stats = served[0].stats()
    live = stats["decode_kv_positions_live"]
    assert stats["decode_index_positions_scored"] == 2 * live
    assert 0 < stats["decode_latent_positions_attended"] <= 2 * 12 * 63
    assert 0 < stats["decode_window_positions_attended"] <= 3 * 9 * 63
    # 63 token steps x 4 of 16 experts a token, half of them held, in 4
    # expert layers: the expectation is 504; every pair lands on an expert
    assert 300 < stats["moe_expert_rows"] < 700
    assert 0 < stats["moe_experts_hit"] <= stats["moe_expert_tiles"] \
        <= stats["moe_expert_rows"]
    assert stats["moe_experts_hit"] <= 4 * 8 * stats["batches"]
    # the chunks' attention (the plain form here): every prompt token is
    # a query once, in two full layers at up to 12 selected positions
    # and three sliding ones at up to 9
    eng, prompts, _ = served
    want = {"latent": 0, "window": 0}
    for p in prompts:
        for depth in range(1, len(p) + 1):
            want["latent"] += 2 * min(depth, 12)
            want["window"] += 3 * min(depth, 9)
    assert stats["prefill_latent_keys_visible"] == want["latent"]
    assert stats["prefill_window_keys_visible"] == want["window"]
    assert stats["prefill_latent_keys_visited"] > want["latent"]
    # a chunk of 8 against a ring of 12: 20 keys a query, padding too
    chunked = sum(-(-len(p) // 8) * 8 for p in prompts if len(p) > 8)
    assert stats["prefill_window_keys_visited"] >= 3 * 20 * chunked


def test_prefill_counters_from_where_the_row_and_the_chunk_lie(
        model, monkeypatch):
    _, cfg, _, _ = model                 # index_topk 12, window 9, ring 12
    names = ("prefill_latent_keys_visited", "prefill_latent_keys_visible",
             "prefill_window_keys_visited", "prefill_window_keys_visible")
    # the plain form, as here on the CPU. Left padding of 5 inside the
    # first block of a cache 48 deep (one block): 16 queries from
    # position 0, 11 of them real, at depths 1..11
    got = m.prefill_counters(cfg, 5, 0, 16, 48)
    assert tuple(got) == names
    assert got == dict(zip(names, (
        2 * 16 * 48, 2 * sum(range(1, 12)),
        3 * 16 * (12 + 16), 3 * (sum(range(1, 10)) + 2 * 9))))
    # a chunk that ends the bucket, all real, far over topk and window
    got = m.prefill_counters(cfg, 5, 32, 16, 48)
    assert got == dict(zip(names, (2 * 16 * 48, 2 * 16 * 12,
                                   3 * 16 * 28, 3 * 16 * 9)))
    # blocks of 1,024: a chunk of 1,024 at 2,048 of a row that starts at
    # 1,100 walks blocks 1 and 2
    assert m.prefill_counters(cfg, 1100, 2048, 1024, 4096)[names[0]] == \
        2 * 1024 * 2 * 1024
    # padding only: nothing visible
    got = m.prefill_counters(cfg, 40, 0, 16, 48)
    assert got[names[1]] == 0 and got[names[3]] == 0
    assert m.prefill_counters(cfg, 0, 0, 0, 96) == dict.fromkeys(names, 0)

    # the kernel's tiles, 8 queries x 4 keys
    from ray_tpu.ops import attention
    from ray_tpu.ops.pallas import latent_attention as la
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(m, "tiles", lambda *a: la.Tiles(2, 8, 4))
    got = m.prefill_counters(cfg, 5, 0, 16, 48)
    # full: queries 0-7 reach key tile 1 (positions 4-7) alone, queries
    # 8-15 tiles 1 to 3. Sliding: the ring is empty (nothing before
    # position 0), the window of 9 reaches back to position 5 - 8 < 4
    # from queries 5-7 (tile 4-7) and to 8 - 8 = 0 from queries 8-15,
    # of which tiles 4-7, 8-11 and 12-15 hold a real position
    assert got[names[0]] == 2 * 8 * 4 * (1 + 3)
    assert got[names[2]] == 3 * 8 * 4 * (1 + 3)
    assert got[names[1]] == 2 * sum(range(1, 12))
    # a later chunk: the ring holds positions 20-31 (slot p % 12), the
    # chunk 32-47. Queries 32-39 see 24-39: ring slots 0-7 (24-31), in
    # ring tiles 0 and 1, and the chunk's first two tiles; queries 40-47
    # see 32-47: the chunk's four tiles
    got = m.prefill_counters(cfg, 5, 32, 16, 48)
    assert got[names[2]] == 3 * 8 * 4 * (4 + 4)
    assert got[names[0]] == 2 * 8 * 4 * (9 + 11)
    # and the tiles are what the kernel's own tables say of that mask
    pos = 32 + np.arange(16)
    k_pos = np.concatenate([31 - (31 - np.arange(12)) % 12, pos])
    dist = pos[:, None] - k_pos[None, :]
    mask = (dist >= 0) & (dist < 9) & (k_pos >= 5)[None, :]
    live, _ = la.tile_tables(jnp.asarray(mask)[None], la.Tiles(2, 8, 4))
    assert int(live.sum()) == 4 + 4


def test_decode_counters_from_row_ranges(model):
    _, cfg, _, _ = model
    got = m.decode_counters(cfg, [(0, 4), (10, 40)], 2)   # depths 5 and 31
    assert got == {"decode_index_positions_scored": 2 * 36,
                   "decode_latent_positions_attended": 2 * (5 + 12),
                   "decode_window_positions_attended": 3 * (5 + 9)}
    assert m.decode_counters(cfg, [], 2) == dict.fromkeys(got, 0)
    assert m.decode_read_block(cfg, None) is None


# ------------------------------------------------------------ the precision
def _check(twin, cfg, params, seed=0, step_params=None):
    """sparse_moe_model.reference_check on two prompts of the twin, at
    an engine of three slots; the program steps with `step_params` where
    given (the reference keeps `params`)."""
    rng = np.random.default_rng(seed)
    service = types.SimpleNamespace(config=twin, engine=LLMEngine(
        cfg, tp=1, max_batch=3, prompt_buckets=(64,), prefill_chunk=8,
        params=params))
    samples = [{"tokens": rng.integers(1, 256, size=n).tolist(),
                "generated": rng.integers(1, 256, size=8).tolist()}
               for n in (20, 50)]
    if step_params is None:
        return sparse_moe_model.reference_check(service, samples, 64, 8)
    step = m.decode_step
    # the check keeps its jitted steps by config: traced anew under the
    # patch, and again without it
    sparse_moe_model._collect_steps.cache_clear()
    try:
        with mock.patch.object(m, "decode_step", lambda p, *a, **kw: step(
                step_params, *a, **kw)):
            return sparse_moe_model.reference_check(service, samples, 64, 8)
    finally:
        sparse_moe_model._collect_steps.cache_clear()


# the four limits this model brings. The other three are the
# rehearsal's, which serves the twin in float32: free-running, a
# bfloat16 twin swaps a position or an expert at a margin and its five
# layers' logits move by more than all their rounding; and the samples
# here continue with random tokens, not with the greedy stream (the
# stream's own limits: test_a_fault_planted_in_the_engine_fails_the_check)
_LIMITS = ("logits_rel_rms_forced", "index_score_rel_rms",
           "selection_margin", "router_margin")


def test_lower_precision_fails_the_forced_comparison():
    """The twin in bfloat16, as the configuration states, stays under
    each of the twin's limits; with the program's matrices rounded to
    fp8's mantissa or to multiples of 1/8 (the reference's not) it
    passes all four that this model brings."""
    twin = _twin(BF16)
    tol = twin["tolerances"]
    cfg = sparse_moe_model.program_config(twin, "serve", max_seq_len=96)
    params = m.init_params(cfg, jax.random.PRNGKey(4))
    stated = _check(twin, cfg, params)
    assert all(c["finite"] for c in stated)
    assert max(c["logits_rel_rms_forced"] for c in stated) \
        <= tol["logits_rel_rms_forced"], stated
    assert max(c["index_score_rel_rms"] for c in stated) \
        <= tol["index_score_rel_rms"], stated
    for name in _LIMITS:
        assert max(c[name] for c in stated) <= tol[name], (name, stated)

    for how in (lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype),
                lambda a: (jnp.round(a * 8) / 8).astype(a.dtype)):
        coarse = jax.tree.map(lambda a: how(a) if a.ndim > 1 else a, params)
        bad = _check(twin, cfg, params, step_params=coarse)
        for name in _LIMITS:
            assert min(c[name] for c in bad) > tol[name], (name, bad)
        assert not sparse_moe_model.correct({"checks": bad}, tol)


def test_a_fault_planted_in_the_engine_fails_the_check(served, model):
    """What the engine streamed, held by the cell's own check at the
    twin's limits: sound as served; not with two rows' streams swapped;
    not from an engine whose `insert_row` grafts the ring half a turn
    off. The check's steps run at the engine's sizes: its slots, per-row
    depths, its own `insert_row` and `retire`."""
    twin, cfg, params, _ = model
    eng, prompts, outs = served
    tol = twin["tolerances"]
    service = types.SimpleNamespace(config=twin, engine=eng)
    picks = (3, 1)                        # prompts of 50 and 20 tokens
    samples = [{"tokens": prompts[i], "generated": outs[i]} for i in picks]
    check = lambda smp: sparse_moe_model.reference_check(service, smp, 64, 8)
    sound = check(samples)
    assert sparse_moe_model.correct({"checks": sound}, tol), sound
    assert max(c["token_margin_program"] for c in sound) == 0.0
    assert sparse_moe_model._placements(3, 2) == [(0, 0), (1, 1)]
    assert sparse_moe_model._placements(32, 2) == [
        (i % 2, 4 * i + 2) for i in range(8)]

    swapped = [{"tokens": samples[0]["tokens"],
                "generated": samples[1]["generated"]},
               {"tokens": samples[1]["tokens"],
                "generated": samples[0]["generated"]}]
    bad = check(swapped)
    assert min(c["token_margin_program"] for c in bad) > \
        tol["token_margin_program"], bad
    assert not sparse_moe_model.correct({"checks": bad}, tol)

    faulty = LLMEngine(cfg, tp=1, max_batch=3, prompt_buckets=(16, 32, 64),
                       prefill_chunk=8, params=params)
    graft = faulty._insert_row
    faulty._insert_row = lambda cache, row, *a: graft(cache, {
        **row, "window": jnp.roll(row["window"], cfg.ring_len // 2, axis=2)},
        *a)

    async def run():
        async def one(p):
            return [t async for t in faulty.generate(p, max_new_tokens=10)]
        return await asyncio.gather(*[one(prompts[i]) for i in picks])

    streams = asyncio.run(run())
    assert streams != [outs[i] for i in picks]
    bad = check([{"tokens": prompts[i], "generated": g}
                 for i, g in zip(picks, streams)])
    assert max(c["token_margin_program"] for c in bad) > \
        tol["token_margin_program"], bad
    assert not sparse_moe_model.correct({"checks": bad}, tol)


def test_published_configuration_gives_the_programs_sizes():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "dots3-note-prev.json")) as f:
        full = json.load(f)
    cfg = sparse_moe_model.program_config(full, "serve", max_seq_len=24576)
    assert cfg.attn("full_attention") == m.AttnSizes(
        128, 128, 64, 128, 1024, 512, 8e7)
    assert cfg.attn("sliding_attention") == m.AttnSizes(
        64, 192, 64, 128, 1024, 1024, 5e4)
    assert (cfg.attn("full_attention").row,
            cfg.attn("sliding_attention").row, cfg.ring_len) == (640, 1152, 640)
    assert (cfg.n_routed_experts, cfg.experts_first, cfg.experts_held,
            cfg.experts_per_tok) == (256, 0, 32, 8)
    assert (cfg.n_layers, cfg.first_k_dense, cfg.vocab_size) == (5, 1, 19008)
    assert 4.08e9 < cfg.num_params() < 4.09e9
    with pytest.raises(ValueError):
        m.from_published({**full, "scoring_func": "softmax"})
    with pytest.raises(ValueError):
        m.from_published({**full, "num_hidden_layers": 6})
