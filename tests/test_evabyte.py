"""The EvaByte model (ray_tpu/models/evabyte.py) on a CPU twin against
its plain reference (benchmarks/reference/evabyte_ref.py, which imports
nothing from ray_tpu): a window of 8 bytes folded four-fold into chunks
of 2, three layers, hidden 64, 4 heads, eight output heads of 32. The
twin's phi and mu are drawn ten times as wide as the program draws
them, so that a summary held wrongly shows in the logits. Prompt
lengths leave `start` off every multiple of 2 and of 8, windows end
inside chunks, and decode crosses three of them."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import evabyte_ref
from ray_tpu.models import evabyte, module_for
from ray_tpu.serve.llm import LLMEngine

W, C, L, H, HD = 8, 2, 3, 4, 16
HP = {"heads": H, "window": W, "chunk": C, "pred_heads": 8,
      "rope_theta": 100000.0, "norm_eps": 1e-5}


def _twin(window: int, chunk: int, max_len: int, seed: int):
    cfg = evabyte.EvaByteConfig(
        vocab_size=32, dim=H * HD, n_layers=L, n_heads=H, hidden_dim=96,
        max_seq_len=max_len, window_size=window, chunk_size=chunk,
        n_pred_heads=8, dtype=jnp.float32, param_dtype=jnp.float32)
    params = evabyte.init_params(cfg, jax.random.PRNGKey(seed))
    for name in ("phi", "mu"):
        params["layers"][name] = params["layers"][name] * 10.0
    return cfg, params


@pytest.fixture(scope="module")
def model():
    return _twin(W, C, 128, 0)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.sqrt(((a - b) ** 2).mean()) / np.sqrt((b ** 2).mean()))


_REF = jax.jit(lambda p, toks: evabyte_ref.logits_and_summaries(p, toks, HP))
# the program's step and forward, one program a shape (the config is
# hashable)
_STEP = jax.jit(evabyte.decode_step, static_argnames=("cfg", "all_heads"))
_FORWARD = jax.jit(evabyte.forward, static_argnames=("cfg", "all_heads"))
_SUMMARISE = jax.jit(evabyte._summarise, static_argnames=("c",))


def _reference(params, toks):
    """(logits [S, 8, 32], layer 0's (k~, v~)) over `toks`, padded to a
    multiple of 16 behind the causal mask (few shapes compile)."""
    n = len(toks)
    padded = np.zeros((-(-n // 16) * 16,), np.int32)
    padded[:n] = toks
    logits, sums = _REF(params, jnp.asarray(padded))
    return np.asarray(logits)[:n], [np.asarray(s) for s in sums]


def _slot_summaries(cache, row: int, chunks: int):
    """Layer 0's summaries of a row of the cache, as the reference's."""
    return (np.asarray(cache["k"][0, row, :, :, W:W + chunks]
                       ).transpose(2, 0, 1),
            np.asarray(cache["v"][0, row, :, W:W + chunks]
                       ).transpose(1, 0, 2))


def test_module_serves_the_config_and_sizes_follow_the_published_keys():
    cfg = evabyte.from_published({
        "attention_class": "eva", "vocab_size": 320, "hidden_size": 4096,
        "num_hidden_layers": 8, "num_attention_heads": 32,
        "num_key_value_heads": 32, "intermediate_size": 11008,
        "max_position_embeddings": 32768, "window_size": 2048,
        "chunk_size": 16, "num_pred_heads": 8, "rope_theta": 100000,
        "rms_norm_eps": 1e-5})
    assert module_for(cfg) is evabyte and not evabyte.TENSOR_PARALLEL
    assert (cfg.head_dim, cfg.chunks_per_window, cfg.summaries()) == (
        128, 128, 2048)
    shapes = jax.eval_shape(lambda k: evabyte.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    # a layer: 4 x 4096^2 + 3 x 4096 x 11008, phi, mu, two norms
    assert n == 8 * (202_375_168 + 2 * 32 * 128 + 2 * 4096) \
        + 320 * 4096 + 4096 + 4096 * 2560
    cache = jax.eval_shape(lambda: evabyte.init_cache(cfg, 16))
    assert cache["k"].shape == (8, 16, 32, 128, 4096)
    assert cache["v"].shape == (8, 16, 32, 4096, 128)
    # a prefill cache follows the bucket by its summaries alone
    assert jax.eval_shape(lambda: evabyte.init_cache(cfg, 1, 8192))[
        "k"].shape[-1] == 2048 + 512
    with pytest.raises(ValueError):
        evabyte.from_published({"num_key_value_heads": 8,
                                "num_attention_heads": 32})


@pytest.mark.parametrize("n", [5, 16, 23, 38])
def test_forward_agrees_with_the_reference(model, n):
    cfg, params = model
    toks = np.random.default_rng(n).integers(1, 32, size=n)
    want, _ = _reference(params, toks)
    got = _FORWARD(params, jnp.asarray(toks)[None], cfg=cfg,
                   all_heads=True)[0]
    assert got.shape == (n, 8, 32)
    assert _rel(got, want) < 1e-5
    head0 = _FORWARD(params, jnp.asarray(toks)[None], cfg=cfg)[0]
    assert np.array_equal(np.asarray(head0), np.asarray(got[:, 0]))


def test_summaries_move_the_logits_once_a_window_has_ended(model):
    """What the twin's wide phi and mu are for: without them the logits
    behind the first window change, inside it they do not."""
    cfg, params = model
    toks = np.random.default_rng(1).integers(1, 32, size=20)
    zeroed = {**params, "layers": {**params["layers"], **{
        name: jnp.zeros_like(params["layers"][name])
        for name in ("phi", "mu")}}}
    a = _FORWARD(params, jnp.asarray(toks)[None], cfg=cfg)[0]
    b = _FORWARD(zeroed, jnp.asarray(toks)[None], cfg=cfg)[0]
    assert np.allclose(a[:W], b[:W], atol=1e-6)
    assert _rel(a[W:], b[W:]) > 0.01


def _prefill(cfg, params, toks, bucket: int, chunk: int):
    """The engine's chunked prefill of one left-padded row: the
    all-padding chunks skipped. Returns (last logits, cache)."""
    start = bucket - len(toks)
    cache = evabyte.init_cache(cfg, 1, max_len=bucket)
    cache["start"] = jnp.asarray([start], jnp.int32)
    pos = (start // chunk) * chunk
    cache["length"] = jnp.int32(pos)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, start:] = toks
    while pos < bucket:
        logits, cache = _STEP(
            params, cache, jnp.asarray(padded[:, pos:pos + chunk]), cfg=cfg,
            all_heads=True)
        pos += chunk
    return logits, cache


# (prompt, bucket, chunk): start 11, 3, 5, 2, 37: off every multiple of 2
# and of 8 but for the one even start; a window's end inside a chunk in
# each; the last prefills the bucket in one call of four windows
@pytest.mark.parametrize("n,bucket,chunk", [
    (21, 32, 8), (13, 16, 4), (27, 32, 16), (30, 32, 32), (27, 64, 16)])
def test_chunked_prefill_then_cached_decode_agree_with_the_reference(
        model, n, bucket, chunk):
    cfg, params = model
    total = n + 26                     # decode crosses three windows' ends
    toks = np.random.default_rng(bucket + n).integers(1, 32, size=total)
    want, (k_sum, v_sum) = _reference(params, toks)
    assert (total - 1) // W - (n - 1) // W >= 3
    logits, small = _prefill(cfg, params, toks[:n], bucket, chunk)
    assert _rel(logits[0], want[n - 1]) < 1e-5
    # the engine's graft: the row at the origin of a slot of three, at a
    # depth of its own beside two rows that hold no request
    cache = evabyte.init_cache(cfg, 3)
    cache["length"] = jnp.full((3,), -1, jnp.int32)
    for name in ("k", "v"):
        cache[name] = jax.lax.dynamic_update_slice_in_dim(
            cache[name], small[name], 1, 1)
    cache["length"] = cache["length"].at[1].set(bucket)
    cache["start"] = cache["start"].at[1].set(bucket - n)
    for i in range(n, total):
        fed = np.zeros((3, 1), np.int32)
        fed[1, 0] = toks[i]
        logits, cache = _STEP(params, cache, jnp.asarray(fed), cfg=cfg,
                              all_heads=True)
        cache["length"] = jnp.where(jnp.arange(3) == 1, cache["length"], -1)
        assert _rel(logits[1], want[i]) < 1e-5, i
    # the slot's summaries and window, entry for entry
    chunks = total // C
    got_k, got_v = _slot_summaries(cache, 1, chunks)
    assert np.allclose(got_k, k_sum[:chunks], atol=1e-5)
    assert np.allclose(got_v, v_sum[:chunks], atol=1e-5)


def test_lockstep_decode_of_a_left_padded_batch_equals_forward(model):
    """A scalar cache["length"]: rows of different lengths padded to one
    bucket advance together, one position a call."""
    cfg, params = model
    rng = np.random.default_rng(3)
    lens, bucket, steps = (19, 10), 24, 9
    seqs = [rng.integers(1, 32, size=n + steps) for n in lens]
    cache = evabyte.init_cache(cfg, 2, max_len=bucket + steps)
    cache["start"] = jnp.asarray([bucket - n for n in lens], jnp.int32)
    padded = np.zeros((2, bucket), np.int32)
    for r, n in enumerate(lens):
        padded[r, bucket - n:] = seqs[r][:n]
    logits, cache = _STEP(params, cache, jnp.asarray(padded), cfg=cfg)
    want = [_FORWARD(params, jnp.asarray(seq)[None], cfg=cfg)[0]
            for seq in seqs]
    for i in range(steps):
        for r, n in enumerate(lens):
            assert _rel(logits[r], want[r][n + i - 1]) < 1e-5, (r, i)
        logits, cache = _STEP(params, cache, jnp.asarray(
            [[seqs[r][n + i]] for r, n in enumerate(lens)], jnp.int32),
            cfg=cfg)


# ------------------------------------------- the fold of a closed chunk
@pytest.fixture(scope="module")
def chunks_of_four():
    """A second twin, whose chunks are long enough to stand at different
    phases: a window of 16 in chunks of 4."""
    return _twin(16, 4, 64, 4)


FOLD_STEPS = 14         # 2 c + 2 and four more: every row ends a window
SET = 1e3               # what an unclosed chunk's slot is made to hold


# (prompt lengths of the three rows, None: the row holds no request; the
# rows that close a chunk on each of the first six steps)
@pytest.mark.parametrize("lens,closing", [
    ((5, 6, 7), [1, 1, 1, 0, 1, 1]),
    ((8, 12, 4), [0, 0, 0, 3, 0, 0]),
    ((6, None, 9), [0, 1, 1, 0, 0, 1]),
    ((None, None, 11), [1, 0, 0, 0, 1, 0]),
    ((7, 10, 3), [2, 1, 0, 0, 2, 1]),
    ((13, 14, 15), [1, 1, 1, 0, 1, 1]),
], ids=["one-row-a-step", "none-then-every-row", "a-row-holds-no-request",
        "one-row-alone", "a-chunk-the-prefill-began",
        "a-chunk-and-a-window-at-once"])
def test_decode_folds_a_chunk_once_on_the_step_that_closes_it(
        chunks_of_four, lens, closing):
    """Rows at different phases of their chunks, stepped together. Before
    every step each slot of a chunk not yet closed (and every slot of a
    row that holds no request) is set to a value no summary has. After
    it: (a) the chunk a row's written position closed holds `_summarise`
    of its c cached rows, exactly, in every layer, and keeps it; every
    other slot set is as it was set: a row in mid-chunk, or with no
    request, wrote nothing; (b) the logits are `forward`'s, which is
    also how (c) no row's range reaches a slot not yet closed: a set
    slot read would move them."""
    cfg, params = chunks_of_four
    Wf, c, cpw = cfg.window_size, cfg.chunk_size, cfg.chunks_per_window
    rng = np.random.default_rng(sum(n or 0 for n in lens))
    seqs = [None if n is None else rng.integers(1, 32, size=48)
            for n in lens]
    want = [None if q is None else np.asarray(
        _FORWARD(params, jnp.asarray(q)[None], cfg=cfg)[0]) for q in seqs]
    cache = evabyte.init_cache(cfg, 3)
    length, start = [-1] * 3, [0] * 3
    for r, n in enumerate(lens):
        if n is None:
            continue
        logits, small = _prefill(cfg, params, seqs[r][:n], 16, 8)
        assert _rel(logits[0, 0], want[r][n - 1]) < 1e-5
        for name in ("k", "v"):
            cache[name] = jax.lax.dynamic_update_slice_in_dim(
                cache[name], small[name], r, 1)
        length[r], start[r] = 16, 16 - n
    cache["start"] = jnp.asarray(start, jnp.int32)
    cache["length"] = jnp.asarray(length, jnp.int32)
    live = np.asarray([n is not None for n in lens])
    layers = [{name: params["layers"][name][li] for name in ("phi", "mu")}
              for li in range(L)]
    folded = {}                # (row, chunk) -> (k~, v~) [L, H, HD]
    seen = []
    for i in range(FOLD_STEPS):
        t = np.asarray([(n or 0) + i for n in lens])
        first_open = np.where(live, t // c, 0)
        for r in range(3):
            at = Wf + int(first_open[r])
            cache["k"] = cache["k"].at[:, r, :, :, at:].set(SET)
            cache["v"] = cache["v"].at[:, r, :, at:].set(SET)
        fed = np.asarray([[0 if q is None else q[n + i]]
                          for q, n in zip(seqs, lens)], np.int32)
        logits, cache = _STEP(params, cache, jnp.asarray(fed), cfg=cfg)
        cache["length"] = jnp.where(live, cache["length"], -1)
        k_now, v_now = np.asarray(cache["k"]), np.asarray(cache["v"])
        closes = live & (t % c == c - 1)
        seen.append(int(closes.sum()))
        for r in range(3):
            if live[r]:
                assert _rel(logits[r], want[r][lens[r] + i]) < 1e-5, (r, i)
            if closes[r]:
                j = int(t[r]) // c
                base = Wf - c - (j % cpw) * c
                sums = [_SUMMARISE(
                    layers[li],
                    jnp.asarray(k_now[li, r, :, :, base:base + c]
                                ).transpose(2, 0, 1)[None, None],
                    jnp.asarray(v_now[li, r, :, base:base + c]
                                ).transpose(1, 0, 2)[None, None],
                    jnp.ones((1, 1, c), bool), c) for li in range(L)]
                folded[r, j] = tuple(np.stack(
                    [np.asarray(s[n][0, 0]) for s in sums]) for n in (0, 1))
            # (a) closed here or on an earlier step: what the fold gave
            for (row, j), (k_sum, v_sum) in folded.items():
                if row == r:
                    assert np.array_equal(k_now[:, r, :, :, Wf + j], k_sum)
                    assert np.array_equal(v_now[:, r, :, Wf + j], v_sum)
            # and nothing written behind the chunks closed so far
            at = Wf + (int(t[r] + 1) // c if live[r] else 0)
            assert (k_now[:, r, :, :, at:] == SET).all(), (r, i)
            assert (v_now[:, r, :, at:] == SET).all(), (r, i)
    assert seen[:6] == closing
    assert len(folded) == sum(seen) >= 3
    assert evabyte.decode_counters(
        cfg, [(0, n + i) for n in lens if n is not None
              for i in range(FOLD_STEPS)], 3)["chunks_folded"] == sum(seen)


# -------------------------------------------------------------- the engine
PROMPTS = (5, 21, 13, 37, 30, 60, 9)


@pytest.fixture(scope="module")
def served(model):
    """Seven greedy requests over three slots and two chunked buckets
    (and one prefilled in one call), 20 bytes each: rows admitted at
    different phases of their windows, decoding across two or three
    windows' ends."""
    cfg, params = model
    eng = LLMEngine(cfg, tp=1, max_batch=3, prompt_buckets=(16, 32, 64),
                    prefill_chunk=8, params=params, prefix_cache_entries=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 32, size=n).tolist() for n in PROMPTS]

    async def one(p):
        return [t async for t in eng.generate(p, max_new_tokens=20)]

    async def run():
        return await asyncio.gather(*[one(p) for p in prompts])

    return eng, prompts, asyncio.run(run())


def test_engine_streams_the_greedy_references_tokens_of_head_zero(served,
                                                                  model):
    cfg, params = model
    eng, prompts, outs = served
    for p, o in zip(prompts, outs):
        want, _ = _reference(params, np.asarray(p + o))
        head0 = want[len(p) - 1:len(p + o) - 1, 0]
        assert head0.argmax(-1).tolist() == o, len(p)
        assert max(o) < cfg.vocab_size
    stats = eng.stats()
    assert stats["prefills"] == 7 and stats["prefill_chunks"] > 7
    assert stats["decode_overlapped"] > 0


def test_slot_rows_after_insert_row_equal_the_references(model):
    """Three rows admitted one after another into an engine's slots,
    each at another phase of its window: after `insert_row` the slot's
    window rows and its summaries are the reference's, entry for entry."""
    cfg, params = model
    eng = LLMEngine(cfg, tp=1, max_batch=3, prompt_buckets=(16, 32),
                    prefill_chunk=8, params=params)
    eng._ensure_decode_cache()
    rng = np.random.default_rng(5)
    for slot, (n, bucket) in enumerate(((21, 32), (13, 16), (30, 32))):
        toks = rng.integers(1, 32, size=n)
        _, small = _prefill(cfg, params, toks, bucket, 8)
        eng._decode_cache = eng._insert_row(
            eng._decode_cache, eng._row(small), jnp.int32(slot),
            jnp.int32(bucket), jnp.int32(bucket - n))
        with jax.default_matmul_precision("highest"):
            x = params["embed"][toks]
            lp = {k: v[0] for k, v in params["layers"].items()}
            h = evabyte_ref._rms(x, lp["attn_norm"], 1e-5)
            k = evabyte_ref._rope((h @ lp["wk"]).reshape(n, H, HD), 1e5)
            v = (h @ lp["wv"]).reshape(n, H, HD)
        _, (k_sum, v_sum) = _reference(params, toks)
        got_k, got_v = _slot_summaries(eng._decode_cache, slot, n // C)
        assert np.allclose(got_k, k_sum[:n // C], atol=1e-5)
        assert np.allclose(got_v, v_sum[:n // C], atol=1e-5)
        # the current window's positions, the latest at the lowest index
        for t in range((n - 1) // W * W, n):
            at = W - 1 - t % W
            assert np.allclose(eng._decode_cache["k"][0, slot, :, :, at],
                               k[t], atol=1e-5), (slot, t)
            assert np.allclose(eng._decode_cache["v"][0, slot, :, at],
                               v[t], atol=1e-5), (slot, t)
    assert np.asarray(eng._decode_cache["length"]).tolist() == [32, 16, 32]


def test_cache_bytes_by_kind_and_no_prefix_store(served, model):
    cfg, params = model
    stats = served[0].stats()
    # 3 layers x 3 slots x 4 heads x 16 numbers x float32, K and V: a
    # window of 8 rows, and 128 / 2 = 64 summaries
    per = 2 * 3 * 3 * 4 * 16 * 4
    assert stats["cache_bytes"] == {"kv": 0, "state": 0, "window": per * 8,
                                    "summary": per * 64}
    # nothing of the cache is as deep as the context: no prefix to cut
    assert evabyte.CACHE_LEN_AXIS == {}
    assert stats["prefix_cache_entries"] == 0
    assert stats["prefix_entries"] == 0 and stats["prefix_hits"] == 0
    deeper = LLMEngine(dataclasses.replace(cfg, max_seq_len=256), tp=1,
                       max_batch=3, prompt_buckets=(16,), params=params)
    assert deeper.stats()["cache_bytes"] == {
        "kv": 0, "state": 0, "window": per * 8, "summary": per * 128}


def test_engine_counts_what_the_steps_read_and_saw(served):
    """The counters of stats() against their formulas, over every
    position the seven requests made a query of."""
    eng, prompts, outs = served
    stats = eng.stats()
    want = dict.fromkeys(("pw", "ps", "dw", "ds", "folds", "chunks"), 0)
    for p, o in zip(prompts, outs):
        for t in range(len(p) + len(o) - 1):
            phase = "p" if t < len(p) else "d"
            want[phase + "w"] += L * (t % W + 1)
            want[phase + "s"] += L * (W // C) * (t // W)
            want["folds"] += t > 0 and t % W == 0
            want["chunks"] += phase == "d" and t % C == C - 1
    assert stats["prefill_window_keys_visible"] == want["pw"]
    assert stats["prefill_summaries_visible"] == want["ps"]
    assert stats["decode_window_positions_live"] == want["dw"]
    assert stats["decode_summaries_live"] == want["ds"]
    assert stats["windows_folded"] == want["folds"]
    # the decode steps that closed a chunk: one in C of the rows stepped
    assert stats["chunks_folded"] == want["chunks"]
    # nothing bounds a step's read on the CPU: both parts whole, for
    # every row the step has
    assert stats["decode_window_positions_read"] == \
        stats["batches"] * L * 3 * W
    assert stats["decode_summaries_read"] == stats["batches"] * L * 3 * 64
    # a call scores the window as found and its own keys for every query,
    # padding too, and every summary row of its bucket's cache
    calls = {16: 0, 32: 0, 64: 0}       # chunks of 8 by bucket
    for p in prompts:
        bucket = next(b for b in calls if len(p) <= b)
        calls[bucket] += (bucket - (bucket - len(p)) // 8 * 8) // 8
    assert stats["prefill_window_keys_visited"] == \
        L * 8 * (W + 8) * sum(calls.values())
    assert stats["prefill_summaries_visited"] == L * 8 * sum(
        n * (b // C) for b, n in calls.items())


@pytest.mark.parametrize("start,pos,chunk,depth", [
    (11, 8, 8, 32), (11, 16, 8, 32), (0, 0, 16, 16), (37, 32, 16, 64)])
def test_prefill_counters_from_where_the_row_and_the_chunk_lie(
        model, start, pos, chunk, depth):
    cfg, _ = model
    got = evabyte.prefill_counters(cfg, start, pos, chunk, depth)
    ts = [p - start for p in range(pos, pos + chunk) if p >= start]
    assert got == {
        "prefill_window_keys_visible": L * sum(t % W + 1 for t in ts),
        "prefill_window_keys_visited": L * chunk * (W + chunk),
        "prefill_summaries_visible": L * sum(4 * (t // W) for t in ts),
        "prefill_summaries_visited": L * chunk * (-(-depth // W) * 4),
        "windows_folded": sum(1 for t in ts if t and t % W == 0)}


def test_decode_counters_from_row_ranges_and_the_kernels_blocks(
        model, monkeypatch):
    cfg, _ = model
    assert evabyte.decode_counters(cfg, [], 3)["windows_folded"] == 0
    spans = [(11, 11 + 16), (3, 3 + 21), (0, 5)]        # t = 16, 21, 5
    got = evabyte.decode_counters(cfg, spans, 3)
    assert got == {
        "decode_window_positions_live": L * (1 + 6 + 6),
        "decode_summaries_live": L * (8 + 8 + 0),
        "decode_window_positions_read": L * 3 * W,
        "decode_summaries_read": L * 3 * 64,
        "windows_folded": 1, "chunks_folded": 2}   # t = 21 and 5 of C 2
    # where the kernel bounds the read: whole blocks that meet a row's
    # range, [W - 1 - t mod W, W + summaries - 1], of 2,048 + 2,048 rows
    big = evabyte.EvaByteConfig(n_layers=2)
    monkeypatch.setattr(evabyte, "_read_block", lambda cfg: 128)
    got = evabyte.decode_counters(big, [(0, 6143), (0, 6144), (100, 400)],
                                  16)
    assert got["decode_window_positions_live"] == 2 * (2048 + 1 + 301)
    assert got["decode_summaries_live"] == 2 * (256 + 384 + 0)
    assert got["decode_window_positions_read"] == 2 * (2048 + 128 + 384)
    assert got["decode_summaries_read"] == 2 * (256 + 384 + 0)
    assert got["windows_folded"] == 1
    assert got["chunks_folded"] == 1            # t = 6143 of chunks of 16


def test_decode_step_on_the_kernels_path_equals_the_plain_one(model,
                                                              monkeypatch):
    """The decode kernel the models share, interpreted, over a row's one
    range of window rows and summaries, against the masked XLA form."""
    from ray_tpu.ops import attention

    cfg = evabyte.EvaByteConfig(
        vocab_size=32, dim=256, n_layers=2, n_heads=2, hidden_dim=64,
        max_seq_len=2048, window_size=128, chunk_size=16, n_pred_heads=8,
        dtype=jnp.float32, param_dtype=jnp.float32)
    params = evabyte.init_params(cfg, jax.random.PRNGKey(1))
    toks = np.random.default_rng(2).integers(1, 32, size=300)
    _, small = _prefill(cfg, params, toks[:290], 512, 128)
    cache = evabyte.init_cache(cfg, 2)
    cache["length"] = jnp.asarray([-1, 512], jnp.int32)
    cache["start"] = jnp.asarray([0, 512 - 290], jnp.int32)
    for name in ("k", "v"):
        cache[name] = jax.lax.dynamic_update_slice_in_dim(
            cache[name], small[name], 1, 1)
    fed = jnp.asarray([[0], [int(toks[290])]], jnp.int32)
    plain, _ = _STEP(params, dict(cache), fed, cfg=cfg)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert evabyte._read_block(cfg) == 256     # of 128 + 128 rows
    kernel, _ = evabyte.decode_step(params, dict(cache), fed, cfg)
    assert _rel(kernel[1], plain[1]) < 1e-5
    want = _FORWARD(params, jnp.asarray(toks[:291])[None], cfg=cfg)[0, -1]
    assert _rel(kernel[1], want) < 1e-5


# the chunk kernel's twin: windows of 32 in chunks of 4, a leaf for 128
# positions (32 rows of the window, 32 summaries), heads of 128
KW, KC, KS = 32, 4, 32
# first own positions of the rows of one call, its queries, key tile
CHUNK_CASES = {
    "inside-a-window": ([8], 16, 8),
    "a-window-ends-inside": ([24], 16, 8),
    "a-window-ends-inside-a-call-of-its-length": ([16], 32, 8),
    "left-padded-first-chunk": ([-5], 16, 8),
    "deep-last-tile-partly-seen": ([3 * KW + 8], 16, 16),
    "two-rows-two-phases": ([8, 59], 16, 8),
}


@pytest.fixture
def chunk_kernel(monkeypatch):
    """(config, tiles of 8 queries x `tk` keys chosen as on a TPU) for
    the chunk kernel interpreted."""
    from ray_tpu.ops import attention
    from ray_tpu.ops.pallas import gqa_chunk_attention as gqa

    cfg = evabyte.EvaByteConfig(
        vocab_size=32, dim=256, n_layers=2, n_heads=2, hidden_dim=64,
        max_seq_len=4 * KW, window_size=KW, chunk_size=KC, n_pred_heads=8,
        dtype=jnp.float32, param_dtype=jnp.float32)

    def tiles_of(tk):
        monkeypatch.setattr(attention, "_on_tpu", lambda: True)
        monkeypatch.setattr(gqa, "_Q_TILES", (8,))
        monkeypatch.setattr(gqa, "_K_TILES", (tk,))
        return cfg

    return tiles_of


def _sees(cfg, t0: int, s: int, S: int, made: int):
    """[s, W + S] and [s, s + made] bool by the module's docstring, each
    pair on its own: whether the query at own position t0 + i attends to
    the leaf's column as the call found it, and to the call's own."""
    W, c, cpw = cfg.window_size, cfg.chunk_size, cfg.chunks_per_window
    first = max(t0, 0)
    found = np.zeros((s, W + S), bool)
    own = np.zeros((s, s + made), bool)
    for i in range(s):
        t = t0 + i
        if t < 0:
            continue
        for col in range(W):            # the position the column holds
            p = W * (first // W) + W - 1 - col
            found[i, col] = p < first and p // W == t // W
        for j in range(S):              # final before the call began
            found[i, W + j] = c * (j + 1) <= first and j // cpw < t // W
        for k in range(s):
            p = t0 + k
            own[i, k] = 0 <= p <= t and p // W == t // W
        for g in range(made):           # a chunk the call holds bytes of
            j = first // c + g
            own[i, s + g] = (c * (j + 1) > first and c * j < t0 + s
                             and j // cpw < t // W)
    return found, own


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_chunk_kernel_against_the_plain_form(chunk_kernel, case):
    """ops/pallas/gqa_chunk_attention.py interpreted, as `_chunk_attend`
    calls it twice a layer (the leaf as found, where it lies in the
    stack; the call's own rows and the summaries it makes), against the
    plain form over a leaf of noise: a column one form sees and the other
    does not shows. The layer written is the plain form's."""
    t0, s, tk = CHUNK_CASES[case]
    cfg = chunk_kernel(tk)
    b, H, hd, n = len(t0), cfg.n_heads, cfg.head_dim, KW + KS
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 7)
    q, kk, vv = (jax.random.normal(k, (b, s, H, hd)) for k in ks[:3])
    kc = jax.random.normal(ks[3], (2, b, H, hd, n))
    vc = jax.random.normal(ks[4], (2, b, H, n, hd))
    layer = {"phi": jax.random.normal(ks[5], (H, hd)) * 0.2,
             "mu": jax.random.normal(ks[6], (H, hd)) * 0.2}
    t = jnp.asarray(t0)[:, None] + jnp.arange(s)[None, :]
    plan = evabyte._chunk_plan(cfg, t, n)
    assert plan[0][:2] == ((8, tk), (8, tk))
    want = evabyte._chunk_attend(cfg, layer, 1, q, kk, vv, kc, vc, t)
    got = evabyte._chunk_attend(cfg, layer, 1, q, kk, vv, kc, vc, t, plan)
    for a, b_ in zip(got, want):
        assert float(jnp.abs(a - b_).max()) < 2e-5
    assert float(jnp.abs(want[0]).max()) > 0.1
    # a left-padding query gives zeros; the layer not asked for is left
    pad = np.asarray(t) < 0
    assert float(jnp.abs(got[0])[pad].sum()) == 0.0
    assert np.array_equal(got[1][0], kc[0])


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_chunk_kernels_tables_and_what_the_counters_call_visited(
        chunk_kernel, case):
    """A tile is live iff it holds a pair of query and key that counts,
    and `prefill_counters` counts the live tiles' pairs by what their
    columns hold, by the rule the device's tables follow."""
    t0, s, tk = CHUNK_CASES[case]
    cfg = chunk_kernel(tk)
    t = jnp.asarray(t0)[:, None] + jnp.arange(s)[None, :]
    (_, _, made), _, (found_live, found_named), _, (own_live, own_named) = \
        evabyte._chunk_plan(cfg, t, KW + KS)
    for r, first in enumerate(t0):
        found, own = _sees(cfg, first, s, KS, made)
        visited = [0, 0]
        for sees, live, named in ((found, found_live, found_named),
                                  (own, own_live, own_named)):
            by_tile = sees.reshape(s // 8, 8, -1, tk).any((1, 3))
            assert np.array_equal(np.asarray(live[r]) > 0, by_tile)
            # a step that computes nothing names a tile that is live
            if by_tile.any():
                assert np.take_along_axis(
                    by_tile, np.asarray(named[r]), 1)[by_tile.any(1)].all()
            edge = KW if sees is found else s   # rows | summaries
            pairs = 8 * np.repeat(by_tile.sum(0), tk)
            visited[0] += int(pairs[:edge].sum())
            visited[1] += int(pairs[edge:].sum())
        got = evabyte.prefill_counters(cfg, 7, 7 + first, s, 4 * KW)
        L = cfg.n_layers
        assert got["prefill_window_keys_visited"] == L * visited[0]
        assert got["prefill_summaries_visited"] == L * visited[1]
        assert got["prefill_window_keys_visible"] == L * int(
            found[:, :KW].sum() + own[:, :s].sum())
        assert got["prefill_summaries_visible"] == L * int(
            found[:, KW:].sum() + own[:, s:].sum())
        assert visited[0] * L >= got["prefill_window_keys_visible"]
        assert visited[1] * L >= got["prefill_summaries_visible"]


@pytest.mark.parametrize("model_name", ["evabyte", "llama"])
def test_one_prefill_cache_is_alive_among_the_prompts_admitted(model,
                                                              model_name):
    """N long prompts admitted at once hold one prefill cache between
    them: the one whose chunks are at work (serve/llm.py, PR 43)."""
    if model_name == "evabyte":
        cfg, params = model
        eng = LLMEngine(cfg, tp=1, max_batch=4, prompt_buckets=(32, 64),
                        prefill_chunk=8, params=params)
    else:
        eng = LLMEngine("debug", tp=1, max_batch=4, max_seq_len=128,
                        prompt_buckets=(32, 64), prefill_chunk=8,
                        prefix_cache_entries=0)
    alive = []
    advance = eng._advance_prefill

    def watched(epoch):
        advance(epoch)
        alive.append(sum(pf.small is not None
                         for pf in eng._pending_prefills))

    eng._advance_prefill = watched
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 32, size=n).tolist() for n in (60, 50, 40, 30)]

    async def one(p):
        return [t async for t in eng.generate(p, max_new_tokens=3)]

    async def run():
        return await asyncio.gather(*[one(p) for p in prompts])

    outs = asyncio.run(run())
    assert [len(o) for o in outs] == [3] * 4
    assert eng.stats()["prefills"] == 4 and len(alive) > 12
    assert max(alive) == 1 and not eng._pending_prefills


def test_lower_precision_and_a_shifted_chunk_grid_fail_the_twins_limits(
        model):
    """What the cell's limits are for, on the twin in bfloat16: the
    stated precision passes; matrices rounded to fp8's mantissa fail the
    logits; summaries cut one byte off the grid fail the summaries."""
    cfg, params = model
    bf = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    toks = np.random.default_rng(7).integers(1, 32, size=40)
    want, (k_sum, _) = _reference(params, toks)

    def reads(p):
        logits, cache = _prefill(bf, p, toks, 64, 16)
        got_k, _ = _slot_summaries(cache, 0, 20)
        return _rel(logits[0], want[39]), _rel(got_k, k_sum[:20])

    stated = reads(params)
    assert stated[0] < 0.05 and stated[1] < 0.02, stated
    fp8 = jax.tree.map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
        if w.ndim == 3 and w.shape[-1] >= 64 else w, params)
    assert reads(fp8)[0] > 0.05
    # the grid one byte off: the reference's chunks begin at byte 1
    _, (off, _) = _reference(params, toks[1:])
    assert _rel(_slot_summaries(_prefill(bf, params, toks, 64, 16)[1], 0,
                                19)[0], off[:19]) > 0.02
