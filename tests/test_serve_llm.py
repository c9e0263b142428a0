"""TP-sharded LLM serving (on the CPU mesh):
engine batching/parity + Serve deployment streaming (ref analog:
serve/_private/replica.py:750 + response streaming; the engine itself is
TPU-native, no reference equivalent)."""

import asyncio
import time

import jax
import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu import serve
from ray_tpu.models import llama
from ray_tpu.serve import llm as llm_mod
from ray_tpu.serve.llm import LLMEngine


def _collect(engine, tokens, **kw):
    async def run():
        return [t async for t in engine.generate(tokens, **kw)]
    return asyncio.run(run())


def test_engine_greedy_matches_unbatched_decode():
    """Batched left-padded generation must equal a plain single-sequence
    greedy decode with the same params."""
    eng = LLMEngine("debug", tp=2, max_batch=4)
    prompt = [5, 9, 11, 42, 7]
    got = _collect(eng, prompt, max_new_tokens=8)
    assert got == _greedy_reference(eng, prompt, 8)


def test_engine_batches_concurrent_requests():
    eng = LLMEngine("debug", tp=2, max_batch=4)

    async def run():
        outs = await asyncio.gather(*[
            _agen_list(eng.generate([3 + i, 8, 1], max_new_tokens=5))
            for i in range(3)])
        return outs

    outs = asyncio.run(run())
    assert all(len(o) == 5 for o in outs)
    # continuous batching: all three requests decode in SHARED steps.
    # Each needs 4 decode steps after its prefill token; run serially
    # that would be 12 — shared slots need far fewer (admission skew can
    # cost a couple of extra steps).
    assert eng.prefills == 3
    assert eng.batches <= 8
    # different prompts may produce different streams; each is deterministic
    again = _collect(eng, [3, 8, 1], max_new_tokens=5)
    assert again == outs[0]


async def _agen_list(agen):
    return [t async for t in agen]


def test_engine_respects_per_request_lengths_and_eos():
    eng = LLMEngine("debug", tp=2, max_batch=4)

    async def run():
        a, b = await asyncio.gather(
            _agen_list(eng.generate([1, 2, 3], max_new_tokens=2)),
            _agen_list(eng.generate([9, 9], max_new_tokens=7)))
        return a, b

    a, b = asyncio.run(run())
    assert len(a) == 2
    assert len(b) == 7


def test_late_request_joins_mid_decode():
    """The continuous-batching contract: a request arriving while another
    is mid-generation starts decoding within ~1 step — it never waits
    for the in-flight request to drain its token budget."""
    eng = LLMEngine("debug", tp=2, max_batch=4)

    async def run():
        first = asyncio.ensure_future(
            _agen_list(eng.generate([1, 2, 3], max_new_tokens=60)))
        # let the first request get well into decode
        while eng.batches < 5:
            await asyncio.sleep(0.01)
        steps_before = eng.batches
        late = await _agen_list(eng.generate([7, 7], max_new_tokens=3))
        steps_for_late = eng.batches - steps_before
        first_done = first.done()
        out_first = await first
        return out_first, late, steps_for_late, first_done

    out_first, late, steps_for_late, first_done = asyncio.run(run())
    assert len(out_first) == 60
    assert len(late) == 3
    # 3 tokens = 1 prefill token + 2 decode steps; a drain-first engine
    # would burn ~55 steps before the late request emitted anything
    assert steps_for_late <= 6
    # and the first request was still decoding when the late one finished
    assert not first_done


def test_llm_serve_app_streams_tokens(local_cluster):
    try:
        app = __import__("ray_tpu.serve.llm", fromlist=["llm_app"]).llm_app(
            "debug", tp=2, max_batch=4)
        h = serve.run(app, name="llm")
        items = list(h.options(stream=True).remote(
            {"tokens": [4, 8, 15], "max_new_tokens": 6}))
        assert len(items) == 6
        assert all(isinstance(d["token"], int) for d in items)
    finally:
        serve.shutdown()


@pytest.mark.slow  # 3 engine builds (~35s of traces); tier-1 keeps the
# base-decode parity test, the LoRA-specific path gates in the slow lane
def test_engine_applies_lora_adapter():
    """An engine whose params carry a "lora" subtree shards and applies
    the adapter for real: a zero-init adapter (B=0) matches the base
    decode bit-for-bit, a nonzero adapter changes the stream."""
    from ray_tpu.models import lora as lora_mod

    base_eng = LLMEngine("debug", tp=2, max_batch=2, seed=0)
    base = jax.device_get(base_eng.params)
    cfg = base_eng.cfg
    adapter = lora_mod.init_lora_params(
        cfg, lora_mod.LoraConfig(rank=4, alpha=cfg.lora_alpha),
        jax.random.PRNGKey(7))
    eng = LLMEngine("debug", tp=2, max_batch=2,
                    params={**base, "lora": adapter}, seed=0)
    prompt = [5, 9, 11, 42, 7]
    want = _collect(base_eng, prompt, max_new_tokens=8)
    got = _collect(eng, prompt, max_new_tokens=8)
    assert got == want  # B=0: adapter is an exact no-op
    # a trained (nonzero-B) adapter must change the decode
    adapter2 = jax.tree.map(
        lambda a: a + 0.5 if a.ndim and a.shape[-1] != 4 else a, adapter)
    eng2 = LLMEngine("debug", tp=2, max_batch=2,
                     params={**base, "lora": adapter2}, seed=0)
    assert _collect(eng2, prompt, max_new_tokens=8) != want


@pytest.mark.slow  # cluster + three per-adapter engine builds
def test_multiplexed_lora_service_e2e(local_cluster):
    """lora_llm_app: adapters route by multiplexed model id, stream
    adapter-tagged tokens, and the per-replica LRU bounds residents
    (third adapter evicts the LRU one)."""
    try:
        from ray_tpu.serve.llm import lora_llm_app

        app = lora_llm_app("debug", tp=2, max_batch=2,
                           max_adapters_per_replica=2)
        h = serve.run(app, name="lora")

        def gen(adapter):
            return list(h.options(
                multiplexed_model_id=adapter, stream=True).remote(
                {"tokens": [4, 8, 15], "max_new_tokens": 4}))

        a = gen("ad-a")
        assert len(a) == 4 and all(d["adapter"] == "ad-a" for d in a)
        b = gen("ad-b")
        assert len(b) == 4 and all(d["adapter"] == "ad-b" for d in b)
        # different adapters may produce different streams; repeat
        # traffic for one adapter is deterministic (cached engine)
        assert gen("ad-a") == a
        # residency reported through replica stats; 2-adapter LRU means
        # a third adapter evicts one
        h._refresh(force=True)
        replica = h._replicas[0]
        models = rt.get(replica.get_stats.remote(), timeout=30)["models"]
        assert sorted(models) == ["ad-a", "ad-b"]
        gen("ad-c")
        models = rt.get(replica.get_stats.remote(), timeout=30)["models"]
        assert len(models) == 2 and "ad-c" in models
    finally:
        serve.shutdown()


def test_chunked_prefill_interleaves_with_decode():
    """A long-prompt admission must not stall active decode streams for
    the whole prompt: prefill advances one CHUNK per engine round, with
    decode steps in between (vLLM-style chunked prefill)."""
    eng = LLMEngine("debug", tp=2, max_batch=4, max_seq_len=1024,
                    prompt_buckets=(32, 512), prefill_chunk=64)

    async def run():
        # record the engine's prefill progress at each first-stream token
        # so we can assert tokens kept flowing DURING the chunked prefill
        chunks_at_token = []

        async def consume_first():
            out = []
            async for t in eng.generate([1, 2, 3], max_new_tokens=40):
                out.append(t)
                chunks_at_token.append(eng.prefill_chunks)
            return out

        first = asyncio.ensure_future(consume_first())
        while eng.batches < 3:
            await asyncio.sleep(0.01)
        # inject a LONG prompt (bucket 512 -> 8 chunks of 64)
        long_prompt = list(range(1, 301))
        late = await _agen_list(eng.generate(long_prompt,
                                             max_new_tokens=3))
        out_first = await first
        return out_first, late, chunks_at_token

    out_first, late, chunks_at_token = asyncio.run(run())
    assert len(out_first) == 40
    assert len(late) == 3
    # 300 real tokens in a 512 bucket, chunk 64: pad chunks are skipped
    # (192 of 212 pad tokens), leaving ceil(320/64) = 5 chunk rounds
    assert eng.prefill_chunks == 5
    # the actual interleaving claim: first-stream tokens were emitted
    # while the long prefill was mid-flight (a drain-prefill-first engine
    # would show every token at chunks 0 or 5)
    assert any(0 < c < 5 for c in chunks_at_token), chunks_at_token
    # parity: the chunked path produces the same tokens as monolithic
    eng2 = LLMEngine("debug", tp=2, max_batch=4, max_seq_len=1024,
                     prompt_buckets=(32, 512), prefill_chunk=0, seed=0)
    eng3 = LLMEngine("debug", tp=2, max_batch=4, max_seq_len=1024,
                     prompt_buckets=(32, 512), prefill_chunk=64, seed=0)
    prompt = [5, 9, 11, 42, 7] * 30  # 150 tokens -> bucket 512
    mono = _collect(eng2, prompt, max_new_tokens=6)
    chunked = _collect(eng3, prompt, max_new_tokens=6)
    assert mono == chunked


def test_chunked_prefill_with_a_lora_adapter_matches_unbatched_decode():
    """The prefill chunk's program with adapters in its layer loop, under
    the 2-way tensor mesh: a prompt prefilled in chunks of 64 streams
    what a plain unbatched decode with the same base and adapter gives,
    and the adapter is no no-op (PR 45 holds q and k as projected, the
    low-rank path summed in; the decode step alone was covered)."""
    cfg = llama.config_for("debug", max_seq_len=1024)
    params = _lora_params(cfg)
    base = {k: v for k, v in params.items() if k != "lora"}
    eng = LLMEngine("debug", tp=2, max_batch=2, max_seq_len=1024,
                    prompt_buckets=(32, 512), prefill_chunk=64,
                    params=params, seed=0)
    prompt = [5, 9, 11, 42, 7] * 30  # 150 tokens -> bucket 512
    got = _collect(eng, prompt, max_new_tokens=6)
    assert eng.prefill_chunks >= 3
    assert got == _greedy_reference(eng, prompt, 6)
    eng.params = base   # the reference reads the engine's: now without
    assert got != _greedy_reference(eng, prompt, 6)


# --------------------------------------------------------------------------
# The mixed program: a prefill chunk carries the decode rows (PR 57)
# --------------------------------------------------------------------------
def _lora_params(cfg):
    """Base parameters with a trained adapter (B random, not the zeros
    it starts from) under "lora"."""
    from ray_tpu.models import lora as lora_mod

    adapter = lora_mod.init_lora_params(
        cfg, lora_mod.LoraConfig(rank=4, alpha=cfg.lora_alpha),
        jax.random.PRNGKey(7))
    adapter = {"layers": {
        k: (0.3 * jax.random.normal(jax.random.PRNGKey(i), v.shape, v.dtype)
            if k.endswith("_b") else v)
        for i, (k, v) in enumerate(sorted(adapter["layers"].items()))}}
    return {**llama.init_params(cfg, jax.random.PRNGKey(0)), "lora": adapter}


@pytest.mark.parametrize("lora", [False, True], ids=["base", "lora"])
def test_mixed_step_equals_a_chunk_step_and_a_decode_step(lora):
    """`llama.mixed_step` from one state against `decode_step` called
    twice from it, the chunk on the request's cache and one token a row
    on the slots': both logits to float32 round-off with the same
    argmax, both caches leaf by leaf. The rows stand at their own
    depths, one holds no request (depth < 0), and the chunk is a
    left-padded prompt's first."""
    import jax.numpy as jnp

    cfg = llama.config_for("debug", max_seq_len=128)
    params = _lora_params(cfg) if lora else llama.init_params(
        cfg, jax.random.PRNGKey(0))
    b, s, bucket = 4, 16, 64
    rng = np.random.default_rng(0)
    fill = lambda key, a: jax.random.normal(
        jax.random.PRNGKey(key), a.shape, jnp.float32).astype(a.dtype)
    cache = llama.init_kv_cache(cfg, b, max_len=128)
    cache.update(k=fill(1, cache["k"]), v=fill(2, cache["v"]),
                 length=jnp.asarray([40, -1, 17, 90], jnp.int32),
                 start=jnp.asarray([3, 0, 0, 30], jnp.int32))
    # 41 real tokens in a bucket of 64: the first chunk that holds one
    # starts at 16, its first seven positions padding
    small = llama.init_kv_cache(cfg, 1, max_len=bucket)
    small.update(start=jnp.asarray([23], jnp.int32), length=jnp.int32(16))
    chunk = jnp.asarray(rng.integers(1, 255, (1, s)), jnp.int32)
    tokens = jnp.asarray(rng.integers(1, 255, (b, 1)), jnp.int32)
    want_chunk, want_small = _reference_step(params, small, chunk, cfg)
    want_rows, want_cache = _reference_step(params, cache, tokens, cfg)
    got_chunk, got_small, got_rows, got_cache = jax.jit(
        llama.mixed_step, static_argnums=5)(
            params, small, chunk, cache, tokens, cfg)
    if lora:  # the adapter is no no-op
        bare, _ = _reference_step({k: v for k, v in params.items()
                                   if k != "lora"}, small, chunk, cfg)
        assert np.abs(np.asarray(bare - want_chunk)).max() > 1e-2
    for got, want in ((got_chunk, want_chunk), (got_rows, want_rows)):
        assert got.dtype == jnp.float32 and got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert (np.argmax(got, -1) == np.argmax(want, -1)).all()
    for got, want in ((got_small, want_small), (got_cache, want_cache)):
        assert sorted(got) == sorted(want)
        for leaf in want:
            np.testing.assert_array_equal(
                np.asarray(got[leaf], np.float32),
                np.asarray(want[leaf], np.float32), err_msg=leaf)


def _long_prompts_beside_decoding_rows(eng):
    """Two rows decode; two long prompts (5 and 4 chunks of 64 in the
    bucket of 512) and a short one arrive while they do. Returns the
    five streams in the order sent."""
    reqs = [([1, 2, 3], 40), ([7, 7], 30),
            ([(7 * i) % 251 + 1 for i in range(300)], 6),
            ([(5 * i) % 241 + 1 for i in range(200)], 5),
            ([9, 4, 9], 8)]

    async def run():
        first = [asyncio.ensure_future(_agen_list(
            eng.generate(p, max_new_tokens=m))) for p, m in reqs[:2]]
        await _when(lambda: eng.batches >= 3)
        late = await asyncio.gather(*[
            _agen_list(eng.generate(p, max_new_tokens=m))
            for p, m in reqs[2:]])
        assert not all(f.done() for f in first)
        return [await f for f in first] + late
    return reqs, asyncio.run(run())


def _mixed_engine():
    """(engine, calls): every call of the engine's two jitted programs
    is noted in `calls` as (program, the shape of its prompt tokens or
    of its chunk)."""
    eng = LLMEngine("debug", tp=1, max_batch=4, max_seq_len=1024,
                    prompt_buckets=(32, 512), prefill_chunk=64,
                    prefix_cache_entries=0)
    calls = []
    for program in ("_step_jit", "_mixed_jit"):
        jitted = getattr(eng, program)
        if jitted is not None:
            setattr(eng, program, lambda *a, _p=program, _j=jitted: (
                calls.append((_p, a[2].shape)), _j(*a))[1])
    return eng, calls


def test_mixed_rounds_stream_what_two_programs_stream(monkeypatch):
    """Long prompts arriving while rows decode: the engine's round with
    a chunk due is the mixed program (every chunk call, and the rows
    that decode beside it), and every greedy stream is the one the
    two-program path gives (the same engine over the module with its
    `mixed_step` taken away), with the chunks and decode steps counted
    as that path counts them."""
    eng, calls = _mixed_engine()
    reqs, got = _long_prompts_beside_decoding_rows(eng)
    st = eng.stats()
    assert st["mixed_steps"] == st["prefill_chunks"] == 5 + 4
    # beside a chunk: the two rows, the short prompt's, a finished long's
    assert 2 * 5 <= st["mixed_rows"] <= 3 * 9
    # a chunk goes through the mixed program, never the chunk's own
    assert calls.count(("_mixed_jit", (1, 64))) == 9
    assert ("_step_jit", (1, 64)) not in calls
    monkeypatch.setattr(llama, "mixed_step", None)
    two, calls = _mixed_engine()
    assert two._mixed_jit is None
    _, want = _long_prompts_beside_decoding_rows(two)
    assert got == want and calls.count(("_step_jit", (1, 64))) == 9
    for (prompt, max_new), stream in zip(reqs, got):
        assert stream == _greedy_reference(eng, prompt, max_new), prompt
    st2 = two.stats()
    assert (st2["mixed_steps"], st2["mixed_rows"]) == (0, 0)
    assert st["prefill_chunks"] == st2["prefill_chunks"]
    assert st["prefills"] == st2["prefills"] == 5
    # every stream's tokens but its first come from a decode step: the
    # steps are counted, whichever program made them
    assert st["generated_tokens"] == st2["generated_tokens"]
    assert abs(st["batches"] - st2["batches"]) <= 2
    assert eng._inflight is None and st["active_slots"] == 0


def test_a_chunk_alone_goes_through_the_mixed_program():
    """A chunk of a chunked prompt with no row decoding beside it: the
    mixed program still, over retired rows; no decode step is counted
    and no record kept of one."""
    eng, _ = _mixed_engine()
    prompt = [(7 * i) % 251 + 1 for i in range(300)]
    got = _collect(eng, prompt, max_new_tokens=3)
    st = eng.stats()
    assert st["mixed_steps"] == st["prefill_chunks"] == 5
    assert st["mixed_rows"] == 0 and st["batches"] == 2
    assert got == _greedy_reference(eng, prompt, 3)


def test_a_failed_mixed_step_fails_the_prompt_and_the_rows_once():
    """The mixed program takes both caches donated: when it raises, the
    pending prompt and the live rows each hear the engine's error once,
    nothing is left pending or in flight, and the next request is
    served."""
    eng, _ = _mixed_engine()
    mixed_jit = eng._mixed_jit

    def boom(*a):
        raise RuntimeError("boom at the mixed dispatch")

    async def consume(prompt):
        got = []
        try:
            async for t in eng.generate(prompt, max_new_tokens=200):
                got.append(t)
        except RuntimeError as e:
            return got, e
        return got, None

    async def run():
        rows = [asyncio.ensure_future(consume(p))
                for p in ([1, 2, 3], [7, 7])]
        await _when(lambda: eng.batches >= 4
                    and sum(s is not None for s in eng._slots) == 2)
        with eng._mutex:
            live = [s.req for s in eng._slots if s is not None]
            eng._mixed_jit = boom
        long = asyncio.ensure_future(consume(
            [(7 * i) % 251 + 1 for i in range(300)]))
        results = await asyncio.wait_for(asyncio.gather(*rows, long), 60)
        with eng._mutex:
            eng._mixed_jit = mixed_jit
            assert eng._inflight is None and not eng._pending_prefills
            assert eng._slots == [None] * 4
        assert all(r.out.empty() for r in live)
        after = await _agen_list(eng.generate([5, 9, 11], max_new_tokens=6))
        return results, after

    results, after = asyncio.run(run())
    for got, err in results:
        assert "decode cache lost to a failed engine step" in str(err)
    assert all(len(got) >= 4 for got, _ in results[:2])
    assert results[2][0] == []
    assert after == _greedy_reference(eng, [5, 9, 11], 6)
    assert eng.stats()["mixed_steps"] == 0


# --------------------------------------------------------------------------
# The decode pipeline (one step in flight): what the streams see, and faults
# --------------------------------------------------------------------------
_reference_step = jax.jit(llama.decode_step, static_argnums=3)


def _greedy_reference(eng, prompt, max_new_tokens):
    """What the engine must stream for one greedy request, from a plain
    unbatched, unpadded decode with the engine's params, ended as the
    engine ends a request: eos is not emitted, `max_new_tokens` counts
    the prefill's token, and a row's depth counts from its bucket."""
    cfg, eos = eng.cfg, eng.eos_token_id
    params = jax.device_get(eng.params)
    cache = llama.init_kv_cache(cfg, 1, max_len=cfg.max_seq_len)
    logits, cache = _reference_step(
        params, cache, np.asarray([prompt], np.int32), cfg)
    length = next(b for b in eng.prompt_buckets if len(prompt) <= b)
    out = []
    while True:
        t = int(np.argmax(np.asarray(logits)[0]))
        if t == eos:
            return out
        out.append(t)
        if len(out) >= max_new_tokens or (
                len(out) > 1 and length >= cfg.max_seq_len - 1):
            return out
        logits, cache = _reference_step(
            params, cache, np.asarray([[t]], np.int32), cfg)
        length += 1


async def _when(cond):
    for _ in range(3000):
        if cond():
            return
        await asyncio.sleep(0.01)
    raise AssertionError("the engine never got there")


def _case_late_arrival():
    eng = LLMEngine("debug", tp=1, max_batch=4)
    reqs = [([1, 2, 3], 40), ([7, 7], 5)]

    async def run():
        first = asyncio.ensure_future(_agen_list(
            eng.generate(reqs[0][0], max_new_tokens=reqs[0][1])))
        await _when(lambda: eng.batches >= 5)
        late = await _agen_list(
            eng.generate(reqs[1][0], max_new_tokens=reqs[1][1]))
        assert not first.done()
        return [await first, late]
    return eng, reqs, run


def _case_max_new(n):
    def case():
        eng = LLMEngine("debug", tp=1, max_batch=2)
        reqs = [([4, 8, 15, 16], 9), ([23, 42], n)]

        async def run():
            return await asyncio.gather(*[
                _agen_list(eng.generate(p, max_new_tokens=m))
                for p, m in reqs])
        return eng, reqs, run
    return case


def _case_ends_at_max_seq_len():
    # bucket 32 of 40 positions: the row ends at depth 39, after the
    # prefill's token and seven decode steps, whatever was asked for
    eng = LLMEngine("debug", tp=1, max_batch=2, max_seq_len=40,
                    prompt_buckets=(32,))
    reqs = [([5, 9, 11, 42, 7], 30), ([3, 1, 4], 4)]

    async def run():
        outs = await asyncio.gather(*[
            _agen_list(eng.generate(p, max_new_tokens=m))
            for p, m in reqs])
        assert len(outs[0]) == 8
        return outs
    return eng, reqs, run


def _case_chunked_prefill():
    eng = LLMEngine("debug", tp=1, max_batch=2, max_seq_len=512,
                    prompt_buckets=(32, 256), prefill_chunk=64,
                    prefix_cache_entries=0)
    reqs = [([1, 2, 3], 30), ([(7 * i) % 251 + 1 for i in range(150)], 4)]

    async def run():
        first = asyncio.ensure_future(_agen_list(
            eng.generate(reqs[0][0], max_new_tokens=reqs[0][1])))
        await _when(lambda: eng.batches >= 3)
        before = eng.batches
        late = await _agen_list(
            eng.generate(reqs[1][0], max_new_tokens=reqs[1][1]))
        # 106 pad slots: one chunk skipped, three run, decode steps between
        assert eng.prefill_chunks == 3 and eng.batches - before >= 3
        return [await first, late]
    return eng, reqs, run


def _case_eos(max_batch):
    """A stream ends by eos while its look-ahead step is in flight, and a
    waiting request takes its slot at once. With another stream live the
    look-ahead step is read (and the ended row's token must reach
    nobody); alone, the step nobody waits for is dropped unread."""
    def case():
        eng = LLMEngine("debug", tp=1, max_batch=max_batch)
        # a prompt whose greedy stream has a token, a few steps in, that
        # it has not produced before: that token becomes eos
        for p in ([5, 9, 11, 42, 7], [1, 2, 3], [9, 9], [200, 3, 77]):
            free = _greedy_reference(eng, p, 12)
            k = next((k for k in range(2, 8) if free[k] not in free[:k]),
                     None)
            if k is not None:
                break
        assert k is not None, "no usable eos among the debug streams"
        eng.eos_token_id = free[k]
        reqs = [(p, 12)] + [([17, 4], 20)] * (max_batch - 1) + [([8, 8, 1], 6)]

        async def run():
            # queue order: the last request waits for the first free slot
            outs = await asyncio.gather(*[
                _agen_list(eng.generate(p_, max_new_tokens=m))
                for p_, m in reqs])
            assert outs[0] == free[:k]
            assert eng.decode_rows_discarded >= 1
            return outs
        return eng, reqs, run
    return case


@pytest.mark.parametrize("case", [
    pytest.param(_case_late_arrival, id="late_arrival"),
    pytest.param(_case_eos(2), id="eos_refill_beside_a_live_stream"),
    pytest.param(_case_eos(1), id="eos_refill_alone"),
    pytest.param(_case_max_new(1), id="max_new_1"),
    pytest.param(_case_max_new(2), id="max_new_2"),
    pytest.param(_case_max_new(3), id="max_new_3"),
    pytest.param(_case_ends_at_max_seq_len, id="ends_at_max_seq_len"),
    pytest.param(_case_chunked_prefill, id="chunked_prefill"),
])
def test_greedy_streams_equal_unbatched_decode(case):
    """With one decode step in flight every greedy stream is still, token
    for token, the unbatched decode of its own prompt: no token lost,
    doubled, reordered or handed to another request."""
    eng, reqs, run = case()
    outs = asyncio.run(run())
    for (prompt, max_new), got in zip(reqs, outs):
        assert got == _greedy_reference(eng, prompt, max_new), prompt
    if eng.eos_token_id is None:
        assert eng.decode_rows_discarded == 0
    assert eng._inflight is None and eng.stats()["active_slots"] == 0


class _Unreadable:
    def __array__(self, *a, **kw):
        raise RuntimeError("boom at the read")


@pytest.mark.parametrize("where", ["dispatch", "read"])
def test_fault_with_a_step_in_flight_fails_each_request_once(where):
    """A step that raises while another is in flight (at its dispatch,
    or, as a device fault does, at the deferred read) fails every live
    request exactly once with the engine's error, leaves no in-flight
    record, and the next request is served."""
    eng = LLMEngine("debug", tp=1, max_batch=2)
    step_jit = eng._step_jit

    def boom(*a):
        raise RuntimeError("boom at the dispatch")

    async def consume(prompt):
        got = []
        try:
            async for t in eng.generate(prompt, max_new_tokens=100):
                got.append(t)
        except RuntimeError as e:
            return got, e
        return got, None

    async def run():
        tasks = [asyncio.ensure_future(consume(p))
                 for p in ([1, 2, 3], [7, 7])]
        await _when(lambda: eng.batches >= 4 and eng._inflight is not None
                    and all(s is not None for s in eng._slots))
        with eng._mutex:
            live = [s.req for s in eng._slots]
            assert eng._inflight is not None
            if where == "dispatch":
                eng._step_jit = boom
            else:
                eng._inflight.tokens = _Unreadable()
        results = await asyncio.wait_for(asyncio.gather(*tasks), 60)
        with eng._mutex:
            eng._step_jit = step_jit
            assert eng._inflight is None
            assert eng._slots == [None, None]
        # exactly once: the error and nothing after it, no end marker
        assert all(r.out.empty() for r in live)
        after = await _agen_list(eng.generate([5, 9, 11], max_new_tokens=6))
        return results, after

    results, after = asyncio.run(run())
    for got, err in results:
        assert 4 <= len(got) < 100
        assert "decode cache lost to a failed engine step" in str(err)
    assert after == _greedy_reference(eng, [5, 9, 11], 6)
    assert eng._inflight is None


def test_restart_on_a_new_loop_drops_the_step_in_flight():
    """ensure_started on a new event loop, with a step of the old loop's
    requests still in flight, drops the record: the new loop's first
    request starts from an empty pipeline and streams what it should."""
    eng = LLMEngine("debug", tp=1, max_batch=2)

    async def abandoned():
        task = asyncio.ensure_future(_agen_list(
            eng.generate([1, 2, 3], max_new_tokens=100)))
        await _when(lambda: eng.batches >= 4 and eng._inflight is not None)
        return task  # asyncio.run cancels it, and the engine's loop

    old = asyncio.run(abandoned())
    assert old.cancelled()
    with eng._mutex:  # waits out the old loop's last round
        assert eng._inflight is not None and eng._slots[0] is not None

    async def fresh():
        await eng.ensure_started()
        assert eng._inflight is None and eng._slots == [None, None]
        return await _agen_list(eng.generate([7, 7], max_new_tokens=6))

    assert asyncio.run(fresh()) == _greedy_reference(eng, [7, 7], 6)
    assert eng._inflight is None and eng.decode_rows_discarded == 0


# ------------------------------------------- live rows, live positions
# decode path -> (the model served, the kernel's block of positions).
# With a head of a whole lane row the kernel also writes the step's new
# K and V rows (PR 48); "debug" has heads of 16, whose rows the XLA
# writes lay in
_DECODE_PATHS = {
    "xla": ("debug", None),
    "kernel": ("debug", 32),
    "kernel-writes": (llama.LlamaConfig(
        vocab_size=256, dim=256, n_layers=2, n_heads=2, n_kv_heads=1,
        hidden_dim=128, max_seq_len=256), 128),
}


@pytest.fixture(params=list(_DECODE_PATHS))
def decode_path(request, monkeypatch):
    """The decode step's attention on the XLA path, as every platform
    but a TPU takes it, and through the decode kernel (interpreted, in
    blocks of the path's positions), as a TPU does."""
    block = _DECODE_PATHS[request.param][1]
    if block:
        from ray_tpu.ops import attention

        # the engine asks the model's module (`decode_read_block`), which
        # asks ops/attention.py, as the step's own attention does
        monkeypatch.setattr(attention, "_on_tpu", lambda: True)
        monkeypatch.setattr(attention, "decode_block_len",
                            lambda *a: block)
    return request.param


def _small_engine(path, max_batch=2):
    return LLMEngine(_DECODE_PATHS[path][0], tp=1, max_batch=max_batch,
                     max_seq_len=256, prompt_buckets=(16, 64),
                     prefill_chunk=0, prefix_cache_entries=0)


def test_request_in_a_left_slot_streams_as_in_a_fresh_engine(decode_path):
    """A slot another request has left is told to the device as holding
    nothing (`_retire`) and is given to the next request by insert_row:
    the greedy tokens of a request admitted into it, beside a stream
    that is still live, are those a fresh engine gives it, whatever the
    slot's row held before (a deeper bucket's K and V here)."""
    eng = _small_engine(decode_path)
    leaver = (list(range(1, 41)), 4)        # bucket 64, leaves first
    stayer = ([17, 4, 9], 120)
    late = ([5, 9, 11], 12)                 # bucket 16, into the left slot

    async def run():
        a = asyncio.ensure_future(_agen_list(
            eng.generate(leaver[0], max_new_tokens=leaver[1])))
        b = asyncio.ensure_future(_agen_list(
            eng.generate(stayer[0], max_new_tokens=stayer[1])))
        await a
        c = await _agen_list(eng.generate(late[0], max_new_tokens=late[1]))
        assert not b.done()         # beside a stream that is still live
        return c, await b

    got_late, got_stayer = asyncio.run(run())
    fresh = _small_engine(decode_path)
    assert got_late == _collect(fresh, late[0], max_new_tokens=late[1])
    assert got_stayer == _collect(fresh, stayer[0],
                                  max_new_tokens=stayer[1])
    # every row is told as left once nothing decodes
    assert eng._row_live.count(True) <= 1


def test_kv_position_counters_rise_with_dispatched_steps_only(decode_path):
    """`decode_kv_positions_live` counts, per dispatched decode step, the
    positions inside the live rows' [start, length];
    `decode_kv_positions_read` those of the blocks the step's attention
    is asked to read: at least the live ones, at most the whole cache."""
    eng = _small_engine(decode_path)
    keys = ("batches", "decode_kv_positions_live",
            "decode_kv_positions_read")
    _collect(eng, [3, 8, 1], max_new_tokens=1)     # a prefill, no step
    st0 = eng.stats()
    assert [st0[k] for k in keys] == [0, 0, 0]
    n, new = 5, 6
    _collect(eng, list(range(1, n + 1)), max_new_tokens=new)
    st = eng.stats()
    steps = new - 1
    assert st["batches"] == steps
    # alone in the engine: step k attends to the prompt, the k tokens
    # decoded before it and the one it writes
    assert st["decode_kv_positions_live"] == sum(
        n + 1 + k for k in range(steps))
    whole = steps * eng.max_batch * eng.cfg.max_seq_len
    read = st["decode_kv_positions_read"]
    assert st["decode_kv_positions_live"] <= read <= whole
    if _DECODE_PATHS[decode_path][1]:
        # bucket 16: the row's one block, and none for the empty slot
        assert read == steps * _DECODE_PATHS[decode_path][1]
    else:
        assert read == whole
    _collect(eng, [3, 8, 1], max_new_tokens=1)
    assert [eng.stats()[k] for k in keys] == [st[k] for k in keys]


# ------------------------------- the loop's own account of its time (PR 58)
TILING = ("host_us_wait", "host_us_admit", "host_us_prefill_chunk",
          "host_us_finish_prefill", "host_us_decode_dispatch",
          "host_us_token_sync", "host_us_emit", "host_us_handoff")
HOST_TIME = TILING + ("loop_us", "loop_hops", "host_us_prefill_cache",
                      "host_us_gc", "loop_stalls", "loop_stall_us",
                      "prompt_tokens")


def _tiles(read: dict) -> bool:
    """The eight counters sum to `loop_us` within 1% (they are whole
    microseconds of one sum of seconds)."""
    return abs(sum(read[k] for k in TILING) - read["loop_us"]) <= \
        0.01 * read["loop_us"] + len(TILING)


def test_host_time_tiles_the_loop_at_any_read():
    """A run with one-shot prompts, chunked ones and mixed rounds: the
    wait, the six units' self time and the hand-off sum to the loop's
    elapsed time at the end, at reads taken from the event loop while a
    hop is out (the engine's loop is then in mid-hop) and in mid-wait."""
    eng, _ = _mixed_engine()
    assert set(HOST_TIME) <= set(eng.stats()) and eng.stats()["loop_us"] == 0
    reads = []

    async def run():
        work = asyncio.ensure_future(asyncio.to_thread(
            _long_prompts_beside_decoding_rows, eng))
        while not work.done():
            reads.append(eng.host_time())
            await asyncio.sleep(0.005)
        return await work

    asyncio.run(run())
    # the run's own event loop is closed, and with it the engine's loop
    end = eng.host_time()
    assert end == eng.host_time() and _tiles(end)
    assert len(reads) > 10 and all(_tiles(r) for r in reads if r["loop_us"])
    for a, b in zip(reads, reads[1:] + [end]):
        assert all(b[k] >= a[k] for k in HOST_TIME), (a, b)
    assert all(end[k] > 0 for k in TILING)
    st = eng.stats()
    # an admission, a chunk's round or a decode round each; a decode
    # round dispatches a step or reads the last one of a stream
    rounds = end["loop_hops"] - st["prefills"] - st["prefill_chunks"]
    assert 0 < rounds <= st["batches"] + st["prefills"]
    assert 0 < end["host_us_prefill_cache"] < end["host_us_prefill_chunk"] \
        + end["host_us_decode_dispatch"]
    # in mid-wait: the engine's loop blocks on its queue with no slot taken
    async def idle():
        await _agen_list(eng.generate([1, 2, 3], max_new_tokens=2))
        a = eng.host_time()
        await asyncio.sleep(0.05)
        return a, eng.host_time()

    a, b = asyncio.run(idle())
    assert _tiles(a) and _tiles(b)
    assert b["host_us_wait"] - a["host_us_wait"] >= 45_000
    assert b["loop_hops"] == a["loop_hops"]


class _Ticks:
    """`time` for serve/llm.py with a clock the test sets."""
    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now


def test_a_units_self_time_is_its_interval_less_the_units_inside_it(
        monkeypatch):
    """By a clock the test sets: a one-shot admission (prefill_chunk and
    finish_prefill inside admit), a mixed round (decode_dispatch inside
    prefill_chunk), with reads in mid-wait and in mid-hop."""
    from ray_tpu.serve import llm

    ticks = _Ticks()
    monkeypatch.setattr(llm, "time", ticks)
    clock = llm._LoopClock()
    assert clock.read()["loop_us"] == 0

    def at(t, call, *args):
        ticks.now = 100.0 + t
        return call(*args)

    at(0, clock.start)
    at(1, clock.turn, "wait")
    mid_wait = at(3, clock.read)
    assert (mid_wait["loop_us"], mid_wait["host_us_wait"],
            mid_wait["host_us_handoff"]) == (3_000_000, 2_000_000, 1_000_000)
    at(4, clock.turn, "between")
    at(5, clock.begin_hop, "admit", "r1", 0)
    at(6, clock.enter, "admit")
    at(8, clock.enter, "prefill_chunk")
    mid_hop = at(9, clock.read)
    assert (mid_hop["host_us_admit"], mid_hop["host_us_prefill_chunk"],
            mid_hop["host_us_handoff"]) == (2_000_000, 1_000_000, 3_000_000)
    assert _tiles(mid_hop) and mid_hop["loop_us"] == 9_000_000
    at(11, clock.leave)
    at(11, clock.enter, "finish_prefill")
    at(12, clock.leave)
    at(15, clock.leave)
    at(16, clock.end_hop)
    at(16, clock.begin_hop, "prefill", "r2", 1)
    at(16, clock.enter, "prefill_chunk")
    at(17, clock.enter, "decode_dispatch")
    at(21, clock.leave)
    at(21.5, clock.leave)
    at(21.5, clock.enter, "token_sync")
    at(22, clock.leave)
    at(22.25, clock.end_hop)
    at(23, clock.turn, None)
    end = at(40, clock.read)
    assert end == {
        "loop_us": 23_000_000, "loop_hops": 2, "host_us_wait": 3_000_000,
        "host_us_admit": 5_000_000, "host_us_prefill_chunk": 4_500_000,
        "host_us_finish_prefill": 1_000_000,
        "host_us_decode_dispatch": 4_000_000, "host_us_token_sync": 500_000,
        "host_us_emit": 0, "host_us_handoff": 5_000_000,
        "host_us_prefill_cache": 0, "loop_stalls": 2,
        "loop_stall_us": 17_250_000}
    first, second = clock.kept_stalls()
    assert first == {"t": 105.0, "seconds": 11.0, "hop": "admit",
                     "units": {"admit": 5.0, "prefill_chunk": 3.0,
                               "finish_prefill": 1.0},
                     "handoff_s": 2.0, "prefill_cache_s": 0.0, "gc_s": 0.0,
                     "programs_asked": 0, "active_slots": 0,
                     "request_id": "r1"}
    assert second["units"] == {"prefill_chunk": 1.5, "decode_dispatch": 4.0,
                               "token_sync": 0.5}
    assert (second["hop"], second["handoff_s"], second["active_slots"]) == (
        "prefill", 0.25, 1)
    # a loop that starts afterwards goes on counting; the newest stalls
    # are kept, every one is counted
    at(50, clock.start)
    for k in range(20):
        at(51 + k, clock.begin_hop, "decode", "", 2)
        at(51.5 + k, clock.end_hop)
    at(71, clock.begin_hop, "decode", "", 2)
    at(71.25, clock.end_hop)       # STALL_S itself is no stall
    read = at(72, clock.read)
    assert (read["loop_stalls"], read["loop_stall_us"]) == (22, 27_250_000)
    assert read["loop_us"] == 45_000_000 and _tiles(read)
    kept = clock.kept_stalls()
    assert len(kept) == llm.STALLS_KEPT == 16
    assert [r["t"] for r in kept] == [100.0 + 55 + k for k in range(16)]
    assert "request_id" not in kept[0]


def test_admit_excludes_the_prefill_and_the_finish_inside_it(monkeypatch):
    """Through the engine: a one-shot admission whose prefill call is
    made to take 0.2 s. The time lands in `prefill_chunk`, not also in
    `admit`, which holds both; the hop's sum is the loop's."""
    eng = _small_engine("xla")
    _collect(eng, [5, 9, 11], max_new_tokens=2)        # every program
    before = eng.host_time()
    step = eng._step

    def slow_prefill(params, cache, tokens, *rest):
        if tokens.ndim == 2:
            time.sleep(0.2)
        return step(params, cache, tokens, *rest)

    monkeypatch.setattr(eng, "_step", slow_prefill)
    _collect(eng, [5, 9, 12], max_new_tokens=2)
    after = eng.host_time()
    took = {k: after[k] - before[k] for k in HOST_TIME}
    assert took["host_us_prefill_chunk"] >= 200_000
    assert took["host_us_admit"] + took["host_us_finish_prefill"] < \
        took["host_us_prefill_chunk"]
    assert took["prompt_tokens"] == 3 and _tiles(after)


def test_a_stalled_hop_is_counted_and_kept_with_the_unit_it_fell_in(
        monkeypatch):
    """One decode step made to sleep 0.3 s: one stall, its record names
    the hop and the unit, `t` lies in the sleep (the clock of a client's
    stamps), nothing was asked of XLA in it, and the hops around it are
    none."""
    eng = _small_engine("xla")
    _collect(eng, [5, 9, 11], max_new_tokens=6)        # every program
    before = eng.stats()
    step, slept = eng._step, []

    def stalling(params, cache, tokens, *rest):
        if tokens.ndim == 1 and eng.batches - before["batches"] == 2 \
                and not slept:
            slept.append(time.perf_counter())
            time.sleep(0.3)
            slept.append(time.perf_counter())
        return step(params, cache, tokens, *rest)

    monkeypatch.setattr(eng, "_step", stalling)
    assert len(_collect(eng, [5, 9, 12], max_new_tokens=6)) == 6
    after = eng.stats()
    assert after["loop_stalls"] - before["loop_stalls"] == 1
    assert after["loop_hops"] - before["loop_hops"] >= 5
    (rec,) = after["stalls"][len(before["stalls"]):]
    assert rec["hop"] == "decode" and rec["seconds"] >= 0.3 > llm_mod.STALL_S
    assert max(rec["units"], key=rec["units"].get) == "decode_dispatch"
    assert rec["units"]["decode_dispatch"] >= 0.3
    assert rec["t"] <= slept[0] and slept[1] <= rec["t"] + rec["seconds"]
    assert rec["programs_asked"] == 0 and rec["active_slots"] == 1
    assert rec["prefill_cache_s"] == 0.0 and "request_id" not in rec
    assert abs(after["loop_stall_us"] - before["loop_stall_us"]
               - rec["seconds"] * 1e6) <= 1
    # what `serve_host_stall_share` makes of it in a 30 s window
    from benchmarks.readers import loop

    obs = {"before": {"stats": before}, "after": {"stats": after},
           "window": (100.0, 130.0)}
    assert loop.counter_share_of_window(obs, "loop_stall_us") == \
        pytest.approx(100 * rec["seconds"] / 30, rel=1e-4)


def test_prompt_tokens_counts_every_prefilled_prompt_whole():
    """`prompt_tokens`: the prompts' lengths, the part a stored prefix
    covered included, one-shot or in chunks."""
    eng = LLMEngine("debug", tp=1, max_batch=2, max_seq_len=256,
                    prompt_buckets=(16, 64), prefill_chunk=32,
                    prefix_cache_entries=4)
    shared = list(range(3, 40))
    prompts = [[5, 9, 11], shared + [77, 78], shared + [88] * 9]
    for p in prompts:
        assert len(_collect(eng, p, max_new_tokens=2)) == 2
    st = eng.stats()
    assert st["prefix_hits"] == 1 and st["prefix_hit_tokens"] == 32
    assert st["prompt_tokens"] == sum(map(len, prompts)) == 3 + 39 + 46


def test_the_window_holds_every_counter_of_the_loop():
    """What a serve cell's info line prints (`stats_in_window`: the
    difference of every whole number of `stats()` between the window's
    two ends) holds the loop's account, each a count and none negative;
    the stalls' records are no number and stay out."""
    from benchmarks import serve_cell

    eng = _small_engine("xla")
    _collect(eng, [5, 9, 11], max_new_tokens=3)
    before = eng.stats()
    _collect(eng, [5, 9, 12, 4], max_new_tokens=5)
    after = eng.stats()
    window = serve_cell.stats_in_window(
        {"before": {"stats": before}, "after": {"stats": after}})
    assert set(HOST_TIME) <= set(window) and "stalls" not in window
    assert all(type(window[k]) is int and window[k] >= 0 for k in HOST_TIME)
    assert window["prompt_tokens"] == 4 and window["loop_hops"] >= 5
    assert abs(sum(window[k] for k in TILING) - window["loop_us"]) <= \
        0.01 * window["loop_us"] + len(TILING)


def test_counter_share_of_window_by_hand():
    """benchmarks/readers/loop.py: 3 s of `loop_stall_us` in a window of
    30 s read 10.0; no counter at either end, nothing."""
    from benchmarks.readers import loop

    ends = lambda a, b: {"before": {"stats": a}, "after": {"stats": b},
                         "window": (100.0, 130.0)}
    obs = ends({"loop_stall_us": 250_000}, {"loop_stall_us": 3_250_000})
    assert loop.counter_share_of_window(obs, "loop_stall_us") == 10.0
    assert loop.counter_share_of_window(obs, "host_us_wait") is None
    for a, b in (({}, {"loop_stall_us": 1}), ({"loop_stall_us": 1}, {}),
                 ({}, {})):
        assert loop.counter_share_of_window(
            ends(a, b), "loop_stall_us") is None
    sound = ends({"loop_stall_us": 0}, {"loop_stall_us": 0})
    assert loop.counter_share_of_window(sound, "loop_stall_us") == 0.0
