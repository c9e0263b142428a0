"""Every model module of `models.registry()` against the contract that
`models.module_for` states and `serve/llm.py`'s engine relies on. The
cases come from the registry and from `models.REQUIRED` / `OPTIONAL`: a
seventh module is covered by its row there and its smallest config here."""

import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks import (gqa_moe_model, latent_moe_model,
                        manifest as manifest_mod, rehearsal, sparse_moe_model)
from ray_tpu import models
from ray_tpu.models import (dots3_note, evabyte, granite_hybrid, kimi_k2,
                            laguna, llama)

B, N, CHUNK = 7, 32, 16     # slots, cache depth, a prefill chunk
BOOKKEEPING = ("length", "start", "aux")


def _rehearsal_twin(name: str, program_config):
    """The benchmark's CPU twin of a published config, as the model's
    own test file builds it."""
    configs = os.path.join(manifest_mod.ROOT, "benchmarks")
    with open(os.path.join(configs, "configs", name)) as f:
        full = json.load(f)
    with open(os.path.join(configs, "rehearsal", "configs", name)) as f:
        twin = rehearsal.overlay(full, json.load(f))
    held = {"param_dtype": "float32", "compute_dtype": "float32"}
    return program_config({**twin, "held_as": {"serve": held}}, "serve",
                          max_seq_len=96)


# the smallest config of each registered type, from the model's test file
SMALLEST = {
    llama.LlamaConfig: lambda: llama.config_for("debug"),
    granite_hybrid.GraniteHybridConfig:
        lambda: granite_hybrid.GraniteHybridConfig(
            vocab_size=256, dim=64, hidden_dim=128, n_heads=4, n_kv_heads=2,
            layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_chunk_size=32, max_seq_len=256),
    dots3_note.Dots3NoteConfig: lambda: _rehearsal_twin(
        "dots3-note-prev.json", sparse_moe_model.program_config),
    evabyte.EvaByteConfig: lambda: evabyte.EvaByteConfig(
        vocab_size=32, dim=64, n_layers=3, n_heads=4, hidden_dim=96,
        max_seq_len=128, window_size=8, chunk_size=2, n_pred_heads=8,
        dtype=jnp.float32, param_dtype=jnp.float32),
    kimi_k2.KimiK2Config: lambda: _rehearsal_twin(
        "Kimi-K2.6.json", latent_moe_model.program_config),
    laguna.LagunaConfig: lambda: _rehearsal_twin(
        "Laguna-S-2.1.json", gqa_moe_model.program_config),
}


@pytest.fixture(params=models.registry(),
                ids=lambda row: row[1].__name__.rsplit(".", 1)[-1])
def served(request):
    """(module, smallest config) of one row of the registry, the module
    as `module_for` hands it to the engine."""
    config_type, module = request.param
    cfg = SMALLEST[config_type]()
    assert models.module_for(cfg) is module
    return module, cfg


def _shapes(tree):
    return jax.tree.map(lambda x: (x.shape, x.dtype), tree)


def test_module_has_every_name_and_serves_its_config(served):
    module, cfg = served
    for name in (*models.REQUIRED, *models.OPTIONAL):
        assert hasattr(module, name), name
    assert isinstance(module.TENSOR_PARALLEL, bool)


def test_cache_leaves_are_its_axes_keys_and_rows_lie_on_batch(served):
    module, cfg = served
    cache = jax.eval_shape(lambda: module.init_cache(cfg, B, max_len=N))
    axes = module.cache_logical_axes(cfg)
    assert set(cache) == set(axes)
    for name, leaf in cache.items():
        assert len(axes[name]) == leaf.ndim, name
        if name not in BOOKKEEPING:
            # B is no other dimension of any twin's leaves
            assert leaf.shape[axes[name].index("batch")] == B, name
            assert leaf.shape.count(B) == 1, name
    assert {"length", "start"} <= set(cache)


def test_position_axes_are_as_deep_as_asked_and_kinds_name_leaves(served):
    module, cfg = served
    cache = jax.eval_shape(lambda: module.init_cache(cfg, B, max_len=N))
    rows = set(cache) - set(BOOKKEEPING)
    assert set(module.CACHE_LEN_AXIS) <= rows
    for name, axis in module.CACHE_LEN_AXIS.items():
        # no module rounds max_len: the engine cuts a bucket's positions
        # out of a leaf made for exactly that bucket
        assert cache[name].shape[axis] == N, name
    assert set(module.CACHE_KIND) <= rows


def test_decode_step_returns_the_engines_logits_and_the_cache_it_got(served):
    """Both shapes the engine's `step` traces: the slots' [b, 1] tokens
    over per-row depths, and one request's [1, chunk] prefill. The cache
    comes back in the tree, shapes and dtypes it went in (donation)."""
    module, cfg = served
    params = jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.PRNGKey(0)))
    for b, s in ((B, 1), (1, CHUNK)):
        def run(params, b=b, s=s):
            cache = module.init_cache(cfg, b, max_len=N)
            if s == 1:
                cache["length"] = jnp.full((b,), 5, jnp.int32)
            out = module.decode_step(params, cache,
                                     jnp.ones((b, s), jnp.int32), cfg)
            return cache, out
        cache, (logits, new_cache) = jax.eval_shape(run, params)
        assert logits.shape == (b, cfg.vocab_size), (b, s)
        assert jnp.issubdtype(logits.dtype, jnp.floating)
        assert _shapes(new_cache) == _shapes(cache), (b, s)


def test_step_aux_is_the_caches_aux_leaf(served):
    module, cfg = served
    cache = jax.eval_shape(lambda: module.init_cache(cfg, B, max_len=N))
    assert bool(module.STEP_AUX) == ("aux" in cache)
    if module.STEP_AUX:
        assert cache["aux"].shape == (len(module.STEP_AUX),)
        assert all(isinstance(k, str) and isinstance(v, str)
                   for k, v in module.STEP_AUX.items())


def test_counters_name_what_stats_will_carry(served):
    """The two calls the engine's constructor makes give the keys of
    `stats()`; every later call adds into those keys and no others."""
    module, cfg = served
    idle = module.decode_counters(cfg, [], B)
    none = module.prefill_counters(cfg, 0, 0, 0, cfg.max_seq_len)
    for counters in (idle, none):
        assert all(isinstance(k, str) and type(v) is int and v >= 0
                   for k, v in counters.items()), counters
    # a call of no tokens counts nothing (a step of no live row may: it
    # reads what it reads of every slot it has)
    assert not any(none.values())
    assert module.decode_counters(cfg, [(0, 5), (2, 9)], B).keys() \
        == idle.keys()
    assert module.prefill_counters(cfg, 4, 0, CHUNK, N).keys() == none.keys()


def test_mixed_step_is_optional_and_llama_alone_has_it(served):
    """`mixed_step` (PR 57): None unless the module has the shared pass
    of a chunk and the rows' step. llama's gives both programs' logits
    and both caches as they went in."""
    module, cfg = served
    assert models.OPTIONAL["mixed_step"] is None
    if module is not llama:
        assert module.mixed_step is None
        return
    params = jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.PRNGKey(0)))

    def run(params):
        small = module.init_cache(cfg, 1, max_len=N)
        cache = module.init_cache(cfg, B, max_len=N)
        cache["length"] = jnp.full((B,), 5, jnp.int32)
        return (small, cache), module.mixed_step(
            params, small, jnp.ones((1, CHUNK), jnp.int32), cache,
            jnp.ones((B, 1), jnp.int32), cfg)

    (small, cache), (chunk_logits, new_small, logits, new_cache) = \
        jax.eval_shape(run, params)
    assert chunk_logits.shape == (1, cfg.vocab_size)
    assert logits.shape == (B, cfg.vocab_size)
    assert chunk_logits.dtype == logits.dtype == jnp.float32
    assert _shapes(new_small) == _shapes(small)
    assert _shapes(new_cache) == _shapes(cache)


def test_an_engine_over_a_module_without_mixed_step_runs_two_programs():
    """A round with a chunk due, over a module that has no `mixed_step`
    (the byte model's smallest config): the chunk's call of `step` and
    then the rows' call, as before PR 57, and nothing counted as
    mixed."""
    import asyncio

    from ray_tpu.serve.llm import LLMEngine

    cfg = SMALLEST[evabyte.EvaByteConfig]()
    eng = LLMEngine(cfg, tp=1, max_batch=2, prompt_buckets=(32, 64),
                    prefill_chunk=8)
    assert eng._mixed_jit is None
    calls, step_jit = [], eng._step_jit
    eng._step_jit = lambda *a: (calls.append(a[2].shape), step_jit(*a))[1]

    async def stream(prompt, n):
        return [t async for t in eng.generate(prompt, max_new_tokens=n)]

    async def run():
        row = asyncio.ensure_future(stream([1, 2, 3], 60))
        while eng.batches < 3:
            await asyncio.sleep(0.01)
        late = await stream(list(range(1, 31)) * 2, 3)
        return await row, late

    row, late = asyncio.run(run())
    assert (len(row), len(late)) == (60, 3)
    st = eng.stats()
    assert (st["mixed_steps"], st["mixed_rows"]) == (0, 0)
    # the short prompt's one chunk that holds a token, the long one's 8
    assert st["prefill_chunks"] == 1 + 8
    # each chunk's program and, beside a live row, the rows' decode step
    # behind it
    chunks = [i for i, shape in enumerate(calls) if shape == (1, 8)]
    assert len(chunks) == 9
    assert all(calls[i + 1] == (eng.max_batch,) for i in chunks[1:])


def test_a_module_lacking_a_required_name_is_refused(monkeypatch):
    lacking = types.SimpleNamespace(**vars(llama))
    del lacking.decode_read_block
    monkeypatch.setattr(
        models, "registry", lambda: ((llama.LlamaConfig, lacking),))
    with pytest.raises(TypeError, match=r"ray_tpu\.models\.llama.*lacks "
                                        r"decode_read_block"):
        models.module_for(llama.config_for("debug"))
