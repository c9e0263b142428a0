"""The deployment a serve cell runs: the program's LlamaService, given a
configuration file instead of a preset name.

The request path is the program's own (proxy -> router -> replica ->
LLMEngine). What this subclass adds runs outside the measured window or
beside it: the configuration registered under its name, the weights made
in one jitted program from the seed, the comparison with the plain
reference, the profiler's start and stop, and the device's memory peak.
"""

from __future__ import annotations

import os
import time

from ray_tpu.serve.llm import LlamaService


class BenchLlamaService(LlamaService):
    def __init__(self, config: dict, seed: int, engine_kw: dict):
        import jax

        from ray_tpu.models import llama

        from benchmarks import model

        t0 = time.perf_counter()
        self.config = config
        # every program this process asks XLA for, compiled or fetched
        # from the persistent cache, from here on
        self._programs = 0

        def on_duration(event, secs, *a, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self._programs += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        preset = model.register_preset(config, "serve")
        cfg = llama.config_for(preset,
                               max_seq_len=engine_kw["max_seq_len"])
        jax.devices()  # backend up before the clock of `weights_s`
        t1 = time.perf_counter()
        params = model.jitted_init(cfg, seed)
        jax.block_until_ready(params)
        t2 = time.perf_counter()
        engine_kw = dict(engine_kw)
        if "prompt_buckets" in engine_kw:
            engine_kw["prompt_buckets"] = tuple(engine_kw["prompt_buckets"])
        super().__init__(preset, params=params, seed=model.fold_seed(seed),
                         **engine_kw)
        self.setup = {"backend_s": t1 - t0, "weights_s": t2 - t1,
                      "engine_s": time.perf_counter() - t2}
        self._trace_dir = None
        self._trace_wall = None

    # ----------------------------------------------------- observations
    def bench_report(self) -> dict:
        from benchmarks import model

        rep = self.device_report()
        rep.pop("memory", None)
        return {**rep, "setup": self.setup, "stats": self.stats(),
                "programs": self._programs,
                "memory_peak_bytes": model.memory_peak_bytes()}

    def trace_start(self, trace_dir: str) -> bool:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # host TraceMe spans only: small
        os.makedirs(trace_dir, exist_ok=True)
        self._trace_dir = trace_dir
        self._trace_wall = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        return True

    def trace_stop(self) -> dict:
        import jax

        jax.profiler.stop_trace()
        return {"traced_wall_s": time.perf_counter() - self._trace_wall}

    def trace_reduce(self) -> dict:
        """Reduce the trace just taken (call after the window: it takes
        seconds)."""
        from benchmarks import trace_reduce

        return trace_reduce.reduce_dir(self._trace_dir)

    # ------------------------------------------------------ correctness
    def reference_check(self, samples: list, check_len: int,
                        decode_tokens: int) -> list:
        """Hold finished greedy requests against the plain reference,
        with this replica's own parameters.

        For each sample {"tokens", "generated"}: the reference's full
        forward over prompt + generated gives, at every generated
        position, the logits a correct decoder had before it. Against
        them, (a) the program's own prefill (prompt left-padded to
        `check_len`, one call) and cached decode (teacher-forced, the
        first `decode_tokens` tokens) logits, as relative RMS error; (b)
        every streamed token's distance under the reference's best
        logit at its position."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import llama

        from benchmarks import model
        from benchmarks.reference import llama_ref

        cfg = self.engine.cfg
        params = self.engine.params
        hp = model.reference_hp(self.config)
        ref_fn = jax.jit(lambda p, toks, rows: llama_ref.logits_at(
            p, toks, hp, rows))
        step_fn = jax.jit(lambda p, cache, toks: llama.decode_step(
            p, cache, toks, cfg), donate_argnums=(1,))
        out = []
        for s in samples:
            prompt, gen = list(s["tokens"]), list(s["generated"])
            n, g = len(prompt), len(gen)
            # reference: pad on the right (behind the causal mask) to a
            # multiple of 128 so few shapes compile
            total = -(-(n + g) // 128) * 128
            toks = np.zeros((1, total), np.int32)
            toks[0, :n + g] = prompt + gen
            rows = np.arange(n - 1, n + g - 1, dtype=np.int32)
            ref = np.asarray(ref_fn(params, jnp.asarray(toks),
                                    jnp.asarray(rows)), np.float32)
            got = np.asarray(gen)
            margin = ref.max(-1) - ref[np.arange(g), got]

            # the program's prefill and cached decode, teacher-forced
            k = min(decode_tokens, g)
            start = check_len - n
            cache = llama.init_kv_cache(cfg, 1, max_len=check_len + k)
            cache["start"] = jnp.asarray([start], jnp.int32)
            padded = np.zeros((1, check_len), np.int32)
            padded[0, start:] = prompt
            logits, cache = step_fn(params, cache, jnp.asarray(padded))
            prog = [np.asarray(logits[0], np.float32)]
            for t in gen[:k - 1]:
                logits, cache = step_fn(
                    params, cache, jnp.asarray([[t]], jnp.int32))
                prog.append(np.asarray(logits[0], np.float32))
            prog = np.stack(prog)
            err = prog - ref[:k]
            out.append({
                "prompt_len": n, "generated": g,
                "logits_rel_rms": float(np.sqrt((err ** 2).mean())
                                        / np.sqrt((ref[:k] ** 2).mean())),
                "logits_max_abs_err": float(np.abs(err).max()),
                "logit_std": float(ref.std()),
                "token_max_margin": float(margin.max()),
                "tokens_not_argmax": int((ref.argmax(-1) != got).sum()),
                "finite": bool(np.isfinite(prog).all()
                               and np.isfinite(ref).all())})
        return out
