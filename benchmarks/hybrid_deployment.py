"""The deployment the hybrid family's serve cell runs: the program's
LlamaService (the engine's one service class, whatever the model), given
the configuration as data. Beside benchmarks/deployment.py, whose
observation methods (bench_report, trace_start/stop/reduce) it inherits:
what differs is how the config and the weights are made and which plain
reference the finished requests are held against.
"""

from __future__ import annotations

import time

from ray_tpu.serve.llm import LlamaService

from benchmarks.deployment import BenchLlamaService


def state_errors(got, ref, mamba: dict):
    """Relative RMS error of the recurrent state, one number a Mamba
    layer: got, ref [layers, heads, p, n]; over each layer's slow heads,
    the eighth of them whose nominal rate softplus(dt_bias) * exp(A_log)
    is smallest. A slow head forgets little in a step, so what every
    step's rounding of a state held below float32 adds stays and grows
    as a random walk (2**-9 / sqrt(3) of the state a step in bfloat16,
    0.9% after 64), while a fast head carries the last rounding alone.
    The FIRST layer's number is the judged one: its inputs are two
    bfloat16 roundings from exact, so the float32 state reads 0.33-0.62%
    there and a bfloat16 state 1.0-4.1% (builder's chip runs, PR 28;
    the readings are in the configuration's tolerances.why); with depth
    the inputs' own error (3-5% at the last layer) covers the state's."""
    import jax
    import numpy as np

    rate = np.asarray(jax.nn.softplus(mamba["dt_bias"])
                      * jax.numpy.exp(mamba["A_log"]), np.float32)
    slow = np.argsort(rate, axis=1)[:, :max(1, rate.shape[1] // 8)]
    rows = np.arange(rate.shape[0])[:, None]
    err = ((got - ref) ** 2).sum((-1, -2))[rows, slow].sum(1)
    return np.sqrt(err / (ref ** 2).sum((-1, -2))[rows, slow].sum(1))


class BenchHybridService(BenchLlamaService):
    def __init__(self, config: dict, seed: int, engine_kw: dict):
        import jax

        from benchmarks import hybrid_model, model

        t0 = time.perf_counter()
        self.config = config
        self._programs = 0   # as BenchLlamaService counts them

        def on_duration(event, secs, *a, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self._programs += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        cfg = hybrid_model.program_config(
            config, "serve", max_seq_len=engine_kw["max_seq_len"])
        jax.devices()  # backend up before the clock of `weights_s`
        t1 = time.perf_counter()
        params = hybrid_model.jitted_init(cfg, seed)
        jax.block_until_ready(params)
        t2 = time.perf_counter()
        engine_kw = dict(engine_kw)
        if "prompt_buckets" in engine_kw:
            engine_kw["prompt_buckets"] = tuple(engine_kw["prompt_buckets"])
        LlamaService.__init__(self, cfg, params=params,
                              seed=model.fold_seed(seed), **engine_kw)
        self.setup = {"backend_s": t1 - t0, "weights_s": t2 - t1,
                      "engine_s": time.perf_counter() - t2}
        self._trace_dir = None
        self._trace_wall = None

    def reference_check(self, samples: list, check_len: int,
                        decode_tokens: int) -> list:
        """BenchLlamaService.reference_check for this family: the plain
        reference's full forward over prompt + generated against (a) the
        program's own prefill (prompt left-padded to `check_len`, one
        call, so the recurrent state must come through the padding
        untouched) and cached decode (teacher-forced, the first
        `decode_tokens` tokens, each from the state the step before
        left) as relative RMS error of the logits, (b) every streamed
        token's distance under the reference's best logit, and (c) the
        recurrent state those steps left in the program's cache against
        the reference scan's H after the same token (`state_errors`)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import granite_hybrid

        from benchmarks import hybrid_model
        from benchmarks.reference import granite_hybrid_ref

        cfg = self.engine.cfg
        params = self.engine.params
        hp = hybrid_model.reference_hp(self.config)
        step_fn = jax.jit(lambda p, cache, toks: granite_hybrid.decode_step(
            p, cache, toks, cfg), donate_argnums=(1,))
        out = []
        for s in samples:
            prompt, gen = list(s["tokens"]), list(s["generated"])
            n, g = len(prompt), len(gen)
            # reference: pad on the right (behind the causal mask and
            # after every row that is read) to a multiple of 128 so few
            # shapes compile
            total = -(-(n + g) // 128) * 128
            toks = np.zeros((1, total), np.int32)
            toks[0, :n + g] = prompt + gen
            rows = np.arange(n - 1, n + g - 1, dtype=np.int32)
            k = min(decode_tokens, g)
            # the cache after the prefill and k - 1 steps has consumed
            # the tokens up to position n + k - 2
            ref, ref_state = granite_hybrid_ref.logits_and_states(
                params, jnp.asarray(toks), hp, jnp.asarray(rows), n + k - 2)
            ref = np.asarray(ref, np.float32)
            got = np.asarray(gen)
            margin = ref.max(-1) - ref[np.arange(g), got]

            # the program's prefill and cached decode, teacher-forced
            start = check_len - n
            cache = granite_hybrid.init_cache(cfg, 1, max_len=check_len + k)
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
            by_layer = state_errors(
                np.asarray(cache["state"][:, 0], np.float32),
                np.asarray(ref_state), params["mamba"])
            out.append({
                "state_rel_rms": float(by_layer[0]),
                "state_rel_rms_by_layer": [round(float(e), 5)
                                           for e in by_layer],
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
