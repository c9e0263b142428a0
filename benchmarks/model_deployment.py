"""The deployment a serve cell runs for a configuration file that names
its own helper module under "model" (benchmarks/<model>.py:
`program_config`, `jitted_init`, `reference_check`): the program's
LlamaService (the engine's one service class, whatever the model), given
the configuration as data. Beside benchmarks/deployment.py, whose
observation methods (bench_report, trace_start/stop/reduce) it inherits.
It binds no model: what differs between models is the helper's.
"""

from __future__ import annotations

import importlib
import time

from ray_tpu.serve.llm import LlamaService

from benchmarks.deployment import BenchLlamaService


def helper(config: dict):
    """The module the configuration file names under "model"."""
    return importlib.import_module("benchmarks." + config["model"])


class BenchModelService(BenchLlamaService):
    def __init__(self, config: dict, seed: int, engine_kw: dict):
        import jax

        from benchmarks import model

        t0 = time.perf_counter()
        self.config = config
        self._helper = helper(config)
        self._programs = 0   # as BenchLlamaService counts them

        def on_duration(event, secs, *a, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self._programs += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        cfg = self._helper.program_config(
            config, "serve", max_seq_len=engine_kw["max_seq_len"])
        jax.devices()  # backend up before the clock of `weights_s`
        t1 = time.perf_counter()
        params = self._helper.jitted_init(cfg, seed)
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
        return self._helper.reference_check(self, samples, check_len,
                                            decode_tokens)
