"""In-mesh pipeline parallelism (GPipe over a `stage` axis via ppermute,
parallel/pipeline.py) — forward and gradient parity vs sequential
execution on the 8-device CPU mesh. SURVEY §7 step 8 (the reference's
analog is compiled actor-DAGs with NCCL channels; TPU-native PP stays
inside one GSPMD program)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from ray_tpu.parallel.pipeline import pipeline_apply, stack_stage_params


def _mesh(devices, n):
    return Mesh(np.array(devices[:n]), ("stage",))


def _mlp_stage(params, x):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def _make_stage_params(key, n_stages, d, h):
    stages = []
    for i in range(n_stages):
        k1, k2, key = jax.random.split(key, 3)
        stages.append({
            "w1": jax.random.normal(k1, (d, h)) * 0.3,
            "b1": jnp.zeros((h,)),
            "w2": jax.random.normal(k2, (h, d)) * 0.3,
            "b2": jnp.zeros((d,)),
        })
    return stack_stage_params(stages)


def _sequential(stage_params, x, n_stages):
    for s in range(n_stages):
        p = jax.tree.map(lambda l: l[s], stage_params)
        x = _mlp_stage(p, x)
    return x


@pytest.mark.parametrize("n_stages,n_micro", [(2, 4), (4, 4), (4, 8)])
def test_pipeline_forward_parity(cpu_mesh_devices, n_stages, n_micro):
    mesh = _mesh(cpu_mesh_devices, n_stages)
    d, h, b = 8, 16, 8
    params = _make_stage_params(jax.random.PRNGKey(0), n_stages, d, h)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, d))
    out = jax.jit(lambda p, xx: pipeline_apply(
        _mlp_stage, p, xx, mesh, n_micro=n_micro))(params, x)
    ref = _sequential(params, x, n_stages)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_pipeline_grad_parity(cpu_mesh_devices):
    n_stages, n_micro = 4, 4
    mesh = _mesh(cpu_mesh_devices, n_stages)
    d, h, b = 8, 16, 8
    params = _make_stage_params(jax.random.PRNGKey(2), n_stages, d, h)
    x = jax.random.normal(jax.random.PRNGKey(3), (b, d))
    tgt = jax.random.normal(jax.random.PRNGKey(4), (b, d))

    def loss_pipe(p):
        out = pipeline_apply(_mlp_stage, p, x, mesh, n_micro=n_micro)
        return ((out - tgt) ** 2).mean()

    def loss_seq(p):
        return ((_sequential(p, x, n_stages) - tgt) ** 2).mean()

    g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    g_seq = jax.grad(loss_seq)(params)
    for key in ("w1", "b1", "w2", "b2"):
        np.testing.assert_allclose(g_pipe[key], g_seq[key],
                                   atol=1e-5, rtol=1e-4,
                                   err_msg=f"grad {key} mismatch")


def test_pipeline_llama_blocks(cpu_mesh_devices):
    """Transformer blocks as pipeline stages: 4 llama blocks split over 2
    stages (2 layers per stage), parity with the dense scan."""
    from ray_tpu.models import llama
    from ray_tpu.ops.rope import rope_frequencies

    cfg = llama.config_for("debug", remat=False, attn_impl="xla")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    L = cfg.n_layers          # 2 in debug preset
    n_stages = 2
    per_stage = L // n_stages

    # reshape [L, ...] stacked layer params to [n_stages, per_stage, ...]
    stage_params = jax.tree.map(
        lambda l: l.reshape((n_stages, per_stage) + l.shape[1:]),
        params["layers"])

    def stage_fn(stage_layers, x):
        x = x.astype(cfg.dtype)

        def step(xx, layer):
            return llama._block(cfg, xx, layer, cos, sin, None), None

        x, _ = jax.lax.scan(step, x, stage_layers)
        return x.astype(jnp.float32)

    b, s = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                cfg.vocab_size)
    x0 = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    mesh = _mesh(cpu_mesh_devices, n_stages)
    out = jax.jit(lambda p, xx: pipeline_apply(
        stage_fn, p, xx, mesh, n_micro=2))(stage_params, x0)

    # reference: plain scan over all layers
    def step(xx, layer):
        return llama._block(cfg, xx, layer, cos, sin, None), None

    ref, _ = jax.lax.scan(step, x0.astype(cfg.dtype), params["layers"])
    np.testing.assert_allclose(out, ref.astype(jnp.float32),
                               atol=2e-4, rtol=2e-4)


# ------------------------------------------- GPipe microbatches on the DAG
def test_pp_microbatch_loop_on_compiled_dag(local_cluster):
    """The MPMD pipeline shape (VERDICT r3 #3): each stage is an actor
    holding its own jitted block; microbatches stream through the
    channel-compiled DAG, stage k+1 of microbatch i overlapping stage k
    of microbatch i+1. Validated against a single-process forward."""
    import numpy as np

    import ray_tpu as rt
    from ray_tpu.dag import InputNode
    from ray_tpu.dag.channel_exec import ChannelCompiledDAG

    @rt.remote
    class StageActor:
        def __init__(self, seed, dim):
            import jax
            jax.config.update("jax_platforms", "cpu")
            import jax.numpy as jnp

            k = jax.random.PRNGKey(seed)
            self.w = jax.random.normal(k, (dim, dim), jnp.float32) / dim
            self.fwd = jax.jit(lambda w, x: jnp.tanh(x @ w))

        def apply(self, x):
            import numpy as np

            return np.asarray(self.fwd(self.w, x))

        def weights(self):
            import numpy as np

            return np.asarray(self.w)

    dim = 32
    s1, s2 = StageActor.remote(0, dim), StageActor.remote(1, dim)
    # fetch reference weights BEFORE compiling: once the DAG loops start,
    # the actors' ordered queues are dedicated to the DAG (aDAG semantics)
    w1 = rt.get(s1.weights.remote())
    w2 = rt.get(s2.weights.remote())
    with InputNode() as inp:
        out = s2.apply.bind(s1.apply.bind(inp))
    dag = out.experimental_compile(channels=True)
    assert isinstance(dag, ChannelCompiledDAG)
    try:
        rng = np.random.RandomState(0)
        micro = [rng.randn(4, dim).astype("float32") for _ in range(6)]
        refs = [dag.execute(m) for m in micro]       # all in flight
        outs = [r.get(timeout=120) for r in refs]
        for m, o in zip(micro, outs):
            expect = np.tanh(np.tanh(m @ w1) @ w2)
            np.testing.assert_allclose(o, expect, rtol=1e-5, atol=1e-5)
    finally:
        dag.teardown()
