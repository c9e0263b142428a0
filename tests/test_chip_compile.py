"""The main path's kernels and decode step, held to the chip's compiler.

The TPU compiler is installed where the tests run and compiles for a chip
that is described, not attached (on-chip-measurement guide §2). Interpret
mode, which every other kernel test uses, cannot see what it refuses:
a slice off the tiling, too much VMEM, a program that does not fit HBM.
Nothing runs here, so these say nothing about results or times; each
compile takes a second or two. Skipped where the topology cannot be
described.
"""

import functools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import granite_hybrid, llama
from ray_tpu.ops import attention
from ray_tpu.ops.pallas import decode_attention as da
from ray_tpu.ops.pallas import flash_attention as fa

HBM_BYTES = 16 * 2**30  # one v5e chip

# (batch, seq, heads, kv_heads, head_dim) of the attention call in a
# train step of each preset, at the batch the chip has run
SHAPES = {"1b": (4, 2048, 16, 8, 128), "410m": (8, 2048, 16, 16, 64)}


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip. The persistent compile cache is off around
    these compiles: an executable built for a described device is written
    to it but cannot be read back without the chip, and warns."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it cannot describe a v5e
            pytest.skip(f"cannot describe a v5e topology here: {e!r}")
        yield topo.devices[0]
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture
def compiled_not_interpreted(monkeypatch):
    """The tests run with JAX_PLATFORMS=cpu, which is what selects
    interpret mode; these compile the kernel itself."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)


@pytest.fixture
def on_the_chip(monkeypatch, compiled_not_interpreted):
    """What `cached_attention` selects by on a TPU: the decode kernel
    where the shape allows it, compiled. (`jax.devices()` here is the
    CPU's, whatever the program is compiled for.)"""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)


def _flash_fwd(q, k, v, o, lse, do, bq, bk):
    return fa._flash_forward(q, k, v, causal=True, scale=None,
                             block_q=bq, block_k=bk)


def _flash_bwd(q, k, v, o, lse, do, bq, bk):
    return fa._flash_backward(q, k, v, o, lse, do, causal=True, scale=None,
                              block_q=bq, block_k=bk)


# the two train cells' attention a chip: InternLM2-1.8B on one, and a
# tensor shard of Mistral-7B on four (n_rep 4)
SHAPES["lora-ft"] = SHAPES["1b"]
SHAPES["lora-ft-4chip"] = (4, 2048, 16, 4, 128)


@pytest.mark.parametrize("kernel,preset,block_q,block_k,budget", [
    (_flash_fwd, "lora-ft", None, None, None),     # the caller's tile
    (_flash_fwd, "1b", 512, 512, None),
    (_flash_fwd, "1b", 256, 1024, None),   # rectangular: no strips
    (_flash_fwd, "410m", 512, 512, None),
    (_flash_bwd, "lora-ft", None, None, None),
    (_flash_bwd, "lora-ft-4chip", None, None, None),
    (_flash_bwd, "1b", 512, 512, None),
    (_flash_bwd, "1b", 256, 1024, None),
    (_flash_bwd, "410m", 512, 512, None),
    (_flash_bwd, "lora-ft-4chip", 1024, 1024, None),
    (_flash_bwd, "lora-ft", None, None, 0),        # dq does not fit: split
], ids=lambda v: getattr(v, "__name__", None) or str(v))
def test_flash_kernel_compiles_for_the_chip(chip, compiled_not_interpreted,
                                            monkeypatch, kernel, preset,
                                            block_q, block_k, budget):
    """One Mosaic call forward, one backward where dq's scratch fits
    (fused: every shape here) and two where it does not, and no
    gradient per query head in float32 left for XLA to sum."""
    b, s, h, hk, d = SHAPES[preset]
    on = SingleDeviceSharding(chip)
    if budget is not None:
        monkeypatch.setattr(fa, "_DQ_VMEM_BYTES", budget)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    q, o, do = (arg((b, s, h, d)) for _ in range(3))
    k, v = (arg((b, s, hk, d)) for _ in range(2))
    lse = arg((b, h, s), jnp.float32)
    text = jax.jit(
        lambda *a: kernel(*a, block_q, block_k)).lower(
            q, k, v, o, lse, do).compile().as_text()
    fused = fa.backward_path(s, d, h // hk, jnp.bfloat16) == "fused"
    assert fused == (budget is None)
    assert text.count("tpu_custom_call") == (
        1 if kernel is _flash_fwd or fused else 2)
    assert f"f32[{b},{h},{s},{d}]" not in text


@pytest.mark.parametrize("layers,batch,kv_heads,group,head_dim,writes", [
    (24, 8, 8, 2, 128, False),  # InternLM2-1.8B, the chat cell's 8 slots
    (4, 32, 8, 4, 64, False),   # granite-4.0-h-micro's attention layers
    (24, 8, 8, 2, 128, True),   # the chat cell's, writing the new rows
], ids=["internlm2-8x4096", "granite-32x4096", "internlm2-8x4096-writes"])
def test_decode_kernel_compiles_for_the_chip(chip, on_the_chip, layers,
                                             batch, kv_heads, group,
                                             head_dim, writes):
    """The decode kernel alone at the serve cells' shapes, in the blocks
    `decode_block_len` gives them: it fits VMEM at both and takes the
    stacks as they lie (temporaries under a MiB). With head_dim 64 that
    is V in K's order: the compiler holds such a V with positions minor,
    and re-lays the whole stack out (1.07 GB here) for a kernel that
    takes it as declared. Handed the step's new K and V, the kernel
    returns the stacks in the buffers they came in (PR 48)."""
    max_len = 4096
    on = SingleDeviceSharding(chip)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    stacks = (arg((layers, batch, kv_heads, head_dim, max_len)),
              arg((layers, batch, kv_heads, max_len, head_dim)))
    args = (arg((batch, kv_heads, group, head_dim)), *stacks,
            arg((), jnp.int32), arg((batch,), jnp.int32),
            arg((batch,), jnp.int32))
    new = (arg((batch, kv_heads, head_dim)),) * 2 if writes else ()
    block = attention.decode_block_len(kv_heads, head_dim, max_len,
                                       jnp.bfloat16, jax.sharding.Mesh(
                                           [chip], ("tensor",)))
    assert block == 2 ** 20 // (kv_heads * head_dim * 2)
    compiled = jax.jit(lambda *a: attention.decode_attention(
        *a[:6], scale=head_dim ** -0.5, block_len=block,
        new_kv=a[6:] or None), donate_argnums=(1, 2) if writes else ()
        ).lower(*args, *new).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 20
    if writes:
        assert mem.alias_size_in_bytes == sum(
            2 * math.prod(a.shape) for a in stacks)
    if head_dim < 128:
        as_declared = jax.jit(lambda *a: da.decode_attention(
            *a, scale=head_dim ** -0.5, block_len=block)).lower(
                *args).compile()
        assert as_declared.memory_analysis().temp_size_in_bytes >= (
            2 * layers * batch * kv_heads * max_len * head_dim)


def _llama_on(chip, cfg):
    """(place, params): `place` puts a tree of shapes on the described
    chip; `params` are llama's for `cfg`, placed."""
    on = SingleDeviceSharding(chip)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=on), tree)

    return place, place(jax.eval_shape(
        lambda key: llama.init_params(cfg, key), jax.random.PRNGKey(0)))


def _slots_cache(cfg, batch, max_len, per_row=True):
    cache = dict(jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, batch, max_len)))
    if per_row:
        cache["length"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return cache


def _compiled_decode_step(chip, cfg, batch, max_len, s, per_row):
    """llama.decode_step for `batch` rows x `s` tokens against a cache
    `max_len` deep, cache donated, compiled for the described chip.
    Returns (compiled, the cache's shapes)."""
    place, params = _llama_on(chip, cfg)
    cache = _slots_cache(cfg, batch, max_len, per_row)
    tokens = place(jax.ShapeDtypeStruct((batch, s), jnp.int32))
    compiled = jax.jit(
        lambda p, c, t: llama.decode_step(p, c, t, cfg),
        donate_argnums=(1,)).lower(params, place(cache), tokens).compile()
    return compiled, cache


def _compiled_mixed_step(chip, cfg, batch, max_len, s, bucket):
    """llama.mixed_step for a chunk of `s` tokens against a prefill
    cache `bucket` deep and one token for each of `batch` slots `max_len`
    deep (per-row depths), both caches donated, compiled for the
    described chip. Returns (compiled, the slots' cache's shapes, the
    prefill cache's shapes)."""
    place, params = _llama_on(chip, cfg)
    cache = _slots_cache(cfg, batch, max_len)
    small = _slots_cache(cfg, 1, bucket, per_row=False)
    tokens = lambda b, n: place(jax.ShapeDtypeStruct((b, n), jnp.int32))
    compiled = jax.jit(
        lambda p, sm, ch, c, t: llama.mixed_step(p, sm, ch, c, t, cfg),
        donate_argnums=(1, 3)).lower(
            params, place(small), tokens(1, s), place(cache),
            tokens(batch, 1)).compile()
    return compiled, cache, small


def test_1b_decode_step_compiles_for_the_chip(chip):
    """The serving engine's steady-state program: one token for each of 4
    slots against a 2048-deep cache with per-row depths."""
    cfg = llama.config_for("1b", max_seq_len=2048)
    compiled, _ = _compiled_decode_step(chip, cfg, 4, 2048, 1, True)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < HBM_BYTES)


# InternLM2-1.8B widths, as the benchmark's serve cells hold them (bf16)
_SERVE_CFG = dict(vocab_size=92544, dim=2048, n_layers=24, n_heads=16,
                  n_kv_heads=8, hidden_dim=8192, rope_theta=1e6,
                  dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
# result of an HLO instruction: "%name = dtype[dims]{layout} opcode("
_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = ([a-z0-9]+)\[([0-9,]*)\][^ ]* ([\w\-]+)\(")


def _mosaic_calls(text, name=None):
    """The program's Mosaic calls; with `name`, those whose instruction
    carries it (a call is named by its innermost `jax.named_scope`)."""
    return [line for line in text.splitlines()
            if "tpu_custom_call" in line and " custom-call(" in line
            and (name is None or re.match(
                rf"\s*(?:ROOT )?%?{name}[.\d]* = ", line))]


@pytest.mark.parametrize("batch,max_len,s,per_row,kernels,mixed_bucket", [
    (8, 4096, 1, True, 0, None),    # the decode step, 8 slots: XLA path
    (1, 3584, 256, False, 0, None),  # one chunk of a long prompt's prefill
    (8, 4096, 1, True, 1, None),    # the decode step as a TPU runs it
    (1, 3584, 256, False, 0, None),  # the chunk as a TPU runs it: no kernel
    (8, 4096, 256, True, 1, 3584),  # the chunk carrying the 8 rows (PR 57)
    (8, 4096, 256, True, 1, 2048),  # ... at the cell's other bucket
], ids=["decode-8x4096", "chunk-1x3584-s256", "decode-8x4096-on-chip",
        "chunk-1x3584-s256-on-chip", "mixed-s256@3584+8x4096-on-chip",
        "mixed-s256@2048+8x4096-on-chip"])
def test_decode_step_moves_no_cache(chip, request, batch, max_len, s,
                                    per_row, kernels, mixed_bucket):
    """Per step every cache byte is read at most once, by attention, and
    only the new rows are written (PERF.md, PR 25): with the cache
    donated the step's temporaries hold less than ONE layer of it (seed:
    3.63 GB for the decode shape, two whole copies among them), the cache
    is updated in its own buffers, nothing but the in-place row writes
    produces an array of a whole stack's shape, and no array of positions
    x head_dim is as large as a layer's K repeated over its group's query
    heads. On the chip the decode step's attention is the decode kernel
    (PR 31), which takes the stacks as they lie and, since PR 48, leaves
    the new rows written in them: one Mosaic call in the layer loop,
    whose results are the stacks, and NO `dynamic-update-slice` of a
    stack's shape in that program. A chunk keeps the XLA path and its
    writes. The mixed program (`llama.mixed_step`, PR 57: a chunk of `s`
    in a cache `mixed_bucket` deep beside the slots) is held to both: the slots' stacks as the
    decode step's, through its one Mosaic call a layer; the request's
    stacks as the chunk's, with no copy of either (left free, the
    compiler re-lays the request's V stack around the layer loop, 176
    MB each way: `mixed_step` holds it as it lies)."""
    if request.node.callspec.id.endswith("on-chip"):
        request.getfixturevalue("on_the_chip")
    cfg = llama.LlamaConfig(max_seq_len=max_len, **_SERVE_CFG)
    chunk_stacks = set()
    if mixed_bucket is None:
        compiled, cache = _compiled_decode_step(chip, cfg, batch, max_len, s,
                                                per_row)
        own_bytes = 2 * cfg.n_layers * 2 * math.prod(cache["k"].shape[1:])
    else:
        compiled, cache, small = _compiled_mixed_step(
            chip, cfg, batch, max_len, s, mixed_bucket)
        chunk_stacks = {tuple(small[key].shape) for key in ("k", "v")}
        own_bytes = 2 * cfg.n_layers * 2 * (
            math.prod(cache["k"].shape[1:]) + math.prod(small["k"].shape[1:]))
    assert compiled.as_text().count("tpu_custom_call") == kernels

    stacks = {tuple(cache[key].shape) for key in ("k", "v")}
    layer_bytes = 2 * math.prod(cache["k"].shape[1:])
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < min(0.5e9, 2 * layer_bytes)
    assert mem.alias_size_in_bytes >= own_bytes
    repeated = batch * max_len * cfg.n_heads * cfg.head_dim
    may_give_a_stack = ("parameter", "get-tuple-element") + (
        () if kernels else ("dynamic-update-slice",))
    for line in compiled.as_text().splitlines():
        m = _RESULT.match(line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if dims in stacks:
            assert m.group(3) in may_give_a_stack, line[:200]
        elif dims in chunk_stacks:
            assert m.group(3) in ("parameter", "get-tuple-element",
                                  "dynamic-update-slice"), line[:200]
        elif max_len in dims and cfg.head_dim in dims:
            assert math.prod(dims) < repeated, line[:200]
    if kernels:
        # the kernel's results: the attention's rows and both stacks
        call, = (line for line in compiled.as_text().splitlines()
                 if "tpu_custom_call" in line and " custom-call(" in line)
        for dims in stacks:
            assert "[" + ",".join(map(str, dims)) + "]" in call.split(
                " custom-call(")[0], call[:300]


def _computations(text):
    """{name: [instruction lines]} of a compiled module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _weight_moves(compiled, params):
    """Instructions outside every fusion whose result is ONE layer of a
    stacked matrix of `params["layers"]` (`[d, out]` or `[1, d, out]`)
    and that do no arithmetic: a `copy`, a bare `dynamic-slice`, or a
    fusion with no work in it but the `dynamic-slice`. Returns
    [(kind, leaf, bytes)], the leaf read off the stack the slice cuts
    (through the loop's tuple back to the entry parameter's name)."""
    shapes = {}
    for leaf in params["layers"].values():
        if len(leaf.shape) == 3:
            shapes[tuple(leaf.shape[1:])] = leaf.dtype.itemsize
            shapes[(1,) + tuple(leaf.shape[1:])] = leaf.dtype.itemsize
    comps = _computations(compiled.as_text())
    fused = {name for name in comps if "fused_computation" in name}
    entry = [line for name, lines in comps.items() if name.startswith("main")
             for line in lines]

    def work(line):
        called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
        ops = (_RESULT.match(body).group(3) for body in comps[called]
               if _RESULT.match(body))
        return {op for op in ops
                if op not in ("parameter", "constant", "bitcast")}

    def define(lines, name):
        return next((d for d in lines if re.match(
            rf"\s*(?:ROOT )?%?{re.escape(name)} = ", d)), "")

    def operands(line):
        return re.findall(r"%([\w.\-]+)", line[line.index(" = "):].split(
            "(", 1)[1].split("), ")[0])

    def leaf_of(lines, line):
        """The stacked parameter a slice (or the copy of one) cuts: an
        element of the layer loop's carry, which the entry computation
        fills from its parameters."""
        src = define(lines, operands(line)[0])
        while src and " get-tuple-element(" not in src:
            src = define(lines, operands(src)[0])
        index = int(re.search(r"index=(\d+)", src).group(1)) if src else -1
        for loop in (d for d in entry if " while(" in d):
            carried = operands(define(entry, operands(loop)[0]))
            stack = define(entry, carried[index]) if (
                0 <= index < len(carried)) else ""
            if " parameter(" in stack and "layers" in stack:
                return re.findall(r"\w+", re.search(
                    r'op_name="(.*?)"', stack).group(1))[-1]
        return "?"

    found = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = _RESULT.match(line)
            if not m:
                continue
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            if dims not in shapes:
                continue
            op = m.group(3)
            if op == "copy" or op == "dynamic-slice" or (
                    op == "fusion" and work(line) == {"dynamic-slice"}):
                kind = "copy" if op == "copy" else "slice"
                found.append((kind, leaf_of(lines, line),
                              math.prod(dims) * shapes[dims]))
    return found


@pytest.mark.parametrize("batch,max_len,s,per_row,left,mixed_bucket", [
    (8, 4096, 1, True, [], None),
    (8, 4096, 1, True, [], None),
    (1, 3584, 256, False, [("copy", "wv", 4 * 2 ** 20),
                           ("slice", "wv", 4 * 2 ** 20)], None),
    (8, 4096, 256, True, [], 3584),
    (8, 4096, 256, True, [], 2048),
], ids=["decode-8x4096", "decode-8x4096-on-chip",
        "chunk-1x3584-s256-on-chip", "mixed-s256@3584+8x4096-on-chip",
        "mixed-s256@2048+8x4096-on-chip"])
def test_serve_step_reads_weights_where_they_lie(chip, request, batch,
                                                 max_len, s, per_row, left,
                                                 mixed_bucket):
    """Every matrix of a layer is read from its stack by the product
    that uses it (PERF.md, PR 45): in the serve cells' two programs no
    instruction outside a fusion gives one layer of a stacked weight as
    the result of a `copy`, or of a fusion whose only work is the
    `dynamic-slice`. The seed's count (sandbox compile, PR 45, the tree
    of PR 43): the decode step, on either attention path, 2 slices + 2
    copies a layer, wq `bf16[1,2048,2048]` and wk `bf16[1,2048,1024]`,
    12 MB cut out and 12 MB re-laid (the products wanted q and k heads
    major and took the matrices transposed for it); the chunk 3 + 3, wv
    too, 16 MB. Since PR 45 q and k are held as projected
    (`jax.lax.optimization_barrier` in `_decode_block`) and the decode
    step has none. ONE exception is left, named here with its bytes:
    the chunk's wv, 1 slice + 1 copy of `bf16[1,2048,1024]`, 4 MiB
    each a layer. Held like q and k, v makes the compiler re-lay the
    whole V stack `bf16[24,1,8,3584,128]` (176 MB) into and out of the
    layer loop, which `test_decode_step_moves_no_cache` forbids; so does
    writing V a kv head at a time (PR 45: forms tried, CHANGES.md). The
    mixed program (PR 57; the chunk in a cache `mixed_bucket` deep), whose
    products run over the chunk's rows and the slots' together, has
    none, wv's included: v comes of one product for both, and the
    request's V stack is held as it lies."""
    if request.node.callspec.id.endswith("on-chip"):
        request.getfixturevalue("on_the_chip")
    cfg = llama.LlamaConfig(max_seq_len=max_len, **_SERVE_CFG)
    if mixed_bucket is None:
        compiled, _ = _compiled_decode_step(chip, cfg, batch, max_len, s,
                                            per_row)
    else:
        compiled, _, _ = _compiled_mixed_step(chip, cfg, batch, max_len, s,
                                              mixed_bucket)
    params = jax.eval_shape(lambda key: llama.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    assert sorted(_weight_moves(compiled, params)) == left


# granite-4.0-h-micro, whole, as the benchmark's serve cell holds it
_HYBRID_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@pytest.mark.parametrize("batch,max_len,s,per_row", [
    (32, 4096, 1, True),      # the engine's decode step, 32 slots
    (1, 1024, 256, False),    # one chunk of a prompt's prefill
    (32, 4096, 1, True),      # the decode step as a TPU runs it
], ids=["decode-32x4096", "chunk-1x1024-s256", "decode-32x4096-on-chip"])
def test_hybrid_decode_step_moves_no_cache_and_no_state(chip, request, batch,
                                                        max_len, s, per_row):
    """granite_hybrid.decode_step under the rule llama's is held to: with
    the cache donated, K, V, the recurrent state (2.4 GB for 32 slots)
    and the convolution tails are updated in their own buffers, the
    step's temporaries hold less than ONE layer's state for 32 slots (a
    chunk's activations, 10 MB, need no more either), and nothing but
    the in-place writes produces an array of a whole stack's shape
    (stacks under 16 MB aside: the compiler re-lays a batch-1 tail
    stack, 0.9 MB, out for the chunk's convolution).
    (With the input projection held fused, [36, 2048, 8512], the
    compiler copied that whole stack, 1.25 GB, on every step: PERF.md,
    PR 28.) On the chip the attention layers' decode is the decode
    kernel (PR 31), given V in the order the compiler holds it in: the
    same rule, with a Mosaic call in the period's loop. Since PR 39 the
    state update there is a kernel too (ops/pallas/ssm_update.py), the
    first of this repo that writes its operand in place: its result has
    the stack's shape, which is admitted only where that result is
    aliased to the call's operand, and no copy of the state stack may
    exist anywhere, inside a fusion either (a compiler that did not
    honour the alias in the period's loop would copy 2.4 GB around every
    call: the temporaries would hold a stack and the aliased bytes lose
    one)."""
    if request.node.callspec.id.endswith("on-chip"):
        request.getfixturevalue("on_the_chip")
    cfg = granite_hybrid.GraniteHybridConfig(
        layer_types=_HYBRID_PERIOD * 4, max_seq_len=max_len)
    on = SingleDeviceSharding(chip)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=on), tree)

    params = place(jax.eval_shape(
        lambda key: granite_hybrid.init_params(cfg, key),
        jax.random.PRNGKey(0)))
    cache = dict(jax.eval_shape(
        lambda: granite_hybrid.init_cache(cfg, batch, max_len)))
    if per_row:
        cache["length"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    tokens = jax.ShapeDtypeStruct((batch, s), jnp.int32, sharding=on)
    compiled = jax.jit(
        lambda p, c, t: granite_hybrid.decode_step(p, c, t, cfg),
        donate_argnums=(1,)).lower(params, place(cache), tokens).compile()

    on_chip = request.node.callspec.id.endswith("on-chip")
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == on_chip
    state = ",".join(map(str, cache["state"].shape))
    # the update's calls: one for each run of Mamba layers in the period
    updates = [line for line in text.splitlines()
               if f"= (f32[{state}]" in line and " custom-call(" in line]
    mamba_runs = sum(1 for kind, _, _ in cfg.runs if kind == "mamba")
    assert len(updates) == (mamba_runs if on_chip and s == 1 else 0)
    for line in updates:
        assert "output_to_operand_aliasing={{0}: (1, {})}" in line, \
            line[:300]
        assert re.match(r"\s*%?ssm_update[.\d]* = ", line), line[:100]
    assert not re.search(rf"f32\[{state}\]\S* copy\(", text)
    rows = ("k", "v", "state", "conv")
    nbytes = {key: math.prod(cache[key].shape) * cache[key].dtype.itemsize
              for key in rows}
    stacks = {tuple(cache[key].shape) for key in rows
              if nbytes[key] > 16e6}
    stack_bytes = sum(nbytes.values())
    layer_state = 32 * 4 * math.prod(cache["state"].shape[2:])
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < HBM_BYTES)
    assert mem.temp_size_in_bytes < layer_state
    assert mem.alias_size_in_bytes >= stack_bytes
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if dims in stacks and m.group(3) != "fusion":
            assert m.group(3) in ("parameter", "get-tuple-element",
                                  "dynamic-update-slice", "bitcast"), \
                line[:200]


@pytest.mark.parametrize("layers,batch,heads,p,n,block", [
    (36, 32, 64, 64, 128, 64),    # granite-4.0-h-micro, the cell's 32 slots
    (18, 8, 48, 64, 128, 48),     # another head count: one block of 1.5 MiB
    (4, 16, 128, 64, 128, 64),    # granite-4.0-h-small's: two blocks a row
    (2, 4, 24, 128, 256, 8),      # another p and n: three blocks of 1 MiB
], ids=["granite-micro-32", "48-heads", "128-heads", "p128-n256"])
def test_ssm_update_kernel_compiles_for_the_chip(
        chip, compiled_not_interpreted, layers, batch, heads, p, n, block):
    """ops/pallas/ssm_update.py alone, in the blocks `heads_per_block`
    gives each shape: it fits VMEM unasked, takes the stack as it lies
    and writes it in place: with the stack donated nothing is held
    beside it (temporaries under one block) and its bytes are aliased,
    also when the layer index is a loop's counter."""
    from ray_tpu.ops.pallas import ssm_update as su

    on = SingleDeviceSharding(chip)

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=on)

    assert su.heads_per_block(heads, p, n, jnp.float32) == block

    def every_layer(states, x, dt, a, b, c, d):
        def one(li, carry):
            states, y = carry
            return su.ssm_update(states, li, x + y, dt, a, b, c, d,
                                 heads_block=block)[::-1]
        return jax.lax.fori_loop(0, layers, one, (states, x))

    compiled = jax.jit(every_layer, donate_argnums=(0,)).lower(
        arg(layers, batch, heads, p, n), arg(batch, heads, p),
        arg(batch, heads), arg(heads), arg(batch, n), arg(batch, n),
        arg(heads)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "output_to_operand_aliasing={{0}: (1, {})}" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < block * p * n * 4
    assert mem.alias_size_in_bytes >= layers * batch * heads * p * n * 4


def _dots3_cell_config(max_len):
    from ray_tpu.models import dots3_note

    return dots3_note.Dots3NoteConfig(vocab_size=19008, experts_held=32,
                                      max_seq_len=max_len)


def test_dots3_decode_step_moves_no_cache(chip, compiled_not_interpreted):
    """models/dots3_note.decode_step at the benchmark's 32 slots x 24,576
    under the rule the other two models' steps are held to: with the
    cache donated, the latent rows (2.0 GB), the index keys and the rings
    are updated in their own buffers, the step's temporaries stay under a
    quarter of one layer's latent rows (the index scores of 32 x 64 heads
    x 24,576 in float32 are the largest, 0.2 GB), and nothing but the
    in-place writes produces an array of a whole stack's or a whole
    layer's shape. Found by this compile (PR 32): a cached row of 576
    numbers, no multiple of the 128 lanes, made the compiler hold the
    stack with positions minor and copy all of it around every step's
    write (1.8 GB twice), so rows are filled to 640; `latent[li]` before
    the gather of the selected rows was a copy of the layer (1 GB), so
    the gather indexes the stack."""
    from ray_tpu.models import dots3_note

    batch, max_len = 32, 24576
    cfg = _dots3_cell_config(max_len)
    on = SingleDeviceSharding(chip)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=on), tree)

    params = place(jax.eval_shape(
        lambda key: dots3_note.init_params(cfg, key), jax.random.PRNGKey(0)))
    cache = dict(jax.eval_shape(
        lambda: dots3_note.init_cache(cfg, batch, max_len)))
    cache["length"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=on)
    compiled = jax.jit(
        lambda p, c, t: dots3_note.decode_step(p, c, t, cfg),
        donate_argnums=(1,)).lower(params, place(cache), tokens).compile()

    rows = ("latent", "index", "window")
    nbytes = {key: math.prod(cache[key].shape) * cache[key].dtype.itemsize
              for key in rows}
    assert nbytes["latent"] == 2 * 32 * 24576 * 640 * 2
    whole = {tuple(cache[key].shape) for key in rows} | {
        tuple(cache[key].shape[1:]) for key in ("latent", "index")}
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < HBM_BYTES)
    assert mem.temp_size_in_bytes < nbytes["latent"] / 2 / 4
    assert mem.alias_size_in_bytes >= sum(nbytes.values())
    for line in compiled.as_text().splitlines():
        m = _RESULT.match(line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if dims in whole and m.group(3) != "fusion":
            assert m.group(3) in ("parameter", "get-tuple-element",
                                  "dynamic-update-slice", "bitcast"), \
                line[:200]


@pytest.mark.parametrize("kind,keys,layers", [
    ("full_attention", 20480, 2), ("sliding_attention", 640 + 1024, 1),
], ids=["full-20480", "sliding-ring+chunk"])
def test_latent_attention_kernel_compiles_for_the_chip(
        chip, compiled_not_interpreted, kind, keys, layers):
    """ops/pallas/latent_attention.py alone at the dots3 cell's two layer
    kinds, a chunk of 1,024 queries: 128 heads of 128 + 64 against the
    deepest bucket's latent rows, 64 heads of 192 + 64 against the ring
    and the chunk. It fits VMEM in the tiles `tiles` gives, and takes
    the stacked rows as they lie: its temporaries are the mask as int8,
    the two up-projections with the head first and, in this program
    alone, q and the mask copied into the layouts the call takes them
    in: together a quarter of ONE block's scores in float32."""
    from ray_tpu.ops.pallas import latent_attention as la

    a = _dots3_cell_config(24576).attn(kind)
    s = 1024
    t = la.tiles(a.heads, a.nope, a.rope, a.v, a.kv_rank, s, keys)
    assert t == (8, 512, 1024 if keys > 2048 else keys)
    on = SingleDeviceSharding(chip)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    compiled = jax.jit(lambda q, rows, li, w_k, w_v, mask: la.latent_attention(
        q, rows, li, w_k, w_v, mask, kv_rank=a.kv_rank, rope=a.rope, t=t)
    ).lower(arg((1, a.heads, s, a.nope + a.rope)),
            arg((layers, 1, keys, a.row)), arg((), jnp.int32),
            arg((a.kv_rank, a.heads, a.nope)), arg((a.kv_rank, a.heads, a.v)),
            arg((1, s, keys), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27


def test_dots3_chunk_holds_no_block_of_scores(chip, on_the_chip):
    """The dots3 cell's chunk program, `decode_step` of 1,024 tokens with
    a scalar length against a batch-1 cache of the deepest bucket, as a
    TPU takes it: five calls of the latent attention kernel (two full
    layers, three sliding), not interpreted; it fits HBM beside nothing,
    its temporaries stay under 0.5 GB (the plain form's were 1.6: a
    block's scores, twice), and no float32 array of heads x 1,024
    queries x a block of keys or more exists anywhere in it: in the
    plain form f32[1,128,1024,1024], 0.54 GB written after the score
    product and read back twice, and f32[1,64,1024,1664] in a sliding
    layer."""
    from ray_tpu.models import dots3_note

    depth, s = 20480, 1024
    cfg = _dots3_cell_config(24576)
    on = SingleDeviceSharding(chip)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=on), tree)

    params = place(jax.eval_shape(
        lambda key: dots3_note.init_params(cfg, key), jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: dots3_note.init_cache(cfg, 1, depth)))
    tokens = jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=on)
    compiled = jax.jit(
        lambda p, c, t: dots3_note.decode_step(p, c, t, cfg),
        donate_argnums=(1,)).lower(params, cache, tokens).compile()
    text = compiled.as_text()
    # one attention kernel a layer, one expert kernel an expert layer
    assert len(_mosaic_calls(text, "held_experts")) == cfg.n_layers - 1
    assert len(_mosaic_calls(text)) == 2 * cfg.n_layers - 1
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < HBM_BYTES)
    assert mem.temp_size_in_bytes < 0.5e9
    # the smaller of the two; the largest float32 array left is the
    # indexer's, 64 heads x 1,024 queries x a block of 1,024 keys
    scores = cfg.swa_n_heads * s * (cfg.ring_len + s)
    for dims in set(re.findall(r"f32\[([0-9,]+)\]", text)):
        assert math.prod(int(d) for d in dims.split(",")) < scores, dims


@pytest.mark.parametrize("batch,s,max_len", [
    (16, 1, 32768),       # the engine's decode step, the cell's 16 slots
    (1, 1024, 24576),     # one chunk of the deepest bucket's prefill
], ids=["decode-16x32768-on-chip", "chunk-1x1024@24576"])
def test_evabyte_step_moves_no_cache(chip, on_the_chip, batch, s, max_len):
    """models/evabyte.py at the EvaByte cell's sizes (8 layers) under
    llama's rule: with the cache donated the window's rows and the
    summaries are written where they lie, and nothing produces an array
    of a whole stack's shape but the in-place writes. The decode step is
    the decode kernel the models share, given each row's one range over
    both parts of the leaf (32 KV heads of 128, group 1), and holds
    under 16 MB beside its arguments; since PR 53 its fold of a closed
    chunk is a loop inside the layer loop over the rows that close one
    on this step, both stacks carried through it where they lie, and
    nothing cuts a chunk's 16 rows out of a stack outside that loop; a
    chunk reads its layer once and writes it once, and since PR 59 its
    attention is two calls a layer of ops/pallas/gqa_chunk_attention.py
    (the leaf as found, read where it lies in the stack; the chunk's own
    rows and the summaries it makes), so that no float32 array of a
    layer's scores (32 heads x 1,024 queries x 3,072 keys, 403 MB) is
    left and the temporaries are an eighth of the 696 MB they were."""
    from ray_tpu.models import evabyte

    cfg = evabyte.EvaByteConfig(n_layers=8)
    on = SingleDeviceSharding(chip)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=on), tree)

    params = place(jax.eval_shape(
        lambda key: evabyte.init_params(cfg, key), jax.random.PRNGKey(0)))
    cache = dict(jax.eval_shape(
        lambda: evabyte.init_cache(cfg, batch, max_len)))
    if s == 1:
        cache["length"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    tokens = jax.ShapeDtypeStruct((batch, s), jnp.int32, sharding=on)
    compiled = jax.jit(
        lambda p, c, t: evabyte.decode_step(p, c, t, cfg),
        donate_argnums=(1,)).lower(params, place(cache), tokens).compile()
    text = compiled.as_text()
    # in the layer loop: the decode kernel; a chunk's two calls
    assert text.count("tpu_custom_call") == (1 if s == 1 else 2)
    stacks = {tuple(cache[key].shape) for key in ("k", "v")}
    stack_bytes = sum(math.prod(cache[key].shape) * 2 for key in ("k", "v"))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert mem.temp_size_in_bytes < (16e6 if s == 1 else 0.25e9)
    scores = cfg.n_heads * s * (cfg.window_size + s)
    for dims in set(re.findall(r"f32\[([0-9,]+)\]", text)):
        assert s == 1 or math.prod(
            int(d) for d in dims.split(",")) < scores, dims
    assert mem.alias_size_in_bytes >= stack_bytes
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if dims in stacks and m.group(3) != "fusion":
            assert m.group(3) in ("parameter", "get-tuple-element",
                                  "dynamic-update-slice", "bitcast",
                                  "custom-call"), line[:200]
    if s > 1:
        return
    # the layer loop and, inside it, the fold's: the stacks are carried
    # through both and still aliased to the arguments (above), and a
    # chunk's rows [.., H, hd, c] / [.., H, c, hd] are cut out, or
    # copied, under `eva_summarise`'s loop alone
    assert len(re.findall(r" while\(", text)) == 2
    rows = {(cfg.n_heads, cfg.head_dim, cfg.chunk_size),
            (cfg.n_heads, cfg.chunk_size, cfg.head_dim)}
    cut = 0
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m or m.group(3) not in ("copy", "dynamic-slice"):
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if tuple(d for d in dims if d != 1) in rows:
            assert "/eva_summarise/while/body/" in line, line[:300]
            cut += m.group(3) == "dynamic-slice"
    assert cut >= 2


@functools.cache
def _kimi_k2_step(chip, batch, s, max_len):
    """(config, cache shapes, compiled `decode_step`) of models/kimi_k2.py
    at the Kimi-K2.6 cell's sizes (5 layers, 12 of 384 experts, an eighth
    of the vocabulary), the cache donated; compiled once a shape (a
    chunk's program takes 15 s), under the `on_the_chip` fixture of
    whichever test asks first."""
    from ray_tpu.models import kimi_k2

    cfg = kimi_k2.KimiK2Config(vocab_size=20480, n_layers=5,
                               experts_held=12, max_seq_len=24576)
    on = SingleDeviceSharding(chip)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=on), tree)

    params = place(jax.eval_shape(
        lambda key: kimi_k2.init_params(cfg, key), jax.random.PRNGKey(0)))
    cache = dict(jax.eval_shape(
        lambda: kimi_k2.init_cache(cfg, batch, max_len)))
    if s == 1:
        cache["length"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    tokens = jax.ShapeDtypeStruct((batch, s), jnp.int32, sharding=on)
    compiled = jax.jit(
        lambda p, c, t: kimi_k2.decode_step(p, c, t, cfg),
        donate_argnums=(1,)).lower(params, place(cache), tokens).compile()
    return cfg, cache, compiled


@pytest.mark.parametrize("batch,s,max_len", [
    (32, 1, 24576),       # the engine's decode step, the cell's 32 slots
    (1, 1024, 23552),     # one chunk of the deepest bucket's prefill
], ids=["decode-32x24576-on-chip", "chunk-1x1024@23552"])
def test_kimi_k2_step_reads_live_blocks_and_moves_no_cache(
        chip, on_the_chip, batch, s, max_len):
    """models/kimi_k2.py at the Kimi-K2.6 cell's sizes under the rule the
    other models' steps are held to: with the cache donated the latent
    rows (5.03 GB for the slots) are written where they lie, weights,
    cache and temporaries fit 15.75 GB, and nothing but the in-place
    writes and the kernels' operands has a whole stack's or a whole
    layer's shape: the decode step reads the leaf through
    ops/pallas/latent_decode_attention.py (one call a layer, the stack
    its operand as it lies, blocks of 1,024 positions chosen in its
    index map), and a chunk through ops/pallas/latent_attention.py."""
    from ray_tpu.models import kimi_k2

    cfg, cache, compiled = _kimi_k2_step(chip, batch, s, max_len)
    text = compiled.as_text()
    assert len(_mosaic_calls(text, "held_experts")) == cfg.n_layers - 1
    assert len(_mosaic_calls(text)) == 2 * cfg.n_layers - 1
    stack = tuple(cache["latent"].shape)
    stack_bytes = math.prod(stack) * 2
    if s == 1:
        assert stack_bytes == 5 * 32 * 24576 * 640 * 2
        assert kimi_k2.decode_read_block(cfg, None) == 1024
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75e9)
    assert mem.temp_size_in_bytes < (0.1e9 if s == 1 else 0.8e9)
    assert mem.alias_size_in_bytes >= stack_bytes
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if dims in (stack, stack[1:]) and m.group(3) != "fusion":
            assert m.group(3) in ("parameter", "get-tuple-element",
                                  "dynamic-update-slice", "bitcast"), \
                line[:200]


def test_kimi_k2_chunk_holds_no_array_of_every_pair(chip, on_the_chip):
    """The Kimi-K2.6 cell's chunk program (1,024 tokens, bucket 23,552,
    12 of 384 experts): its four expert layers follow the tiles that
    exist (ops/moe.held_experts_ffn), so no array of 8,192 token-expert
    pairs' rows of 7,168 or more exists in any dtype: not the pairs'
    buffer bf16[9728,7168] (139 MB a layer, zero-filled), not its
    gather bf16[8192,7168], not the weighted f32[1024,8,7168] and its
    sum. One parameter has that shape, the attention's output
    projection (64 heads x 128 = 8,192 rows), and is read where it lies.
    The temporaries, 549 MB at PR 47's parent (sandbox compile, PR 47),
    are smaller by more than the buffer."""
    s = 1024
    cfg, _, compiled = _kimi_k2_step(chip, 1, s, 23552)
    text = compiled.as_text()
    for gone in ("[9728,7168]", "f32[8192,7168]", "[1024,8,7168]"):
        assert gone not in text, gone
    for line in text.splitlines():
        m = _RESULT.match(line)
        dims = tuple(int(d) for d in m.group(2).split(",") if d) if m else ()
        if (dims[-1:] == (cfg.dim,)
                and math.prod(dims[:-1]) >= s * cfg.experts_per_tok):
            # a parameter, or its view inside the fusion that reads it
            assert (m.group(3) in ("parameter", "get-tuple-element",
                                   "bitcast")
                    or "calls=%bitcast_fusion" in line), line[:200]
    assert compiled.memory_analysis().temp_size_in_bytes < 549e6 - 139e6


@functools.cache
def _laguna_step(chip, batch, s, max_len):
    """(config, cache shapes, compiled `decode_step`) of models/laguna.py
    at the Laguna-S-2.1 cell's sizes (5 layers, 64 of 256 experts, a
    quarter of the vocabulary), the cache donated; compiled once a shape
    (10 s each), under the `on_the_chip` fixture of whichever test asks
    first."""
    from ray_tpu.models import laguna

    cfg = laguna.LagunaConfig(vocab_size=25088, n_layers=5, experts_held=64,
                              max_seq_len=24576)
    on = SingleDeviceSharding(chip)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=on), tree)

    params = place(jax.eval_shape(
        lambda key: laguna.init_params(cfg, key), jax.random.PRNGKey(0)))
    cache = dict(jax.eval_shape(
        lambda: laguna.init_cache(cfg, batch, max_len)))
    if s == 1:
        cache["length"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    tokens = jax.ShapeDtypeStruct((batch, s), jnp.int32, sharding=on)
    compiled = jax.jit(
        lambda p, c, t: laguna.decode_step(p, c, t, cfg),
        donate_argnums=(1,)).lower(params, place(cache), tokens).compile()
    return cfg, cache, compiled


@pytest.mark.parametrize("batch,s,max_len", [
    (32, 1, 24576),       # the engine's decode step, the cell's 32 slots
    (1, 1024, 20480),     # one chunk of the deepest bucket's prefill
], ids=["decode-32x24576-on-chip", "chunk-1x1024@20480"])
def test_laguna_step_holds_no_scores_and_moves_no_cache(
        chip, on_the_chip, batch, s, max_len):
    """models/laguna.py at the Laguna-S-2.1 cell's sizes and published
    widths under the rule the other models' steps are held to: weights,
    caches and temporaries fit 15.75 GB; with the cache donated K and V
    of the full layers (6.44 GB for the slots) and the rings are written
    where they lie, and nothing but parameters, the in-place writes and
    the kernels' results has a whole stack's shape. One Mosaic call a
    layer for attention (and one an expert layer,
    ops/pallas/held_experts.py): the decode step's two full layers
    through the decode kernel, which writes its row, and its three rings
    through the same kernel's ring mode, which writes its own too (no `dynamic-update-slice` of a
    stack in that program); a chunk's five through
    ops/pallas/gqa_chunk_attention.py, so that no array of queries by
    the cache's depth (one head's scores, 1,024 x 20,480, let alone `[s,
    heads, len]` in float32) exists outside VMEM."""
    from ray_tpu.models import laguna

    cfg, cache, compiled = _laguna_step(chip, batch, s, max_len)
    text = compiled.as_text()
    assert len(_mosaic_calls(text, "held_experts")) == cfg.n_layers - 1
    assert len(_mosaic_calls(text)) == 2 * cfg.n_layers - 1
    leaves = ("k", "v", "window_k", "window_v")
    stacks = {tuple(cache[key].shape) for key in leaves}
    stack_bytes = sum(math.prod(cache[key].shape) * 2 for key in leaves)
    if s == 1:
        assert stack_bytes == 6_442_450_944 + 201_326_592
        assert laguna.decode_read_block(cfg, jax.sharding.Mesh(
            [chip], ("tensor",))) == 512
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75e9)
    assert mem.temp_size_in_bytes < (0.05e9 if s == 1 else 0.3e9)
    assert mem.alias_size_in_bytes >= stack_bytes
    deep = {tuple(cache[key].shape) for key in ("k", "v")}
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if dims in deep or (s == 1 and dims in stacks):
            assert m.group(3) in (
                ("parameter", "get-tuple-element", "custom-call") if s == 1
                else ("parameter", "get-tuple-element",
                      "dynamic-update-slice", "bitcast")), line[:200]
        # a chunk's scores against the deep cache, in any dtype
        assert s == 1 or not (s in dims and max_len in dims), line[:200]
        # wq and w_o are read where they lie: q and k are held as
        # projected (`laguna._qkv`), or every step copies wq transposed,
        # 56 MB a sliding layer (my chip run, PR 51: a quarter of the
        # device time under no phase)
        if m.group(3) == "copy":
            assert sorted(dims) not in ([3072, 6144], [3072, 9216]), \
                line[:200]


# held, d, f, top-k of the three cells whose tokens are routed
_HELD = {"laguna": (64, 3072, 1024, 10), "kimi": (12, 7168, 2048, 8),
         "dots3": (32, 5120, 1536, 8)}


def _held_experts_operands(text):
    """[[defining line of each operand] of each `held_experts` call]."""
    out = []
    for lines in _computations(text).values():
        for call in _mosaic_calls("\n".join(lines), "held_experts"):
            names = re.findall(r"%([\w.\-]+)", call.split(
                " custom-call(", 1)[1].split("), ")[0])
            out.append([next(d for d in lines if re.match(
                rf"\s*(?:ROOT )?%?{re.escape(n)} = ", d)) for n in names])
    return out


@pytest.mark.parametrize("tokens", [32, 1024], ids=["decode-32", "chunk-1024"])
@pytest.mark.parametrize("model", list(_HELD))
def test_held_experts_kernel_compiles_for_the_chip(
        chip, compiled_not_interpreted, model, tokens):
    """ops/moe.held_experts_ffn at the three expert cells' sizes, a
    decode step's 32 rows and a chunk's 1,024: ONE Mosaic call
    (ops/pallas/held_experts.py) inside the VMEM it asks for, x and y
    resident (29 MB of y at Kimi's chunk) beside the blocks of f that
    `f_block` chose; no `while` (the layout's search of the groups' ends
    was one) and no scatter of rows of d; the three stacks are the
    call's operands as the program received them, neither copied nor
    cut."""
    from ray_tpu.ops import moe
    from ray_tpu.ops.pallas import held_experts as he

    held, d, f, k = _HELD[model]
    bm = 16 if tokens == 32 else 128
    fb = he.f_block(tokens, d, f, bm, jnp.bfloat16, jnp.bfloat16)
    assert fb == {"laguna": (1024, 1024), "kimi": (1024, 512),
                  "dots3": (1536, 768)}[model][tokens == 1024]
    assert (he._fixed_bytes(tokens, d, bm, jnp.bfloat16)
            + he._block_bytes(d, fb, bm, jnp.bfloat16)) <= he._VMEM_BYTES
    on = SingleDeviceSharding(chip)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=on)
    compiled = jax.jit(
        lambda x, c, w, wg, wu, wd: moe.held_experts_ffn(x, c, w, wg, wu, wd,
                                                         held)).lower(
        S((tokens, d), jnp.bfloat16), S((tokens, k), jnp.int32),
        S((tokens, k), jnp.float32), S((held, d, f), jnp.bfloat16),
        S((held, d, f), jnp.bfloat16), S((held, f, d), jnp.bfloat16)
    ).compile()
    text = compiled.as_text()
    assert len(_mosaic_calls(text)) == 1
    assert " while(" not in text
    # the layout's integers and x's words are all that is made: 2 MB a
    # decode step (Laguna's one-hot of 320 pairs by 64 experts), x's
    # 14.7 MB beside y's 29.4 at Kimi's chunk
    assert compiled.memory_analysis().temp_size_in_bytes < (
        3e6 if tokens == 32 else 1.6 * tokens * d * 4)
    (operands,) = _held_experts_operands(text)
    assert all(" parameter(" in line for line in operands[-3:]), operands[-3:]
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m and m.group(3) == "scatter":
            assert "," not in m.group(2), line[:200]    # integers, a slot each


@pytest.mark.parametrize("batch,s,max_len", [
    (32, 1, 24576), (1, 1024, 20480),
], ids=["decode-32x24576-on-chip", "chunk-1x1024@20480"])
def test_laguna_step_walks_its_experts_in_one_kernel(chip, on_the_chip, batch,
                                                     s, max_len):
    """The Laguna cell's two step programs hold, under `moe_experts`, no
    `while` (PR 51's loop over the tiles, its inner loop of adds, the
    layout's binary search) and no scatter of rows of d (the weighted
    add into `y`, `fusion f32[1024,3072]`, 8.6% of the cell; ledger, PR
    51); no array of `pairs` or of `n_slots` rows of d exists in any
    dtype but a parameter; and each of the four calls takes its layer's
    three stacks as the program's own parameters, not as copies or
    slices."""
    cfg, _, compiled = _laguna_step(chip, batch, s, max_len)
    text = compiled.as_text()
    T = batch * s
    pairs = T * cfg.experts_per_tok
    bm = 16 if T <= 64 else 128
    n_slots = -(-pairs // bm) * bm + cfg.experts_held * bm
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if "moe_experts" in line:
            assert m.group(3) != "while", line[:200]
            assert m.group(3) != "scatter" or len(dims) == 1, line[:200]
        if dims[-1:] == (cfg.dim,) and math.prod(dims[:-1]) in (pairs,
                                                                n_slots):
            assert m.group(3) in ("parameter", "get-tuple-element",
                                  "bitcast"), line[:200]
    calls = _held_experts_operands(text)
    assert len(calls) == cfg.n_layers - 1
    stacks = set()
    for operands in calls:
        for line in operands[-3:]:
            assert " parameter(" in line and "layers" in line, line[:200]
            stacks.add(re.search(r'op_name="(.*?)"', line).group(1))
    assert len(stacks) == 3 * (cfg.n_layers - 1)


def _without_locations(text: str) -> str:
    """Compiled HLO without what a moved line changes: the tables of
    files and frames, the instructions' metadata, and the Mosaic calls'
    serialized bodies (whose debug locations name lines; the kernel's
    jaxpr is held beside it)."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"backend_config=\{[^}]*\}", "", text)
    text = re.sub(r"stack_frame_id=[0-9]*", "", text)
    return "\n".join(line for line in text.splitlines()
                     if not re.match(r'^[0-9]+ ["{]', line))


def test_chat_decode_step_is_the_program_it_was(chip, on_the_chip):
    """The chat and long-prompt cells' decode step (InternLM2-1.8B, 8
    slots x 4096, per-row depths, as a TPU runs it) compiles to the
    program PR 51's parent compiled it to: the decode kernel gained a
    `ring` mode for models/laguna.py's window layers, and with the mode
    off neither the kernel's jaxpr nor the step around it may differ.
    (A PR that means to change this program computes both digests anew,
    on its parent and on itself.)"""
    import hashlib

    cfg = llama.LlamaConfig(max_seq_len=4096, **_SERVE_CFG)
    compiled, cache = _compiled_decode_step(chip, cfg, 8, 4096, 1, True)
    digest = lambda text: hashlib.sha256(text.encode()).hexdigest()[:16]
    assert digest(_without_locations(compiled.as_text())) == \
        "8a49ebb0af5efd01"
    b, hd = 8, cfg.head_dim
    sd = jax.ShapeDtypeStruct
    new = sd((b, cfg.n_kv_heads, hd), jnp.bfloat16)
    kernel = jax.make_jaxpr(
        lambda q, k, v, li, start, length, kn, vn: da.decode_attention(
            q, k, v, li, start, length, scale=hd ** -0.5, block_len=512,
            new_kv=(kn, vn)))(
        sd((b, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, hd),
           jnp.bfloat16), cache["k"], cache["v"], sd((), jnp.int32),
        sd((b,), jnp.int32), sd((b,), jnp.int32), new, new)
    assert digest(re.sub(r" at 0x[0-9a-f]+", "", str(kernel))) == \
        "d26f2496b372ed36"
