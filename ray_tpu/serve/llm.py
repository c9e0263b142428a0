"""TP-sharded LLM serving: batched prefill/decode engine + Serve app.

A replica pins a pjit-sharded model across the host's local mesh
(tensor axis over chips, ICI collectives inserted by GSPMD), decodes
concurrent requests in a continuously-batched slot ring (finished slots
refill between steps), and streams tokens through the existing
streaming-return path (SSE at the proxy).


Ref analogs: python/ray/serve/_private/replica.py:750 (user-callable
host), router.py:321 (request path); the engine itself has no reference
equivalent (Ray serves LLMs via vLLM) — this is the TPU-native design:
static shapes (prompt-length buckets x fixed batch slots), jitted
prefill/decode with donated KV cache, greedy/temperature sampling in-jit.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import dataclasses
import functools
import os
import threading
import time

from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._internal.profiler import process_log, site_type, span_type
from ray_tpu.models import llama, module_for
from ray_tpu.parallel.mesh import build_mesh, shard_params, spec_for
from ray_tpu.serve.multiplex import multiplexed

# Host spans of the engine's units of work (`rayt.engine.*`), written
# into the JAX profiler's trace when one is being taken of this process
# and a flag check otherwise (_internal/profiler.py). A unit opens its
# own through `LLMEngine._unit`, which also counts its time.
_span = span_type()
# The same span, where a jitted call may be made under it: it also names,
# for its thread, the programs asked for inside it (the site and the
# static key the engine holds there), for the process's log of them.
_site = site_type()


ENGINE = "rayt.engine."
# the engine's units of work, by their spans' names: what the loop's
# executor hops are made of
UNITS = ("admit", "prefill_chunk", "finish_prefill", "decode_dispatch",
         "token_sync", "emit")
# a hop of the engine's loop longer than this is a stall: over twice the
# longest sound hop of any cell (a dots3 round with its chunk and the
# first-token read behind it, about 0.1 s) and an eighth of the shortest
# stall on record (2.08 s; PERF.md section 7)
STALL_S = 0.25
STALLS_KEPT = 16


class _LoopClock:
    """Where the engine loop's time goes, always on, in seconds of
    `time.perf_counter()`: the clock of `t_host`, of the request records
    and of a client's stamps on the same host.

    The loop's side marks its turns (`turn`): it is waiting for work,
    inside an executor hop, or between the two, and the time since the
    last mark goes to `wait_s`, or to `handoff_s` (a hop's wall time
    less the units' self time inside it, and all of the time between).
    The executor's side marks the units (`enter`, `leave`): a unit's
    SELF time, its own interval less the units nested in it, goes to
    `units[name]`. So `loop_s` is tiled by `wait_s`, the six units and
    `handoff_s`, also at a read in mid-wait or mid-hop (`read` counts
    the open interval on both sides). A hop longer than STALL_S is
    counted and kept, with what it was made of, in `stalls`.

    One lock for both sides and the reader: the units run one at a time
    (the engine's mutex), a turn and a unit's mark cost two uncontended
    acquisitions and two clock reads."""

    def __init__(self):
        self._lock = threading.Lock()
        self.units = dict.fromkeys(UNITS, 0.0)
        self._open: list = []       # the units open now, outermost first
        self._since = 0.0           # when the innermost began or resumed
        self.loop_s = self.wait_s = self.handoff_s = 0.0
        self._state = None          # None (no loop), "between", "wait", "hop"
        self._mark = 0.0            # the loop's last turn
        # as the open hop began: the units' sum, and what a stall's
        # record is the difference from
        self._hop_units_s = 0.0
        self._hop: dict = {}
        self.hops = 0
        self.stall_count = 0
        self.stall_s = 0.0
        self.stalls: collections.deque = collections.deque(
            maxlen=STALLS_KEPT)
        # inside `prefill_chunk`, not part of the tiling: a request's
        # prefill cache made and placed (`LLMEngine._prefill_cache`)
        self.prefill_cache_s = 0.0

    # ------------------------------------------------ the executor's side
    def enter(self, name: str):
        with self._lock:
            now = time.perf_counter()
            if self._open:
                self.units[self._open[-1]] += now - self._since
            self._open.append(name)
            self._since = now

    def leave(self):
        with self._lock:
            now = time.perf_counter()
            self.units[self._open.pop()] += now - self._since
            self._since = now

    # ---------------------------------------------------- the loop's side
    def _as_of(self, now: float) -> tuple:
        """(loop_s, wait_s, handoff_s, open_unit_s) with the open
        interval of the loop's state counted up to `now`, and how long
        the innermost open unit has run since it began or resumed
        (callers hold the lock)."""
        open_unit_s = now - self._since if self._open else 0.0
        wait, handoff = self.wait_s, self.handoff_s
        if self._state is None:
            return self.loop_s, wait, handoff, open_unit_s
        open_s = now - self._mark
        if self._state == "wait":
            wait += open_s
        elif self._state == "hop":
            handoff += open_s - (sum(self.units.values()) + open_unit_s
                                 - self._hop_units_s)
        else:
            handoff += open_s
        return self.loop_s + open_s, wait, handoff, open_unit_s

    def turn(self, state):
        """The loop's side enters `state`; -> when."""
        with self._lock:
            return self._turn(state)

    def _turn(self, state) -> float:
        now = time.perf_counter()
        self.loop_s, self.wait_s, self.handoff_s, _ = self._as_of(now)
        self._mark, self._state = now, state
        return now

    def start(self):
        """A loop begins. One that never ended (its event loop was
        dropped, not closed) stopped counting when it last turned."""
        with self._lock:
            self._state = None
            self._turn("between")

    def _levels(self) -> dict:
        """What a stall's record holds the growth of over its hop."""
        log = process_log()
        return {"units": dict(self.units), "handoff_s": self.handoff_s,
                "prefill_cache_s": self.prefill_cache_s, "gc_s": log.gc_s,
                "programs_asked": log.appended}

    def begin_hop(self, kind: str, request_id: str, active_slots: int):
        with self._lock:
            t = self._turn("hop")
            self.hops += 1
            self._hop_units_s = sum(self.units.values())
            self._hop = {"t": t, "hop": kind, "active_slots": active_slots,
                         **({"request_id": request_id} if request_id
                            else {}), "before": self._levels()}

    def end_hop(self):
        with self._lock:
            seconds = self._turn("between") - self._hop["t"]
            if seconds <= STALL_S:
                return
            self.stall_count += 1
            self.stall_s += seconds
            before, now = self._hop.pop("before"), self._levels()
            units = {name: s - before["units"][name]
                     for name, s in now.pop("units").items()
                     if s > before["units"][name]}
            self.stalls.append({
                **self._hop, "seconds": seconds, "units": units,
                **{key: now[key] - before[key] for key in now}})

    # --------------------------------------------------------- the reader
    def kept_stalls(self) -> list:
        with self._lock:
            return [dict(rec, units=dict(rec["units"]))
                    for rec in self.stalls]

    def read(self) -> dict:
        """The flat whole-number counters of `LLMEngine.stats()`, in
        microseconds, as of now."""
        with self._lock:
            loop_s, wait_s, handoff_s, open_unit_s = self._as_of(
                time.perf_counter())
            units = dict(self.units)
            if self._open:
                units[self._open[-1]] += open_unit_s
            us = lambda seconds: int(seconds * 1e6)
            return {"loop_us": us(loop_s), "loop_hops": self.hops,
                    "host_us_wait": us(wait_s),
                    **{"host_us_" + name: us(s)
                       for name, s in units.items()},
                    "host_us_handoff": us(handoff_s),
                    "host_us_prefill_cache": us(self.prefill_cache_s),
                    "loop_stalls": self.stall_count,
                    "loop_stall_us": us(self.stall_s)}


class _Unit:
    """One unit of the engine's work: its span as before (a site where
    `program` names the programs asked for inside it), and its self
    time on the engine's clock, profiler session or none."""
    __slots__ = ("_clock", "_name", "_span")

    def __init__(self, clock: _LoopClock, name: str, program=None,
                 **fields):
        self._clock, self._name = clock, name
        self._span = (_span(ENGINE + name, **fields) if program is None
                      else _site(ENGINE + name, program, **fields))

    def __enter__(self):
        self._clock.enter(self._name)
        return self._span.__enter__()

    def __exit__(self, *exc):
        try:
            return self._span.__exit__(*exc)
        finally:
            self._clock.leave()


def _engine_build_phase(init):
    """`LLMEngine.__init__` as the process's `engine_build` phase, after
    the backend is up (its first touch, where this is it, is phase
    `backend`); only the first engine of a process is a phase."""
    @functools.wraps(init)
    def wrapped(self, *args, **kwargs):
        log = process_log()
        log.backend_up()
        with log.phase("engine_build"):
            init(self, *args, **kwargs)
    return wrapped


def _bucket(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


@dataclass
class _Request:
    tokens: list[int]
    max_new_tokens: int
    temperature: float
    out: asyncio.Queue = field(default_factory=asyncio.Queue)
    loop: Optional[asyncio.AbstractEventLoop] = None
    # phase-stamp observation dict from the serving request context
    # (serve/request_context.py): engine threads write plain floats/ints
    # into it (GIL-atomic stores); the replica folds it into the request
    # record after the handler returns. None when not instrumented.
    obs: Optional[dict] = None
    # disaggregated prefill/decode (generate_prefilled): KV rows that
    # were prefilled in ANOTHER pool — admit by grafting, skip prefill
    prefilled: Optional[dict] = None
    # prefill-pool side (prefill_only): deliver the finished small
    # cache as the result instead of decoding from it
    handoff_out: bool = False

    @property
    def request_id(self) -> str:
        """The id the replica put into obs; joins a span to its record."""
        return self.obs.get("request_id", "") if self.obs else ""


@dataclass
class _Slot:
    """One occupied decode slot: a request mid-generation.
    emitted == -1 marks a slot RESERVED by an in-progress chunked
    prefill: decode steps skip it, refill can't double-book it."""
    req: _Request
    emitted: int = 0
    length: int = 0  # host view of the row's cache depth
    start: int = 0   # the row's first real position (left padding)


@dataclass
class _PendingPrefill:
    """A long prompt being prefilled one chunk per engine round, so
    active decode streams keep emitting between chunks, or in the
    chunk's own program where the model has the shared pass (vLLM-style
    chunked prefill; no reference analog — TPU-native static shapes:
    one trace per (chunk, bucket) pair)."""
    req: _Request
    slot: int
    prompts: Any            # np [1, bucket]
    bucket: int
    pos: int = 0            # tokens already prefilled
    # the stored prefix the first chunk grafts, (entry, matched), or None
    prefix: Optional[tuple] = None
    # per-request prefill cache (the model's pytree), made when the first
    # chunk is dispatched: of the requests admitted and waiting for their
    # chunks only the one at work holds one (where a model's leaves do
    # not follow the bucket that is a third of a GB each)
    small: Any = None


@dataclass
class _InFlight:
    """A decode step the device has been given and the host has not
    read yet."""
    # the step's sampled tokens on the device, [max_batch], and behind
    # them the counters the step decided there (the module's STEP_AUX)
    tokens: Any
    rows: list              # per row, the _Request it was dispatched for
    active: int             # how many rows that is


class LLMEngine:
    """Continuously-batched TP generation engine over the local device
    mesh.

    One engine per replica process. The decode batch is `max_batch`
    fixed SLOTS over one persistent KV cache with per-row depths
    (cache["length"] is [b]): a new request is prefilled alone (batch-1,
    per-bucket trace), its KV rows inserted into a free slot, and it
    joins the very next decode step — it never waits for the previous
    batch to drain. Finished slots free immediately and refill from the
    queue between steps. Static shapes throughout: one decode trace
    ever, one prefill + insert trace per prompt bucket.

    Decoding is a pipeline of depth one (`_decode_step_locked`): step
    k+1 is dispatched, fed by step k's tokens where they lie on the
    device, BEFORE the host reads step k's tokens to stream them, so the
    device works through the read, the emit loop and the event loop's
    turn. `stats()` counts how often the pipeline was full
    (`decode_overlapped` of `batches`) and what the look-ahead wasted
    (`decode_rows_discarded`).

    A prompt longer than `prefill_chunk` is prefilled a chunk a round.
    Where the module has a `mixed_step` (models.OPTIONAL) that round is
    ONE program: the chunk and the rows' decode step in one pass over
    the weights (`_advance_prefill`, `_mixed_dispatch`), also when no
    row decodes beside the chunk; `stats()` counts `mixed_steps` and the
    `mixed_rows` they carried. Where it has none the round is the
    chunk's program and then the decode step's. Which it is follows from
    the module, never from an argument.

    The loop accounts for its own time, always on (`_LoopClock`,
    `host_time`): the wait for work with every slot free, which is also
    span `rayt.engine.wait`, each unit's self time and the hand-off
    around the units tile the loop's elapsed time in `stats()`, and an
    executor hop longer than STALL_S is counted and kept with the unit
    it fell in (`stats()["stalls"]`): the record of a stall in a run no
    profiler watched.

    `model` is a model config or the name of a llama preset. The module
    that serves it is `models.module_for(cfg)`, and everything the
    engine knows of the model it reads from that module by the names of
    `models.REQUIRED` and `models.OPTIONAL`; `module_for`'s docstring
    says what each means. What the engine makes of them: "the cache" is
    the module's pytree, and a request's ROW is every leaf but the
    bookkeeping (`length`, `start`, `aux`) at one index of its batch
    axis. A leaf that `CACHE_LEN_AXIS` names is cut and grafted by
    position, as deep as the bucket. A leaf it does not name is
    recurrent: what the whole prefix left behind, valid at the one
    position it was computed to, and grafted whole. That is a
    state-space layer's state, and also a sliding-window layer's RING
    of the last positions, whose depth does not follow the bucket or
    `max_seq_len`: a request's prefill cache holds a ring of the same
    shape, in which position p lies in row p mod ring, and the slot's
    positions are the bucket's, so `insert_row` copies the ring as it
    stands (the chunked prefill needs the ring between chunks anyway;
    cutting "the last window" out of a bucket-deep leaf would mean
    holding that leaf, 45 MB a layer at 20,480, to keep 1.4 MB of it).
    A third such leaf holds a WINDOW of exact rows and, behind it on the
    same axis, one summary a chunk of everything before
    (`models/evabyte.py`): a prefill cache's leaf has fewer summaries
    and the same origin, so it is grafted as it lies.

    A request's prefill cache is made when its first prefill call is
    dispatched, not when it is admitted: of the prompts admitted and
    waiting for their chunks one holds a cache (a third to half a GB
    each for a model whose leaves do not follow the bucket).

    The prefix store grafts a block-aligned PREFIX of a stored row's
    positions into a new request's cache. Recurrent state has no such
    prefix to cut, and a ring holds the positions before ITS end and no
    others (a window with summaries behind it likewise): grafting K and V (or latent rows) beside a state or a ring
    that saw other tokens would be wrong, so for a model with either
    the store holds nothing (`prefix_cache_entries` is 0 in
    `stats()`), whatever was asked for.
    """

    @_engine_build_phase
    def __init__(self, model: Any = "debug", *, tp: int | None = None,
                 max_batch: int = 4, max_seq_len: int | None = None,
                 prompt_buckets: tuple[int, ...] = (32, 128, 512, 1024),
                 prefill_chunk: int = 256,
                 prefix_cache_entries: int = 8,
                 eos_token_id: int | None = None,
                 params: Any = None, seed: int = 0):
        devices = jax.devices()
        tp = tp or len(devices)
        self.mesh = build_mesh({"data": 1, "tensor": tp}, devices[:tp])
        cfg = llama.config_for(model) if isinstance(model, str) else model
        if max_seq_len is not None:
            cfg = dataclasses.replace(cfg, max_seq_len=max_seq_len)
        self.cfg = cfg
        self._model = mod = module_for(cfg)
        if tp > 1 and not mod.TENSOR_PARALLEL:
            raise ValueError(f"{mod.__name__} has no sharding over a "
                             f"tensor axis; tp={tp}")
        self.max_batch = max_batch
        # chunked prefill: prompts longer than this prefill one chunk
        # per engine round instead of stalling decode for the whole
        # prompt (0 disables)
        self.prefill_chunk = int(prefill_chunk)
        self.prompt_buckets = tuple(
            b for b in prompt_buckets if b < cfg.max_seq_len) or (
                cfg.max_seq_len // 2,)
        self.eos_token_id = eos_token_id
        logical = mod.param_logical_axes(cfg)
        if params is None:
            params = mod.init_params(cfg, jax.random.PRNGKey(seed))
        if "lora" in params:
            # adapter-bearing params: the decode path applies the
            # low-rank delta in-scan (models/llama.py), so the engine
            # just needs matching shardings for the adapter subtree
            from ray_tpu.models import lora as lora_mod

            layers = params["lora"]["layers"]
            targets = tuple(sorted({k[:-2] for k in layers}))
            rank = layers[targets[0] + "_a"].shape[-1]
            logical = {**logical, "lora": lora_mod.lora_logical_axes(
                cfg, lora_mod.LoraConfig(rank=int(rank),
                                         alpha=cfg.lora_alpha,
                                         targets=targets))}
        shardings = shard_params(params, logical, self.mesh)
        self.params = jax.device_put(params, shardings)
        cache_axes = mod.cache_logical_axes(cfg)
        self._cache_sharding = jax.tree.map(
            lambda ax: jax.sharding.NamedSharding(
                self.mesh, spec_for(ax, mesh=self.mesh)),
            cache_axes, is_leaf=lambda x: isinstance(x, tuple))
        # a request's row: every leaf but the bookkeeping, by batch axis
        self._batch_axis = {name: ax.index("batch")
                            for name, ax in cache_axes.items()
                            if name not in ("length", "start", "aux")}
        self._len_axis = mod.CACHE_LEN_AXIS
        recurrent = set(self._batch_axis) - set(self._len_axis)
        shapes = jax.eval_shape(lambda: mod.init_cache(
            cfg, max_batch, max_len=cfg.max_seq_len))
        kinds = mod.CACHE_KIND
        self._cache_bytes = {"kv": 0, "state": 0}
        for name in self._batch_axis:
            kind = kinds.get(name, "state" if name in recurrent else "kv")
            leaf = shapes[name]
            # a leaf whose position axis two kinds share says how
            parts = kind(cfg, leaf) if callable(kind) else {
                kind: leaf.size * leaf.dtype.itemsize}
            for kind, nbytes in parts.items():
                self._cache_bytes[kind] = self._cache_bytes.get(kind, 0) \
                    + nbytes
        # counters the model's step decides on the device and returns
        # in cache["aux"]: field of the emit span -> counter of stats()
        self._aux = dict(mod.STEP_AUX)
        self._aux_totals = dict.fromkeys(self._aux.values(), 0)
        # what the module counts of a step's live ranges and of a
        # chunk's attention, summed
        self._model_counters = dict.fromkeys(
            {**mod.decode_counters(cfg, [], max_batch),
             **mod.prefill_counters(cfg, 0, 0, 0, cfg.max_seq_len)}, 0)

        def sample(key, logits, temperature):
            greedy = jnp.argmax(logits, axis=-1)
            sampled = jax.random.categorical(
                key, logits / jnp.maximum(temperature, 1e-4))
            return jnp.where(temperature[:, 0] > 0, sampled,
                             greedy).astype(jnp.int32)

        def step(params, cache, tokens, key, temperature):
            decode = tokens.ndim == 1
            if decode:  # device-resident [b], the last step's aux behind
                tokens = tokens[:max_batch, None]
            # the phase, known from the static shape, names every device
            # operation of this trace in the profiler (metadata only)
            # under the engine's mesh, so that a kernel in the model
            # finds it and runs per shard (ops/attention.py)
            with jax.sharding.use_abstract_mesh(self.mesh.abstract_mesh), \
                    jax.named_scope("prefill" if tokens.shape[1] > 1
                                    else "decode"):
                depth = cache["length"]
                logits, cache = mod.decode_step(params, cache, tokens, cfg)
                if depth.ndim:
                    # the slots' cache: a row that holds no request
                    # (depth < 0, `retire`) stays that way until
                    # insert_row gives it one; the model's attention
                    # reads nothing for it
                    cache["length"] = jnp.where(depth < 0, depth,
                                                cache["length"])
                with jax.named_scope("sample"):
                    key, sub = jax.random.split(key)
                    nxt = sample(sub, logits, temperature)
                    if decode and self._aux:
                        # one array for the one host read of the step
                        nxt = jnp.concatenate([nxt, cache["aux"]])
                    return nxt, cache, key

        def mixed(params, small, chunk, cache, tokens, key, temperature,
                  temps):
            """A chunk of one request's prefill and the slots' decode
            step as the module's one pass over the weights: what `step`
            does for `(small, chunk, temperature)` and for `(cache,
            tokens, temps)`. The module names the phases of its
            operations; here the rows' bookkeeping and sampling are
            `decode`'s and the chunk's token is `prefill`'s."""
            with jax.sharding.use_abstract_mesh(self.mesh.abstract_mesh):
                depth = cache["length"]
                chunk_logits, small, logits, cache = mod.mixed_step(
                    params, small, chunk, cache, tokens[:max_batch, None],
                    cfg)
                with jax.named_scope("prefill"), jax.named_scope("sample"):
                    key, sub_chunk, sub = jax.random.split(key, 3)
                    first = sample(sub_chunk, chunk_logits, temperature)
                with jax.named_scope("decode"):
                    cache["length"] = jnp.where(depth < 0, depth,
                                                cache["length"])
                    with jax.named_scope("sample"):
                        nxt = sample(sub, logits, temps)
                        if self._aux:
                            nxt = jnp.concatenate([nxt, cache["aux"]])
                return first, small, nxt, cache, key

        # one jit; prefill (s=bucket) and decode (s=1) are separate traces
        # of the same function, cached per shape. Donated: the cache (1)
        # and the PRNG key (3), both rebound from the return at every
        # call site — decode_step writes the new rows into the donated
        # cache's own buffers and makes no copy of it, so a cache handed
        # to a step is gone, whole, even when the step fails. NOT
        # donated: tokens (2), because a decode step's sampled tokens
        # feed the next step before the host has read them (the step in
        # flight, _decode_step_locked) and a donated array cannot be
        # read afterwards; and temps (4), which decode reuses across
        # steps.
        self._step_jit = jax.jit(step, donate_argnums=(1, 3))
        # where the module has the shared pass, the program of a round
        # with a chunk due (_advance_prefill). Donated: both caches
        # (1, 3) and the key (5); the tokens (4) and the slots' temps
        # (7) are not, as in `step`
        self._mixed_jit = None if mod.mixed_step is None else jax.jit(
            mixed, donate_argnums=(1, 3, 5))
        self._key_seed = seed ^ 0x5EED
        self._key_reseeds = 0
        self._stepped = False

        def guarded(program: str):
            def call(*args):
                if not self._stepped:
                    # the first step of any kind, returned, ends the
                    # process's start-up (phase `ready`)
                    self._stepped = True
                    with process_log().phase("ready"):
                        return call(*args)
                # the key rides donated through every call site (incl.
                # the prefill paths that never reach _poison_recover): a
                # failed step may have consumed its buffer, so re-seed
                # BEFORE re-raising or the engine would raise 'Array has
                # been deleted' on every later step, forever
                try:
                    return getattr(self, program)(*args)
                except BaseException:
                    self._reseed_key()
                    raise
            return call

        self._step = guarded("_step_jit")
        self._mixed = guarded("_mixed_jit")
        self._decode_program = f"decode_dispatch[{max_batch}]"

        def insert_row(cache, row, slot, length, start):
            """Graft a freshly prefilled request's row (each leaf along
            its own batch axis; K and V as deep as the bucket, recurrent
            leaves whole) into `slot` of the persistent cache and reset
            that row's depth/start."""
            out = dict(cache)
            for name, leaf in row.items():
                out[name] = jax.lax.dynamic_update_slice_in_dim(
                    cache[name], leaf, slot, self._batch_axis[name])
            out["length"] = cache["length"].at[slot].set(length)
            out["start"] = cache["start"].at[slot].set(start)
            return out

        self._insert_row = jax.jit(insert_row, donate_argnums=(0,))
        # the rows of `gone` hold no request from the next step on
        self._retire = jax.jit(
            lambda length, gone: jnp.where(gone, -1, length),
            donate_argnums=(0,),
            out_shardings=self._cache_sharding["length"])
        # positions in a block of the decode step's attention reads;
        # None where it reads the whole cache: the model's to say
        self._decode_block = mod.decode_read_block(cfg, self.mesh)

        def set_slot(cur, temps, slot, tok, temp):
            return cur.at[slot].set(tok), temps.at[slot, 0].set(temp)

        # `cur` may be the unread tokens of the step in flight: not donated
        self._set_slot = jax.jit(set_slot, donate_argnums=(1,))
        self._queue: asyncio.Queue[_Request] = None  # type: ignore
        self._task = None
        self._loop = None
        # decode-slot state. Mutations happen on executor threads, one at
        # a time under _mutex; _epoch fences out a stale step still
        # running on the process-global executor after a loop rebind
        # (replica restart) so it can't touch the new engine state.
        self._mutex = threading.Lock()
        self._epoch = 0
        self._slots: list[Optional[_Slot]] = [None] * max_batch
        self._decode_cache = None  # lazy: built on first request
        # per row, whether the device's cache["length"] says it holds a
        # request: set by insert_row, cleared by _retire
        self._row_live = [False] * max_batch
        # device-resident between steps: re-uploading from host every
        # decode step would cost two H2D transfers per token
        self._replicated = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec())
        self._cur, self._temps = self._fresh_feed()
        self._key = jax.device_put(jax.random.PRNGKey(seed ^ 0x5EED),
                                   self._replicated)
        # the decode step dispatched and not read yet; whenever it is
        # set, some slot still holds a request one of its rows is for
        self._inflight: Optional[_InFlight] = None
        self._pending_prefills: list[_PendingPrefill] = []
        # prefix KV cache: completed prefills park their small-cache
        # rows here (LRU, `prefix_cache_entries` deep) keyed by the
        # prompt's first token block; a new prompt sharing a block-
        # aligned prefix grafts the stored rows and prefills only the
        # tail. Block size follows the router's prefix key derivation
        # (RAYT_SERVE_PREFIX_BLOCK) so routed prefix hits land where
        # the warm rows actually are. 0 entries disables.
        from collections import OrderedDict

        from ray_tpu.serve.handle import prefix_block_tokens

        self.prefix_cache_entries = 0 if recurrent else int(
            prefix_cache_entries)
        self._prefix_block = prefix_block_tokens()
        self._prefix_store: "OrderedDict[tuple, dict]" = OrderedDict()
        # perf counters (for the serve bench)
        self.generated_tokens = 0
        self.batches = 0       # decode steps dispatched
        # of those, read while a later step was already dispatched
        self.decode_overlapped = 0
        # rows of a look-ahead step whose request had ended (eos) by
        # the time the step was read or dropped: the wasted work
        self.decode_rows_discarded = 0
        # summed over dispatched decode steps: positions inside the live
        # rows' [start, length], and positions of the blocks the step's
        # attention is asked to read (the whole cache where no kernel
        # bounds the read)
        self.decode_kv_positions_live = 0
        self.decode_kv_positions_read = 0
        self.prefills = 0
        self.prefill_chunks = 0
        # of the chunk calls, those the mixed program made, and the
        # decode rows they carried
        self.mixed_steps = 0
        self.mixed_rows = 0
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_hit_tokens = 0   # prefill tokens skipped via reuse
        self.kv_handoffs = 0         # disagg rows admitted via channel
        # prompt tokens of the requests this engine finished a prefill
        # for, stored prefixes included (a row prefilled in another
        # pool is that pool's)
        self.prompt_tokens = 0
        # the loop's own account of its time, always on
        self._clock = _LoopClock()

    # ------------------------------------------------------------ serving
    async def ensure_started(self):
        loop = asyncio.get_running_loop()
        if self._loop is not loop or self._task is None or self._task.done():
            # (re)bind to the current event loop — a queue/task from a
            # previous loop (replica restart, repeated asyncio.run) is
            # dead, and so are any requests parked in old slots. Bumping
            # the epoch under the mutex waits out any in-flight executor
            # step and invalidates stragglers; the cache is rebuilt
            # because the old one may have been donated by a stale step.
            with self._mutex:
                self._epoch += 1
                # a restart must not strand live consumers: anything
                # still parked in a slot OR the old queue gets an error,
                # not silence. A consumer whose loop already closed needs
                # (and can receive) no notification.
                err = RuntimeError("engine restarted")

                def _notify(req):
                    try:
                        req.loop.call_soon_threadsafe(req.out.put_nowait,
                                                      err)
                    except RuntimeError:
                        pass  # consumer's loop is closed: already gone
                for s_ in self._slots:
                    if s_ is not None:
                        _notify(s_.req)
                for pf in self._pending_prefills:
                    _notify(pf.req)
                self._pending_prefills = []
                if self._queue is not None:
                    while True:
                        try:
                            _notify(self._queue.get_nowait())
                        except asyncio.QueueEmpty:
                            break
                self._slots = [None] * self.max_batch
                self._row_live = [False] * self.max_batch
                self._inflight = None
                self._decode_cache = None
                self._cur, self._temps = self._fresh_feed()
            self._queue = asyncio.Queue()
            self._task = asyncio.ensure_future(self._engine_loop())
            self._loop = loop

    async def generate(self, tokens: list[int], *,
                       max_new_tokens: int = 32,
                       temperature: float = 0.0):
        """Async generator of generated token ids. Raises ValueError for
        prompts longer than the largest prefill bucket — silent front-
        truncation would return plausible-but-wrong output."""
        limit = max(self.prompt_buckets)
        if len(tokens) > limit:
            raise ValueError(
                f"prompt is {len(tokens)} tokens; this engine's largest "
                f"prefill bucket is {limit} (raise prompt_buckets / "
                f"max_seq_len)")
        await self.ensure_started()
        try:
            from ray_tpu.serve.request_context import current_request_obs

            obs = current_request_obs()
        except Exception:
            obs = None
        req = _Request(list(tokens), int(max_new_tokens), float(temperature),
                       loop=asyncio.get_running_loop(), obs=obs)
        if obs is not None:
            # queue_s / ttft measure from here: the engine saw the
            # request, whatever happens next (queue park, chunked
            # prefill, decode) is engine-attributable time
            obs["gen_start"] = time.perf_counter()
        await self._queue.put(req)
        while True:
            item = await req.out.get()
            if item is None:
                return
            if isinstance(item, Exception):
                raise item
            yield item

    async def prefill_only(self, tokens: list[int], *,
                           temperature: float = 0.0) -> dict:
        """Run ONLY the prefill (chunked as configured, prefix reuse
        included) and return the KV handoff payload instead of decoding:
        ``{"row", "first", "bucket", "start"}``, `row` the request's
        leaves of the model's cache. This is the
        prefill-pool half of a disaggregated deployment — feed the
        payload to a decode pool's `generate_prefilled`."""
        limit = max(self.prompt_buckets)
        if len(tokens) > limit:
            raise ValueError(
                f"prompt is {len(tokens)} tokens; this engine's largest "
                f"prefill bucket is {limit}")
        await self.ensure_started()
        try:
            from ray_tpu.serve.request_context import current_request_obs

            obs = current_request_obs()
        except Exception:
            obs = None
        req = _Request(list(tokens), 1, float(temperature),
                       loop=asyncio.get_running_loop(), obs=obs,
                       handoff_out=True)
        if obs is not None:
            obs["gen_start"] = time.perf_counter()
        await self._queue.put(req)
        item = await req.out.get()
        if isinstance(item, Exception):
            raise item
        return item

    async def generate_prefilled(self, tokens: list[int], handoff: dict,
                                 *, max_new_tokens: int = 32,
                                 temperature: float = 0.0):
        """Async generator over decode-only generation from KV rows
        prefilled in ANOTHER pool (`prefill_only`'s payload, typically
        arriving as one device-channel tick). The first token was
        sampled by the prefill pool and streams out immediately; this
        engine never runs the prompt — long prefills can no longer dip
        its decode-batch occupancy."""
        await self.ensure_started()
        try:
            from ray_tpu.serve.request_context import current_request_obs

            obs = current_request_obs()
        except Exception:
            obs = None
        req = _Request(list(tokens), int(max_new_tokens),
                       float(temperature),
                       loop=asyncio.get_running_loop(), obs=obs,
                       prefilled=dict(handoff))
        if obs is not None:
            obs["gen_start"] = time.perf_counter()
        await self._queue.put(req)
        while True:
            item = await req.out.get()
            if item is None:
                return
            if isinstance(item, Exception):
                raise item
            yield item

    async def _engine_loop(self):
        """Continuous-batching scheduler: admit into free slots between
        decode rounds; a late-arriving request starts decoding at the
        step after the one in flight at its prefill, regardless of how
        deep the other slots are. A slot stays taken until its
        request's last token is emitted, so "some slot decodes" also
        covers a step in flight that is still to be read."""
        loop = asyncio.get_running_loop()
        epoch = self._epoch
        queue = self._queue  # bound once: after a rebind self._queue is
        # the NEW loop's queue; a stale loop reading it would steal and
        # fail the new loop's requests

        clock = self._clock

        async def hop(kind: str, fn, *args, request_id: str = ""):
            """One executor hop, measured from this side: before the
            call to after its return."""
            clock.begin_hop(kind, request_id,
                            sum(1 for s in self._slots if s is not None))
            try:
                return await loop.run_in_executor(None, fn, *args)
            finally:
                clock.end_hop()

        async def _admit(req: _Request):
            try:
                await hop("admit", self._admit, req, epoch,
                          request_id=req.request_id)
            except Exception as e:
                req.loop.call_soon_threadsafe(req.out.put_nowait, e)

        clock.start()
        try:
            while epoch == self._epoch:
                if not any(s is not None for s in self._slots):
                    # idle: block until work arrives (no spinning), under
                    # a span of its own: the device idle beneath it is
                    # the offered load's, not the program's
                    with _span(ENGINE + "wait"):
                        clock.turn("wait")
                        try:
                            req = await queue.get()
                        finally:
                            clock.turn("between")
                    await _admit(req)
                # opportunistic refill of every free slot, no waiting
                while (not queue.empty()
                       and any(s is None for s in self._slots)):
                    await _admit(queue.get_nowait())
                stepped = False
                if self._pending_prefills:
                    # one chunk per round: a long prompt costs active
                    # streams ~one chunk of latency per step, not the
                    # whole-prompt stall. Where the module has the shared
                    # pass the chunk's program steps the rows too
                    try:
                        stepped = await hop(
                            "prefill", self._advance_prefill, epoch,
                            request_id=self._pending_prefills[0]
                            .req.request_id)
                    except Exception:
                        if epoch != self._epoch:
                            return
                if not stepped and any(s is not None and s.emitted >= 0
                                       for s in self._slots):
                    try:
                        await hop("decode", self._decode_step_all, epoch)
                    except Exception:
                        # _poison_recover already failed the active
                        # requests and reset the (donated, now-dead)
                        # cache; an epoch mismatch means a newer loop
                        # owns the engine — stop
                        if epoch != self._epoch:
                            return
        finally:
            clock.turn(None)

    # ------------------------------------------------------- the hot path
    def _unit(self, name: str, program=None, **fields) -> _Unit:
        """`with self._unit("emit", active=3):` is the unit's span
        `rayt.engine.emit` (a site where `program` is given) and its
        self time in `stats()["host_us_emit"]`."""
        return _Unit(self._clock, name, program, **fields)

    def _ensure_decode_cache(self):
        if self._decode_cache is None:
            cache = self._model.init_cache(self.cfg, self.max_batch,
                                           max_len=self.cfg.max_seq_len)
            # per-row depths: each slot is an independent request
            cache["length"] = jnp.zeros((self.max_batch,), jnp.int32)
            cache = jax.device_put(cache, self._cache_sharding)
            # and holds none yet (through _retire, so that its program
            # exists before the first request ends)
            cache["length"] = self._retire(
                cache["length"], np.ones((self.max_batch,), bool))
            self._decode_cache = cache

    def _finish(self, i: int):
        s = self._slots[i]
        s.req.loop.call_soon_threadsafe(s.req.out.put_nowait, None)
        self._slots[i] = None  # row's temp/token are garbage-masked

    def _admit(self, req: _Request, epoch: int):
        """Prefill one request (batch-1, per-bucket trace) and graft its
        KV rows into a free slot of the persistent decode cache."""
        with self._mutex:
            if epoch != self._epoch:
                raise RuntimeError("engine restarted during admission")
            self._admit_locked(req)

    def _admit_locked(self, req: _Request):
        obs = req.obs
        if obs is not None and "gen_start" in obs:
            obs["admit"] = time.perf_counter()
        slot = next(i for i, s in enumerate(self._slots) if s is None)
        bucket = (int(req.prefilled["bucket"]) if req.prefilled is not None
                  else _bucket(len(req.tokens), self.prompt_buckets))
        with self._unit("admit", f"admit[{bucket}]",
                        request_id=req.request_id,
                        prompt_len=len(req.tokens), bucket=bucket,
                        slot=slot):
            try:
                self._ensure_decode_cache()
            except Exception:
                self._decode_cache = None
                raise
            if req.prefilled is not None:
                # disaggregated handoff: the prefill pool already produced
                # these KV rows — graft them and go straight to decode
                self._admit_prefilled_locked(req, slot)
                return
            self._admit_prompt_locked(req, slot, bucket)

    def _admit_prompt_locked(self, req: _Request, slot: int, bucket: int):
        obs = req.obs
        toks = req.tokens  # generate() enforces len <= max bucket
        start = bucket - len(toks)
        prompts = np.zeros((1, bucket), np.int32)
        prompts[0, start:] = toks

        entry, matched = self._prefix_lookup(toks)
        prefix = (entry, matched) if matched else None
        pos = 0
        if matched:
            # prefix hit: the stored rows go in at this prompt's start
            # offset (KV content is start-RELATIVE — models/llama.py
            # rope positions — so rows are reusable across layouts) and
            # the prefill resumes at the first un-cached token
            pos = start + matched
            self.prefix_hits += 1
            self.prefix_hit_tokens += matched
            if obs is not None:
                obs["prefix_cache"] = "hit"
                obs["prefix_hit_tokens"] = matched
        elif (self.prefix_cache_entries and self._prefix_block
                and len(toks) > self._prefix_block):
            self.prefix_misses += 1
            if obs is not None:
                obs["prefix_cache"] = "cold"
        if self.prefill_chunk and bucket - pos > self.prefill_chunk:
            # long prompt: reserve the slot, prefill chunk-by-chunk
            # between decode steps (engine loop drives _advance_prefill).
            # Left-pad chunks are skipped entirely: they carry no
            # information (masked by `start`, and they leave a recurrent
            # state at the zero it starts from), so begin at the last
            # chunk boundary before the first real token.
            if not matched:
                pos = (start // self.prefill_chunk) * self.prefill_chunk
            self._slots[slot] = _Slot(req, emitted=-1, length=0)
            self._pending_prefills.append(_PendingPrefill(
                req=req, slot=slot, prompts=prompts, bucket=bucket, pos=pos,
                prefix=prefix))
            return
        nxt, small = self._prefill_dispatch(req, None, prompts, pos,
                                            bucket - pos, prefix)
        self.prefills += 1
        self._finish_prefill(req, slot, small, nxt, bucket, start)

    def _prefill_cache(self, bucket: int, start: int, pos: int,
                       prefix: Optional[tuple]) -> dict:
        """A request's prefill cache as its first call finds it: empty
        and `pos` deep (what lies before is left padding, skipped), or
        with the stored prefix `(entry, matched)` grafted at `start`."""
        t = time.perf_counter()
        try:
            small = self._model.init_cache(self.cfg, 1, max_len=bucket)
            small["start"] = jnp.asarray([start], jnp.int32)
            small = jax.device_put(small, self._cache_sharding)
            if prefix is not None:
                return self._graft_prefix(small, prefix[0], start,
                                          prefix[1])
            if pos:
                small["length"] = self._depth(pos)
            return small
        finally:
            self._clock.prefill_cache_s += time.perf_counter() - t

    def _depth(self, pos: int):
        """A prefill cache's `length` set from the host, placed as
        `_prefill_cache` places the one it starts from (the step's
        program is then the same program)."""
        return jax.device_put(np.int32(pos), self._cache_sharding["length"])

    def _prefill_dispatch(self, req: _Request, small, prompts, pos: int,
                          chunk: int, prefix: Optional[tuple] = None):
        """Launch one prefill call over prompts[:, pos:pos + chunk] (the
        whole rest of a short prompt, or one chunk of a long one); the
        request's first call (`small` None) makes its cache. The
        call is asynchronous: its device time is scope `prefill` in a
        profiler trace, not this span. Returns (sampled token, cache)."""
        bucket = prompts.shape[1]
        counters = self._model.prefill_counters(
            self.cfg, bucket - len(req.tokens), pos, chunk, bucket)
        with self._unit("prefill_chunk", f"prefill_chunk[{chunk}@{bucket}]",
                        request_id=req.request_id,
                        pos=pos, chunk=chunk,
                        last=int(pos + chunk >= bucket), **counters):
            if small is None:
                small = self._prefill_cache(
                    bucket, bucket - len(req.tokens), pos, prefix)
            nxt, small, self._key = self._step(
                self.params, small, jnp.asarray(prompts[:, pos:pos + chunk]),
                self._key, jnp.asarray([[req.temperature]], np.float32))
        self._count_prefill_call(req, counters)
        return nxt, small

    def _count_prefill_call(self, req: _Request, counters: dict):
        for name, n in counters.items():
            self._model_counters[name] += n
        if req.obs is not None:
            req.obs["prefill_chunks"] = req.obs.get("prefill_chunks", 0) + 1

    # ----------------------------------------------- prefix KV reuse
    def _prefix_lookup(self, toks: list) -> tuple[Optional[dict], int]:
        """Longest block-aligned reusable prefix for `toks` among the
        stored entries (callers hold _mutex). Returns (entry, matched);
        matched is a multiple of the prefix block, capped one short of
        the full prompt so the tail prefill always has >= 1 token to
        produce the first sampled logits from."""
        block = self._prefix_block
        if (not self.prefix_cache_entries or not block
                or len(toks) <= block):
            return None, 0
        entry = self._prefix_store.get(tuple(toks[:block]))
        if entry is None:
            return None, 0
        self._prefix_store.move_to_end(tuple(toks[:block]))
        etoks = entry["tokens"]
        limit = min(len(etoks), len(toks) - 1)
        n = 0
        while n < limit and etoks[n] == toks[n]:
            n += 1
        matched = (n // block) * block
        return (entry, matched) if matched >= block else (None, 0)

    def _graft_prefix(self, small, entry: dict, off: int,
                      matched: int) -> dict:
        """Copy `matched` stored KV rows into the fresh per-request
        cache at absolute position `off` and advance its write cursor.
        Runs op-by-op outside jit (concrete sizes; one dispatch pair per
        distinct (bucket, matched) — bounded by the block grid)."""
        e_off = int(entry["start"])
        for key_, axis in self._len_axis.items():
            seg = jax.lax.dynamic_slice_in_dim(
                entry["row"][key_], e_off, matched, axis)
            small[key_] = jax.lax.dynamic_update_slice_in_dim(
                small[key_], seg, off, axis)
        small["length"] = self._depth(off + matched)
        return small

    def _prefix_put(self, tokens: list, small, bucket: int):
        """Park a finished prefill's rows in the LRU (callers hold
        _mutex). Entries key on the first token block; a same-key store
        replaces (latest wins — the warm set stays small and fresh)."""
        block = self._prefix_block
        if (not self.prefix_cache_entries or not block
                or len(tokens) <= block):
            return
        key = tuple(tokens[:block])
        self._prefix_store[key] = {
            "tokens": list(tokens), "row": self._row(small),
            "start": bucket - len(tokens), "bucket": bucket}
        self._prefix_store.move_to_end(key)
        while len(self._prefix_store) > self.prefix_cache_entries:
            self._prefix_store.popitem(last=False)

    def _admit_prefilled_locked(self, req: _Request, slot: int):
        h = req.prefilled
        row = jax.device_put(
            dict(h["row"]), {k: self._cache_sharding[k] for k in h["row"]})
        self.kv_handoffs += 1
        self._finish_prefill(req, slot, row, int(h["first"]),
                             int(h["bucket"]), int(h["start"]),
                             store=False)

    def _advance_prefill(self, epoch: int) -> bool:
        """A round's chunk: the next `prefill_chunk` tokens of the first
        pending prompt, and after its last chunk the graft into its
        slot. Where the module has a `mixed_step` the chunk's program
        steps the decode rows too, and the round's read and emit follow
        here (`_mixed_dispatch`); the return says so: the round then
        needs no decode step of its own. A prefill pool's prompt
        (`prefill_only`) keeps the chunk's own program."""
        with self._mutex:
            if epoch != self._epoch or not self._pending_prefills:
                return False
            pf = self._pending_prefills[0]
            mixed = self._mixed_jit is not None and not pf.req.handoff_out
            finishing = False
            try:
                chunk = min(self.prefill_chunk, pf.bucket - pf.pos)
                if mixed:
                    nxt = self._mixed_round_locked(pf, chunk)
                else:
                    nxt, pf.small = self._prefill_dispatch(
                        pf.req, pf.small, pf.prompts, pf.pos, chunk,
                        pf.prefix)
                pf.prefix = None
                pf.pos += chunk
                self.prefill_chunks += 1
                self.mixed_steps += int(mixed)
                if pf.pos < pf.bucket:
                    return mixed
                finishing = True
                self._pending_prefills.pop(0)
                self.prefills += 1
                self._slots[pf.slot] = None  # release the reservation
                self._finish_prefill(
                    pf.req, pf.slot, pf.small, nxt,
                    pf.bucket, pf.bucket - len(pf.req.tokens))
                return mixed
            except BaseException as e:
                # a failed chunk step donated pf.small's buffers, and a
                # failed final insert already removed pf from the lists
                # _poison_recover notifies: either way, retrying is
                # impossible and the consumer must hear about it. A
                # failed mixed step lost the slots' cache too, and
                # _poison_recover has told this prompt with the rest
                pending = bool(self._pending_prefills) and \
                    self._pending_prefills[0] is pf
                if pending:
                    self._pending_prefills.pop(0)
                    self._slots[pf.slot] = None
                if pending or finishing:
                    pf.req.loop.call_soon_threadsafe(
                        pf.req.out.put_nowait,
                        e if isinstance(e, Exception)
                        else RuntimeError(repr(e)))
                raise

    def _mixed_round_locked(self, pf: _PendingPrefill, chunk: int):
        """One round of the decode pipeline (`_decode_step_locked`)
        whose dispatch is the mixed program: it carries `pf`'s next
        chunk and steps the rows. Returns the chunk's sampled token."""
        first = None

        def dispatch(prev):
            nonlocal first
            first, rec = self._mixed_dispatch(pf, chunk, prev)
            return rec

        self._decode_step_locked(dispatch)
        return first

    def _mixed_dispatch(self, pf: _PendingPrefill, chunk: int,
                        prev: Optional[_InFlight]):
        """Dispatch the mixed program for `pf`'s next chunk and the rows
        live in this step (`_step_rows`): what `_prefill_dispatch` and
        `_dispatch_decode` do in two calls, under both their spans.
        With no live row every row is retired, the slots' attention
        reads nothing, and the step counts as a chunk alone. Returns
        (the chunk's sampled token, the rows' record or None)."""
        req, bucket, pos = pf.req, pf.bucket, pf.pos
        rows, active = self._step_rows(prev)
        self._retire_left(rows)
        chunk_counters = self._model.prefill_counters(
            self.cfg, bucket - len(req.tokens), pos, chunk, bucket)
        live, read, counters = self._kv_positions(rows, prev) if active \
            else (0, 0, {})
        with self._unit("prefill_chunk", f"prefill_chunk[{chunk}@{bucket}]",
                        request_id=req.request_id, pos=pos, chunk=chunk,
                        last=int(pos + chunk >= bucket), mixed=1,
                        **chunk_counters), \
                (self._unit("decode_dispatch", active=active,
                            live_positions=live,
                            t_host=time.perf_counter(), mixed=1, **counters)
                 if active else contextlib.nullcontext()):
            if pf.small is None:
                pf.small = self._prefill_cache(
                    bucket, bucket - len(req.tokens), pos, pf.prefix)
            (first, pf.small, nxt, self._decode_cache,
             self._key) = self._mixed(
                self.params, pf.small,
                jnp.asarray(pf.prompts[:, pos:pos + chunk]),
                self._decode_cache, self._cur, self._key,
                jnp.asarray([[req.temperature]], np.float32), self._temps)
        self._cur = nxt
        self._count_prefill_call(req, chunk_counters)
        self.mixed_rows += active
        if not active:
            return first, None
        return first, self._count_decode_step(nxt, rows, active, live, read,
                                              counters)

    def _row(self, small: dict) -> dict:
        """A batch-1 cache's leaves without the bookkeeping."""
        return {k: small[k] for k in self._batch_axis}

    def _finish_prefill(self, req: _Request, slot: int, small, first,
                        bucket: int, start: int, store: bool = True):
        """Deliver the prefill's sampled token and graft the request's
        row into the slot (callers hold _mutex). `first` is the step's
        sampled-token array, read here (the host waits for the prefill
        and for the decode step in flight ahead of it: the pipeline
        drains in this round), or the int a prefill pool already read.
        insert_row and set_slot are queued behind the step in flight and
        apply to its outputs (`_decode_cache`, `_cur`), so device order
        makes the graft safe and the request joins at the step after."""
        row = self._row(small)
        if req.prefilled is None:
            self.prompt_tokens += len(req.tokens)
        with self._unit("finish_prefill", f"finish_prefill[{bucket}]",
                        request_id=req.request_id, slot=slot,
                        row_bytes=sum(a.nbytes for a in row.values())):
            if not isinstance(first, int):
                first = int(np.asarray(first)[0])
            if store:
                # park the rows for prefix reuse BEFORE any donation can
                # touch them (insert_row leaves small's arrays alive; the
                # store holds its own refs)
                self._prefix_put(req.tokens, small, bucket)
            if req.handoff_out:
                # prefill-pool side of a disaggregated deployment: the
                # result IS the KV handoff payload — the decode pool grafts
                # it via generate_prefilled. No slot, no insert, no decode.
                req.loop.call_soon_threadsafe(
                    req.out.put_nowait,
                    {"row": row, "first": int(first),
                     "bucket": int(bucket), "start": int(start)})
                req.loop.call_soon_threadsafe(req.out.put_nowait, None)
                return
            if self.eos_token_id is not None and first == self.eos_token_id:
                req.loop.call_soon_threadsafe(req.out.put_nowait, None)
                return
            self.generated_tokens += 1
            if req.obs is not None:
                now = time.perf_counter()
                req.obs["first_token"] = now
                req.obs["last_token"] = now
                req.obs["tokens"] = req.obs.get("tokens", 0) + 1
            req.loop.call_soon_threadsafe(req.out.put_nowait, first)
            if req.max_new_tokens <= 1:
                req.loop.call_soon_threadsafe(req.out.put_nowait, None)
                return
            try:
                self._decode_cache = self._insert_row(
                    self._decode_cache, row,
                    jnp.int32(slot), jnp.int32(bucket), jnp.int32(start))
            except BaseException:
                # insert_row donates the shared cache: a failure here loses
                # every active slot's KV, not just the new request's
                self._poison_recover()
                raise
            self._slots[slot] = _Slot(req, emitted=1, length=bucket,
                                      start=start)
            self._row_live[slot] = True
            self._cur, self._temps = self._set_slot(
                self._cur, self._temps, jnp.int32(slot), jnp.int32(first),
                jnp.float32(req.temperature))

    def _fresh_feed(self):
        """(`_cur`, `_temps`) of an engine no row decodes in, placed as
        a step's outputs are: a program asked for with these is the
        program asked for with those."""
        return jax.device_put(
            (jnp.zeros((self.max_batch + len(self._aux),), jnp.int32),
             jnp.zeros((self.max_batch, 1), jnp.float32)), self._replicated)

    def _reseed_key(self):
        """Rebuild the PRNG key after a failed (donating) step consumed
        its buffer; the reseed counter keeps the stream fresh."""
        import jax as _jax

        self._key_reseeds += 1
        self._key = _jax.device_put(_jax.random.PRNGKey(
            self._key_seed ^ (self._key_reseeds << 16)), self._replicated)

    def _poison_recover(self):
        """The shared decode cache was donated into a call that failed:
        its buffers are gone. Fail every active request and reset so the
        next admission rebuilds from scratch (callers hold _mutex).
        The PRNG key is re-seeded by the _step guard at the raise site."""
        err = RuntimeError("decode cache lost to a failed engine step")
        for s in self._slots:
            # a reserved slot's request is told with the pending prompts
            if s is not None and s.emitted >= 0:
                s.req.loop.call_soon_threadsafe(s.req.out.put_nowait, err)
        for pf in self._pending_prefills:
            pf.req.loop.call_soon_threadsafe(pf.req.out.put_nowait, err)
        self._pending_prefills = []
        self._slots = [None] * self.max_batch
        self._row_live = [False] * self.max_batch
        self._inflight = None
        self._decode_cache = None
        self._cur, self._temps = self._fresh_feed()

    def _decode_step_all(self, epoch: int):
        with self._mutex:
            if epoch != self._epoch:
                raise RuntimeError("engine restarted during decode")
            self._decode_step_locked()

    def _decode_step_locked(self, dispatch=None):
        """One round of the decode pipeline: dispatch step k+1, fed by
        step k's tokens where they lie on the device, THEN read step k's
        tokens and emit them. The device holds the next step queued
        while the host waits for the read, runs the emit loop and gives
        the event loop its turn. An admission (insert_row, set_slot) is
        queued behind the step in flight and applies to its outputs, so
        the admitted request joins at the step after. `dispatch` is
        `_dispatch_decode`, or the mixed program's (a round with a
        chunk due: `_mixed_round_locked`)."""
        prev = self._inflight
        try:
            self._inflight = (dispatch or self._dispatch_decode)(prev)
            if prev is None:
                return
            # a device fault of the step just dispatched surfaces at a
            # later read: this one, or _finish_prefill's
            with self._unit("token_sync", active=prev.active):
                toks = np.asarray(prev.tokens)  # host sync: step k's tokens
        except BaseException:
            self._poison_recover()
            raise
        if self._inflight is not None:
            self.decode_overlapped += 1
        self._emit(prev, toks)
        ahead = self._inflight
        if ahead is not None and not self._owned_rows(ahead):
            # every request of the look-ahead step ended at this read
            # (eos): nobody waits for its tokens, so no host read
            self.decode_rows_discarded += ahead.active
            self._inflight = None

    def _step_rows(self, prev: Optional[_InFlight]) -> tuple[list, int]:
        """(per row, the request a step dispatched now is for, or None;
        how many rows that is). A row that gets its last token from
        `prev`, the step in flight, is not live: the ends the host can
        count are known here, an eos only at the read."""
        rows: list = [None] * self.max_batch
        for i, s in enumerate(self._slots):
            if s is None or s.emitted < 0:  # free or mid-prefill
                continue
            if (prev is not None and prev.rows[i] is s.req
                    and (s.emitted + 1 >= s.req.max_new_tokens
                         or s.length + 1 >= self.cfg.max_seq_len - 1)):
                continue
            rows[i] = s.req
        return rows, sum(1 for r in rows if r is not None)

    def _retire_left(self, rows: list):
        """Tell the device which rows were freed since the last step
        (_finish, or `_step_rows`' rule), so that the step for `rows`
        reads none of their cache."""
        gone = [live and r is None for live, r in zip(self._row_live, rows)]
        if any(gone):
            self._decode_cache["length"] = self._retire(
                self._decode_cache["length"], np.asarray(gone))
            self._row_live = [r is not None for r in rows]

    def _dispatch_decode(self, prev: Optional[_InFlight]):
        """Dispatch a decode step across all slots (free rows compute
        masked garbage — the price of a single static-shape trace) for
        the rows that are live in it, and return its record; None, and
        no dispatch, when no row is."""
        rows, active = self._step_rows(prev)
        if not active:
            return None
        self._retire_left(rows)
        live, read, counters = self._kv_positions(rows, prev)
        # t_host is this process's perf_counter read as the span opens:
        # the one event that carries both clocks, so request records and
        # a client's stamps (CLOCK_MONOTONIC, one clock for the host)
        # can be laid on the profiler's time axis
        with self._unit("decode_dispatch", self._decode_program,
                        active=active, live_positions=live,
                        t_host=time.perf_counter(), **counters):
            nxt, self._decode_cache, self._key = self._step(
                self.params, self._decode_cache, self._cur,
                self._key, self._temps)
        self._cur = nxt  # stays on device for the next step
        return self._count_decode_step(nxt, rows, active, live, read,
                                       counters)

    def _count_decode_step(self, nxt, rows: list, active: int, live: int,
                           read: int, counters: dict) -> _InFlight:
        self.batches += 1
        self.decode_kv_positions_live += live
        self.decode_kv_positions_read += read
        for name, n in counters.items():
            self._model_counters[name] += n
        return _InFlight(nxt, rows, active)

    def _kv_positions(self, rows: list, prev: Optional[_InFlight]):
        """(live, read, counters) of the step about to be dispatched for
        `rows`: the positions inside its live rows' ranges, each [start,
        the slot the step writes]; the positions of the blocks its
        attention is asked to read: the blocks that overlap those ranges
        (and one that nobody reads where row 0 holds no request:
        ops/pallas/decode_attention.py), or the whole cache where
        nothing bounds the read; and what the model's module counts of
        those ranges for itself (`decode_counters`)."""
        # per live row (start, the slot the step writes); the step in
        # flight has not been counted into a slot's length yet
        spans = [(self._slots[i].start, self._slots[i].length
                  + int(prev is not None and prev.rows[i] is req))
                 for i, req in enumerate(rows) if req is not None]
        live = sum(last - start + 1 for start, last in spans)
        counters = self._model.decode_counters(
            self.cfg, spans, self.max_batch)
        block = self._decode_block
        if not block:
            return live, self.max_batch * self.cfg.max_seq_len, counters
        blocks = sum(last // block - start // block + 1
                     for start, last in spans)
        return live, (blocks + (rows[0] is None)) * block, counters

    def _owned_rows(self, rec: _InFlight) -> list:
        """(row, slot) for every row of a dispatched step whose slot
        still holds the request the row was dispatched for. The other
        rows' requests ended (eos) while the step was in flight: their
        slots are free, or taken by a later admission that must see
        none of their tokens."""
        return [(i, s) for i, s in enumerate(self._slots)
                if s is not None and s.req is rec.rows[i]]

    def _emit(self, rec: _InFlight, toks):
        """Hand a read step's tokens to the requests its rows were
        dispatched for, and free the slots of those that end."""
        # occupancy of THIS step, stamped into each participant's obs:
        # mean over a request's steps = how full its decode batches ran
        occupancy = rec.active / self.max_batch
        with self._unit("emit", active=rec.active) as span:
            now = time.perf_counter()
            finished = 0
            owned = self._owned_rows(rec)
            self.decode_rows_discarded += rec.active - len(owned)
            aux: dict = {}
            if self._aux:
                # what the step counted on the device, read with its
                # tokens: on the span, so that a reader can count them
                # inside a traced stretch, and summed for stats()
                aux = {field: int(n) for field, n in zip(
                    self._aux, toks[self.max_batch:])}
                for field, name in self._aux.items():
                    self._aux_totals[name] += aux[field]
            for i, s in owned:
                t = int(toks[i])
                s.length += 1
                if self.eos_token_id is not None and t == self.eos_token_id:
                    self._finish(i)
                    finished += 1
                    continue
                s.emitted += 1
                self.generated_tokens += 1
                if s.req.obs is not None:
                    o = s.req.obs
                    o["tokens"] = o.get("tokens", 0) + 1
                    o["decode_steps"] = o.get("decode_steps", 0) + 1
                    o["occupancy_sum"] = (o.get("occupancy_sum", 0.0)
                                          + occupancy)
                    o["last_token"] = now
                s.req.loop.call_soon_threadsafe(s.req.out.put_nowait, t)
                if (s.emitted >= s.req.max_new_tokens
                        or s.length >= self.cfg.max_seq_len - 1):
                    self._finish(i)
                    finished += 1
            span.set_metadata(finished=finished, **aux)

    def host_time(self) -> dict:
        """The loop's account of its own time, flat whole numbers (the
        times in microseconds): `loop_us` since the loop started, tiled
        by `host_us_wait` (blocked on an empty queue with no slot
        taken), the six units' self time `host_us_<unit>` and
        `host_us_handoff` (a hop's wall time on the loop's side less
        the units inside it: the executor both ways, the wait for the
        mutex, the loop's own Python between hops); `loop_hops`; not
        part of the tiling, `host_us_prefill_cache` (inside
        `prefill_chunk`) and `host_us_gc` (the process's collector,
        inside anything); `loop_stalls` and `loop_stall_us`, the hops
        longer than STALL_S; `prompt_tokens`."""
        return {**self._clock.read(),
                "host_us_gc": int(process_log().gc_s * 1e6),
                "prompt_tokens": self.prompt_tokens}

    def stats(self) -> dict:
        """The engine's counters, the loop's account of its time
        (`host_time`) with the newest stalled hops whole under `stalls`
        ({"t": perf_counter at the hop's start, "seconds", "hop",
        "units": {unit: self seconds inside it}, "handoff_s",
        "prefill_cache_s", "gc_s", "programs_asked": records the log
        grew by, "active_slots", "request_id" where the hop has one}),
        and the process's own log
        (_internal/profiler.ProcessLog): `programs`, every program the
        process asked XLA for, by the site that asked (`last` is the
        answer to "which step recompiled, and when"), and `startup`, the
        phases of the process's start."""
        log = process_log()
        return {"generated_tokens": self.generated_tokens,
                "batches": self.batches,
                "prefills": self.prefills,
                "prefill_chunks": self.prefill_chunks,
                "mixed_steps": self.mixed_steps,
                "mixed_rows": self.mixed_rows,
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "prefix_entries": len(self._prefix_store),
                "prefix_cache_entries": self.prefix_cache_entries,
                "cache_bytes": dict(self._cache_bytes),
                "kv_handoffs": self.kv_handoffs,
                "decode_overlapped": self.decode_overlapped,
                "decode_rows_discarded": self.decode_rows_discarded,
                "decode_kv_positions_live": self.decode_kv_positions_live,
                "decode_kv_positions_read": self.decode_kv_positions_read,
                **self._model_counters, **self._aux_totals,
                "active_slots": sum(1 for s in self._slots
                                    if s is not None),
                "tp": self.mesh.shape.get("tensor", 1),
                **self.host_time(),
                "stalls": self._clock.kept_stalls(),
                "programs": log.programs(), "startup": log.startup()}


def greedy_reference_check(engine: "LLMEngine", tokens: list[int],
                           generated: list[int]) -> dict:
    """Hold a finished greedy request against the training-path model:
    one `forward` of the engine's model over prompt + generated with its own
    params (no KV cache, no left padding, `_block`'s attention instead
    of `_decode_block`'s). Position by position, the token the engine
    emitted must be the argmax of the reference's logits given the same
    prefix — which is greedy decoding with `forward`, since equal
    prefixes are what it would have fed itself. `max_margin` is how far
    an emitted token sat below the reference's best logit (0 where they
    agree): in bf16 two near-tied logits can swap between the two
    programs, so callers hold it to a tolerance instead of asking for
    bit-equal arithmetic."""
    cfg = engine.cfg
    seq = list(tokens) + list(generated)
    padded = 128
    while padded < len(seq):
        padded *= 2  # right pads sit behind the causal mask
    toks = np.zeros((1, padded), np.int32)
    toks[0, :len(seq)] = seq

    def forward(params, toks):
        # under the engine's mesh: with tp > 1 a flash kernel in the
        # reference has to be split per shard (ops/attention.py)
        with jax.sharding.use_abstract_mesh(engine.mesh.abstract_mesh):
            return engine._model.forward(params, toks, cfg)

    logits = jax.jit(forward)(engine.params, jnp.asarray(toks))
    rows = np.asarray(logits[0, len(tokens) - 1:len(seq) - 1], np.float32)
    ref = rows.argmax(-1)
    got = np.asarray(generated)
    margin = rows.max(-1) - rows[np.arange(len(got)), got]
    return {"reference": [int(t) for t in ref],
            "equal": bool((ref == got).all()),
            "mismatches": int((ref != got).sum()),
            "max_margin": float(margin.max()),
            "logit_std": float(rows.std()),
            "forward_len": padded}


def _replica_chips(engine_kw: dict) -> dict:
    """Actor options for a replica that hosts an engine: the chips its
    tensor axis spans, when the cluster has chips. Only a worker that
    holds a TPU lease may leave the CPU (node_manager._spawn_worker), so
    an engine that is to run on the chip has to ask for it. With no `tp`
    the engine spans every device its process sees, which for a leased
    worker is every chip of its node; the request pins that count into
    `engine_kw` so lease and mesh agree."""
    import ray_tpu as rt

    if not rt.is_initialized():
        return {}
    per_node = [int(n.resources_total.get("TPU", 0))
                for n in rt.nodes() if n.alive]
    if not any(per_node):
        return {}
    if not engine_kw.get("tp"):
        engine_kw["tp"] = max(per_node)
    return {"num_tpus": engine_kw["tp"]}


class LlamaService:
    """Serve callable hosting one LLMEngine (deploy via serve.deployment).

    Request payload: {"tokens": [...], "max_new_tokens": int,
    "temperature": float} -> streams {"token": id} dicts.
    """

    def __init__(self, model: Any = "debug", **engine_kw):
        self.engine = LLMEngine(model, **engine_kw)

    async def __call__(self, payload: dict):
        tokens = payload["tokens"]
        if isinstance(tokens, str):  # raw byte-level "tokenizer"
            tokens = [b % self.engine.cfg.vocab_size
                      for b in tokens.encode()]
        async for tok in self.engine.generate(
                tokens,
                max_new_tokens=int(payload.get("max_new_tokens", 32)),
                temperature=float(payload.get("temperature", 0.0))):
            yield {"token": int(tok)}

    def stats(self) -> dict:
        return self.engine.stats()

    def device_report(self) -> dict:
        """Where this replica computes, as jax reports it from inside
        the replica's process, and what it holds on each device."""
        devs = jax.devices()
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs), "pid": os.getpid(),
                "mesh_devices": [d.id for d in
                                 self.engine.mesh.devices.flat],
                # distinct compiled variants of the engine's jitted
                # steps: a prefill per bucket or chunk shape (the mixed
                # program's where the module has one), one decode
                "step_programs": sum(
                    jitted._cache_size() for jitted in (
                        self.engine._step_jit, self.engine._mixed_jit)
                    if jitted is not None),
                "memory": [d.memory_stats() or {} for d in devs]}

    def reference_check(self, tokens: list[int],
                        generated: list[int]) -> dict:
        return greedy_reference_check(self.engine, tokens, generated)


def llm_app(model: Any = "debug", *, num_replicas: int = 1,
            max_ongoing_requests: int = 64, **engine_kw):
    """Build a Serve application for one model behind an LLMEngine:
    `model` is a model config or the name of a llama preset."""
    from ray_tpu.serve.deployment import deployment

    dep = deployment(
        LlamaService,
        num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=_replica_chips(engine_kw),
    )
    return dep.bind(model, **engine_kw)


class MultiplexedLoraService:
    """Multi-LoRA serving: one base model, many adapters time-sharing a
    replica through the multiplex LRU (ref analog: serve's multi-app
    multiplexing; the LoRA mechanics are repo-native, models/lora.py).

    Each adapter id owns its own LLMEngine whose params are
    ``{**base, "lora": adapter}`` — the decode scan applies the
    low-rank delta for real, and the BASE weight arrays are shared
    across engines (jax arrays are immutable), so an extra resident
    adapter costs only its A/B matrices + a KV cache. The per-replica
    adapter cache is the ``@multiplexed`` LRU: the router's affinity
    keeps a hot adapter's traffic on replicas where it is already
    resident, so steady state runs load-free (watch
    rayt_serve_mux_{loads,evictions}_total for thrash).

    ``_load_adapter`` seeds adapters deterministically from the adapter
    id — the stand-in for fetching trained A/B from storage; override
    it to load real checkpoints.

    Request payload: {"tokens": [...], "max_new_tokens": int,
    "temperature": float} with the adapter chosen by the multiplexed
    model id (HTTP header ``serve_multiplexed_model_id`` /
    handle.options(multiplexed_model_id=...)); streams
    {"token": id, "adapter": model_id} dicts.
    """

    def __init__(self, preset: str = "debug", *,
                 max_adapters_per_replica: int = 2, lora_rank: int = 4,
                 seed: int = 0, **engine_kw):
        self.preset = preset
        self.engine_kw = dict(engine_kw)
        self.lora_rank = int(lora_rank)
        self.cfg = llama.config_for(preset)
        self._base = llama.init_params(self.cfg, jax.random.PRNGKey(seed))
        # instance override consumed by the @multiplexed LRU
        self._rayt_mux_max_models = int(max_adapters_per_replica)

    def _load_adapter(self, model_id: str) -> dict:
        from ray_tpu.models import lora as lora_mod

        key = jax.random.PRNGKey(
            int.from_bytes(model_id.encode()[:4].ljust(4, b"\0"), "big"))
        return lora_mod.init_lora_params(
            self.cfg, lora_mod.LoraConfig(rank=self.lora_rank,
                                          alpha=self.cfg.lora_alpha),
            key)

    @multiplexed(max_num_models_per_replica=2)  # instance attr overrides
    async def get_engine(self, model_id: str) -> "LLMEngine":
        params = dict(self._base)
        if model_id:  # empty id serves the bare base model
            params["lora"] = self._load_adapter(model_id)
        return LLMEngine(self.preset, params=params, **self.engine_kw)

    async def __call__(self, payload: dict):
        from ray_tpu.serve.multiplex import get_multiplexed_model_id

        model_id = get_multiplexed_model_id()
        engine = await self.get_engine(model_id)
        tokens = payload["tokens"]
        if isinstance(tokens, str):
            tokens = [b % self.cfg.vocab_size for b in tokens.encode()]
        async for tok in engine.generate(
                tokens,
                max_new_tokens=int(payload.get("max_new_tokens", 8)),
                temperature=float(payload.get("temperature", 0.0))):
            yield {"token": int(tok), "adapter": model_id}


def lora_llm_app(preset: str = "debug", *, num_replicas: int = 1,
                 max_ongoing_requests: int = 16,
                 max_adapters_per_replica: int = 2, **engine_kw):
    """Serve application for multi-LoRA multiplexed serving; route
    requests with handle.options(multiplexed_model_id=<adapter>)."""
    from ray_tpu.serve.deployment import deployment

    dep = deployment(
        MultiplexedLoraService,
        num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=_replica_chips(engine_kw),
    )
    return dep.bind(preset,
                    max_adapters_per_replica=max_adapters_per_replica,
                    **engine_kw)


# ---------------------------------------------------------------------------
# Disaggregated prefill/decode serving
# ---------------------------------------------------------------------------

PREFILL_REPLICAS_ENV = "RAYT_SERVE_PREFILL_REPLICAS"
DECODE_REPLICAS_ENV = "RAYT_SERVE_DECODE_REPLICAS"


def _pool_size(env: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(env, default)))
    except (TypeError, ValueError):
        return default


def _edge_kind(channel, spec) -> str:
    """Classify a KV-handoff edge for accounting: ``device`` when both
    sides share a jax client (same-process handoff, buffers never leave
    the device plane), ``dcn`` when the transport spec rides the
    cross-host DCN store, ``shm`` for the same-host shared-memory ring."""
    from ray_tpu.dag.device_channel import DeviceChannel

    if isinstance(channel, DeviceChannel):
        return "device"
    try:
        from ray_tpu.dag.dcn_channel import DcnChannelSpec

        if isinstance(getattr(spec, "inner", None), DcnChannelSpec):
            return "dcn"
    except Exception:
        pass
    return "shm"


class PrefillWorker:
    """Prefill half of a disaggregated llm deployment (deploy via
    ``disagg_llm_app``). One call = one prompt's prefill: run it
    (chunked, prefix-cache included), then hand the finished KV rows to
    the caller's decode pool as ONE device-channel tick — raw shard
    bytes over the framing in dag/device_channel.py, never a generic
    pickle of the arrays.

    Payload: ``{"tokens": [ids], "temperature": float,
    "chan": DeviceChannelSpec}`` — the decode side owns the channel and
    is already blocked on the read. Returns a handoff summary
    ``{"bytes", "edge_kind", "n_arrays", "bucket", "start"}``.
    """

    def __init__(self, model: Any = "debug", **engine_kw):
        self.engine = LLMEngine(model, **engine_kw)

    async def __call__(self, payload: dict) -> dict:
        from ray_tpu.dag.dcn_channel import attach_channel
        from ray_tpu.dag.device_channel import tree_nbytes

        spec = payload["chan"]
        tokens = [int(t) for t in payload["tokens"]]
        handoff = await self.engine.prefill_only(
            tokens, temperature=float(payload.get("temperature", 0.0)))
        nbytes = int(tree_nbytes(handoff["row"]))
        loop = asyncio.get_running_loop()
        ch = await loop.run_in_executor(None, attach_channel, spec)
        kind = _edge_kind(ch, spec)
        try:
            # one tick, written from an executor thread (the ring may
            # block until the decode side frees a slot)
            await loop.run_in_executor(
                None, lambda: ch.write(dict(handoff), timeout=30.0))
            n_arrays = int(getattr(ch, "device_arrays", 0))
        finally:
            ch.close()
        try:
            from ray_tpu.serve.request_context import current_request_obs

            obs = current_request_obs()
        except Exception:
            obs = None
        if obs is not None:
            obs["pool"] = "prefill"
            obs["kv_handoff_bytes"] = nbytes
            obs["kv_handoff_edge"] = kind
        return {"bytes": nbytes, "edge_kind": kind,
                "n_arrays": n_arrays, "bucket": int(handoff["bucket"]),
                "start": int(handoff["start"])}

    def stats(self) -> dict:
        return self.engine.stats()


class DecodeLlamaService:
    """Decode half of a disaggregated llm deployment: same request
    payload as LlamaService, but the prompt never runs here. Per
    request it creates a private shm ring, asks the prefill pool to
    fill it (the request id and trace carrier ride the composed handle
    call, so both pools' partial records coalesce into ONE waterfall),
    reads the KV rows as one tick, and decodes from them — long
    prefills can no longer dip this pool's decode-batch occupancy.
    """

    def __init__(self, prefill, model: Any = "debug", **engine_kw):
        self.engine = eng = LLMEngine(model, **engine_kw)
        self._prefill = prefill  # DeploymentHandle (composed app node)
        # one tick = one prompt's row at the largest bucket, recurrent
        # leaves included (+ pickle framing): pad 25% + 64KiB so the
        # slot always fits
        row = eng._row(jax.eval_shape(lambda: eng._model.init_cache(
            eng.cfg, 1, max_len=max(eng.prompt_buckets))))
        nbytes = sum(a.size * a.dtype.itemsize for a in row.values())
        self._slot_size = nbytes + nbytes // 4 + (1 << 16)

    def _request_context(self, obs) -> Optional[dict]:
        if not obs or not obs.get("request_id"):
            return None
        return {"request_id": obs["request_id"], "trace": obs.get("trace")}

    async def __call__(self, payload: dict):
        from ray_tpu.dag.channel import ShmChannel
        from ray_tpu.dag.device_channel import (DeviceChannelSpec,
                                                DeviceTransportChannel)

        tokens = payload["tokens"]
        if isinstance(tokens, str):  # raw byte-level "tokenizer"
            tokens = [b % self.engine.cfg.vocab_size
                      for b in tokens.encode()]
        try:
            from ray_tpu.serve.request_context import current_request_obs

            obs = current_request_obs()
        except Exception:
            obs = None
        loop = asyncio.get_running_loop()
        # per-request ring: the shm channel is strictly SPSC, so each
        # handoff gets its own (decode owns it and unlinks on close)
        shm = await loop.run_in_executor(
            None, lambda: ShmChannel.create(
                slot_size=self._slot_size, n_slots=2))
        spec = DeviceChannelSpec(name=shm.spec.name, inner=shm.spec)
        ch = DeviceTransportChannel(shm, spec)
        try:
            handle = self._prefill
            rctx = self._request_context(obs)
            if rctx is not None:
                handle = handle.options(request_context=rctx)
            req = {"tokens": tokens, "chan": spec,
                   "temperature": float(payload.get("temperature", 0.0))}
            # summary first (it surfaces prefill errors with their real
            # traceback), then the tick — which is already in the ring,
            # the prefill side writes it before returning
            summary = await loop.run_in_executor(
                None, lambda: handle.remote(req).result(timeout=120.0))
            tick = await loop.run_in_executor(
                None, lambda: ch.read(timeout=30.0))
        finally:
            ch.close()
        if obs is not None:
            # kv_handoff_* stays OFF this side's record: the prefill
            # partial carries it, and the GCS derives the bytes counter
            # at partial ingest — a second stamp would double-count
            obs["pool"] = "decode"
        async for tok in self.engine.generate_prefilled(
                tokens,
                {k: tick[k] for k in ("row", "first", "bucket", "start")},
                max_new_tokens=int(payload.get("max_new_tokens", 32)),
                temperature=float(payload.get("temperature", 0.0))):
            yield {"token": int(tok)}

    def stats(self) -> dict:
        return self.engine.stats()


def disagg_llm_app(model: Any = "debug", *,
                   prefill_replicas: int | None = None,
                   decode_replicas: int | None = None,
                   max_ongoing_requests: int = 64, **engine_kw):
    """Serve application with disaggregated prefill/decode pools: the
    decode pool is the ingress; each request's prefill runs in the
    prefill pool and hands its KV rows over a device-channel edge. Pool
    sizes default from RAYT_SERVE_PREFILL_REPLICAS /
    RAYT_SERVE_DECODE_REPLICAS (1 each). Both pools build identical
    weights (same model + seed), so rows graft across them."""
    from ray_tpu.serve.deployment import deployment

    if prefill_replicas is None:
        prefill_replicas = _pool_size(PREFILL_REPLICAS_ENV, 1)
    if decode_replicas is None:
        decode_replicas = _pool_size(DECODE_REPLICAS_ENV, 1)
    chips = _replica_chips(engine_kw)  # each pool's replica holds its own
    prefill_dep = deployment(
        PrefillWorker, num_replicas=prefill_replicas,
        max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=chips)
    decode_dep = deployment(
        DecodeLlamaService, num_replicas=decode_replicas,
        max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=chips)
    return decode_dep.bind(prefill_dep.bind(model, **engine_kw),
                           model, **engine_kw)
