"""Exchange subsystem tests (data/exchange.py + the columnar partition
kernels in data/block.py): pipelined map/reduce scheduling, retry
safety, driver-gather-free repartition, columnar end-to-end memory
shape, dedup, and the exchange telemetry counters."""

import os

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu import data as rd
from ray_tpu.data.block import (NumpyBlock, block_rows, dedup_block,
                                hash_partition, hash_values,
                                is_numpy_block, num_rows_of,
                                range_partition, sort_block,
                                split_partition, stable_hash, take)
from ray_tpu.data.dataset import Dataset
from ray_tpu.data.exchange import ExchangeController, ExchangeSpec
from ray_tpu.data.executor import StreamingExecutor
from ray_tpu.data.streaming_executor import ExecutionOptions


# ------------------------------------------------------- kernel units
def test_take_preserves_block_flavor():
    blk = NumpyBlock({"x": np.arange(10), "y": np.arange(10) * 2.0})
    out = take(blk, [3, 1, 7])
    assert is_numpy_block(out)
    assert out.cols["x"].tolist() == [3, 1, 7]
    rows = [{"x": i} for i in range(5)]
    assert take(rows, [4, 0]) == [{"x": 4}, {"x": 0}]


def test_hash_values_agrees_with_stable_hash():
    # columnar and row blocks in ONE exchange must route equal keys to
    # the same partition, whatever the key dtype
    ints = np.array([0, 5, -3, 2**40], dtype=np.int64)
    assert hash_values(ints).tolist() == [stable_hash(int(v))
                                          for v in ints]
    strs = np.array(["a", "bb", "ccc"])
    assert hash_values(strs).tolist() == [stable_hash(s)
                                          for s in ["a", "bb", "ccc"]]
    floats = [1.5, -2.25, 0.0]
    assert hash_values(floats).tolist() == [stable_hash(v)
                                            for v in floats]
    # numpy SCALARS in row blocks (user map fns emit them) must route
    # like their Python twins in columnar blocks
    assert stable_hash(np.int64(5)) == stable_hash(5)
    assert stable_hash(np.float64(1.5)) == stable_hash(1.5)
    assert stable_hash(np.str_("abc")) == stable_hash("abc")
    # 5 == 5.0 (dedup membership agrees), so routing must too: JSON
    # mixes int/float flavors of the same key
    assert stable_hash(5.0) == stable_hash(5)
    assert stable_hash(np.float64(5.0)) == stable_hash(5)


def test_int_hash_mixes_strided_keys():
    """An identity hash sends stride-n integer keys (all-even ids,
    ids*10) to ONE partition, serializing every hash exchange — the
    mixer must spread them."""
    for stride, n in ((2, 2), (10, 10), (16, 4)):
        keys = np.arange(0, 400 * stride, stride)
        pids = hash_values(keys) % n
        counts = np.bincount(pids, minlength=n)
        assert counts.min() > 0, (stride, n, counts.tolist())
        assert counts.max() < 2 * len(keys) // n, \
            (stride, n, counts.tolist())


def test_hash_partition_columnar_and_rows_agree():
    keys = [f"k{i % 7}" for i in range(100)]
    blk = NumpyBlock({"k": np.array(keys), "v": np.arange(100)})
    rows = [{"k": k, "v": i} for i, k in enumerate(keys)]
    col_shards = hash_partition(blk, "k", 4)
    row_shards = hash_partition(rows, "k", 4)
    for cs, rs in zip(col_shards, row_shards):
        assert sorted(cs.cols["v"].tolist()) == \
            sorted(r["v"] for r in rs)


def test_split_partition_balances_remainders():
    # remainder rows rotate with the offset, so summing over m blocks
    # balances outputs within m rows — without any count gather
    blk = NumpyBlock({"x": np.arange(10)})
    sizes0 = [num_rows_of(s) for s in split_partition(blk, 4, offset=0)]
    sizes1 = [num_rows_of(s) for s in split_partition(blk, 4, offset=1)]
    assert sum(sizes0) == sum(sizes1) == 10
    assert sizes0 == [3, 3, 2, 2] and sizes1 == [2, 3, 3, 2]


def test_range_partition_and_sort_columnar():
    blk = NumpyBlock({"k": np.array([5, 1, 9, 3, 7, 3])})
    parts = range_partition(blk, "k", [3, 7])
    assert sorted(parts[0].cols["k"].tolist()) == [1, 3, 3]
    assert parts[1].cols["k"].tolist() == [5, 7]
    assert parts[2].cols["k"].tolist() == [9]
    # a key equal to a bound lands in the EARLIER partition (both
    # directions): 7 joins partition 0, the 3s join partition 1
    desc = range_partition(blk, "k", [7, 3], descending=True)
    assert sorted(desc[0].cols["k"].tolist()) == [7, 9]
    assert sorted(desc[1].cols["k"].tolist()) == [3, 3, 5]
    assert sorted(desc[2].cols["k"].tolist()) == [1]
    assert sort_block(blk, "k").cols["k"].tolist() == [1, 3, 3, 5, 7, 9]
    assert sort_block(blk, "k", descending=True).cols["k"].tolist() == \
        [9, 7, 5, 3, 3, 1]


def test_dedup_block_kernels():
    blk = NumpyBlock({"k": np.array([2, 1, 2, 3, 1]),
                      "v": np.arange(5)})
    out = dedup_block(blk, "k")
    assert is_numpy_block(out)
    # first occurrence per key, original order preserved within a block
    assert out.cols["k"].tolist() == [2, 1, 3]
    assert out.cols["v"].tolist() == [0, 1, 3]
    rows = [{"a": 1, "b": [1, 2]}, {"a": 1, "b": [1, 2]},
            {"a": 2, "b": [3]}]
    assert dedup_block(rows, None) == [{"a": 1, "b": [1, 2]},
                                       {"a": 2, "b": [3]}]


# -------------------------------------------- controller: pipelining
def test_reduce_starts_before_all_maps_finish(local_cluster, tmp_path):
    """The acceptance criterion: reduce-side folds launch while map
    tasks are still outstanding (controller instrumentation — a barrier
    executor would always show 0 folds before maps done). The order is
    a fact of the test, not of the host's clock: the last map does not
    finish until the controller has launched a fold, so a controller
    that waits for every map before it folds leaves that map to its
    deadline and then shows folds == 0."""
    import threading
    import time

    gate = str(tmp_path / "a-fold-was-launched")

    def gated_map(block, n, idx):
        if idx == 9:
            deadline = time.monotonic() + 60.0
            while not os.path.exists(gate) and time.monotonic() < deadline:
                time.sleep(0.01)
        return split_partition(block, n, idx)

    refs = [rt.put(NumpyBlock({"x": np.full(1000, i)}))
            for i in range(10)]
    spec = ExchangeSpec(4, map_fn=gated_map, fold_min=2)
    ctl = ExchangeController(spec,
                             options=ExecutionOptions(max_in_flight=2))
    ran = threading.Event()

    def open_gate():
        while ctl.stats.folds == 0 and not ran.is_set():
            time.sleep(0.005)
        open(gate, "w").close()

    watcher = threading.Thread(target=open_gate, daemon=True)
    watcher.start()
    try:
        out = ctl.run(refs)
    finally:
        ran.set()
        watcher.join(timeout=10)
    stats = ctl.stats
    assert stats.map_tasks == 10 and stats.maps_done == 10
    # folds only launch while the map side is unfinished, so folds > 0
    # means reduce work ran before all maps completed
    assert stats.folds > 0, stats
    assert 0 < stats.maps_done_at_first_fold < stats.map_tasks, stats
    assert len(out) == 4
    total = sum(num_rows_of(rt.get(r)) for r in out)
    assert total == 10_000


def test_exchange_empty_source(local_cluster):
    spec = ExchangeSpec(3, map_fn=lambda b, n, i: split_partition(b, n))
    out = ExchangeController(spec).run([])
    assert [num_rows_of(rt.get(r)) for r in out] == [0, 0, 0]


def test_exchange_map_fn_shard_count_validated(local_cluster):
    spec = ExchangeSpec(3, map_fn=lambda b, n, i: [b])  # wrong arity
    out = ExchangeController(spec).run([rt.put([{"x": 1}])])
    with pytest.raises(Exception, match="shards"):
        rt.get(out[0])


# ------------------------------------------------- satellite: retries
def test_exchange_map_retry_preserves_rows(local_cluster, tmp_path):
    """A map task whose worker dies mid-exchange retries and reproduces
    the SAME deterministic shard assignment: the reduce outputs hold
    exactly the input multiset — nothing duplicated, nothing lost."""
    marker = str(tmp_path / "crash-once")

    def crashy_map(block, n, idx):
        from ray_tpu.data.block import random_partition

        if idx == 2 and not os.path.exists(marker):
            open(marker, "w").close()
            os._exit(1)  # kill the worker on the FIRST attempt only
        return random_partition(block, n, seed=7 + idx)

    refs = [rt.put([{"v": b * 100 + i} for i in range(100)])
            for b in range(5)]
    spec = ExchangeSpec(4, map_fn=crashy_map, name="retry-test",
                        fold_min=2)
    out = ExchangeController(
        spec, options=ExecutionOptions(max_in_flight=2)).run(refs)
    vals = sorted(r["v"] for ref in out for r in rt.get(ref))
    assert vals == sorted(b * 100 + i for b in range(5)
                          for i in range(100))
    assert os.path.exists(marker)  # the crash really happened


def test_random_shuffle_seedless_is_attempt_stable(local_cluster,
                                                   monkeypatch):
    """Satellite fix: with seed=None the shard assignment must still be
    deterministic per (block index, submission) — the base seed is
    drawn once on the driver and baked into the task args, so a
    driver-level map-task retry cannot route rows differently."""
    from ray_tpu.data import exchange as ex

    captured = {}
    orig_run = ex.ExchangeController.run

    def spy_run(self, refs):
        captured["spec"] = self.spec
        return orig_run(self, refs)

    monkeypatch.setattr(ex.ExchangeController, "run", spy_run)
    execu = StreamingExecutor()
    refs = [rt.put([{"x": b * 10 + i} for i in range(10)])
            for b in range(4)]
    out = execu.random_shuffle(refs, seed=None)
    ids = sorted(r["x"] for ref in out for r in rt.get(ref))
    assert ids == list(range(40))

    spec = captured["spec"]
    block = [{"x": i} for i in range(30)]
    # a retried attempt (same block index) re-derives the SAME shards
    first = spec.map_fn(block, 3, 1)
    again = spec.map_fn(block, 3, 1)
    assert first == again
    # while distinct block indices still get independent assignments
    other = spec.map_fn(block, 3, 2)
    assert first != other


# --------------------------------------- satellite: repartition barrier
def test_repartition_never_gathers_on_driver(local_cluster, monkeypatch):
    """Satellite fix: the old repartition blocked the driver on
    rt.get(per-block counts). The exchange repartition must complete
    without a single driver-side rt.get."""
    gets = []
    real_get = rt.get

    def spy_get(*a, **k):
        gets.append(a)
        return real_get(*a, **k)

    monkeypatch.setattr(rt, "get", spy_get)
    execu = StreamingExecutor()
    refs = [rt.put([{"v": b * 10 + i} for i in range(10 + b)])
            for b in range(5)]
    out = execu.repartition(refs, 3)
    assert not gets, "repartition gathered data on the driver"
    monkeypatch.undo()
    sizes = [num_rows_of(rt.get(r)) for r in out]
    assert sum(sizes) == sum(10 + b for b in range(5))
    # local split + remainder rotation balances within ±(num blocks)
    assert max(sizes) - min(sizes) <= len(refs), sizes


# ------------------------------- satellite: columnar end-to-end memory
def test_columnar_1m_rows_repartition_shuffle_sort_memory(local_cluster):
    """1M columnar rows through repartition→shuffle→sort stay columnar
    END TO END, and the driver never materializes rows: tracemalloc
    driver-peak stays orders of magnitude under the ~200MB a
    row-dict materialization would cost (PR-3 grouped-memory pattern)."""
    import tracemalloc

    n, nblocks = 1_000_000, 8
    per = n // nblocks
    rng = np.random.default_rng(0)
    refs = []
    for b in range(nblocks):
        refs.append(rt.put(NumpyBlock({
            "k": rng.integers(0, 10_000, size=per),
            "v": np.arange(b * per, (b + 1) * per, dtype=np.int64)})))
    # shuffle FIRST: the plan optimizer (correctly) drops a shuffle
    # that a following sort would destroy, so shuffle→repartition→sort
    # is the order that runs all three exchanges
    ds = Dataset(refs).random_shuffle(seed=3).repartition(6).sort("k")

    tracemalloc.start()
    out_refs = list(ds._iter_block_refs())
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < 32 << 20, \
        f"driver peak {peak / 1e6:.1f}MB — rows materializing?"

    blocks = [rt.get(r) for r in out_refs]
    assert blocks and all(is_numpy_block(b) for b in blocks), \
        [type(b) for b in blocks]
    keys = np.concatenate([b.cols["k"] for b in blocks])
    assert len(keys) == n
    assert np.all(keys[1:] >= keys[:-1]), "not globally sorted"
    # no row lost or duplicated through three exchanges
    assert int(np.concatenate([b.cols["v"] for b in blocks]).sum()) == \
        n * (n - 1) // 2


def test_sort_columnar_string_key_descending(local_cluster):
    words = ["pear", "apple", "fig", "kiwi", "date", "plum", "lime",
             "mango"]
    refs = [rt.put(NumpyBlock({"w": np.array(words[i::2])}))
            for i in range(2)]
    execu = StreamingExecutor()
    out = execu.sort(refs, "w", descending=True)
    got = [w for ref in out for w in rt.get(ref).cols["w"].tolist()]
    assert got == sorted(words, reverse=True)


# ----------------------------------------------------- dedup operators
def test_drop_duplicates_columnar(local_cluster):
    ks = np.array([i % 50 for i in range(400)])
    ds = Dataset([rt.put(NumpyBlock({"k": ks[i::4],
                                     "v": np.arange(i, 400, 4)}))
                  for i in range(4)])
    out = ds.drop_duplicates("k")
    blocks = [rt.get(r) for r in out._iter_block_refs()]
    assert all(is_numpy_block(b) for b in blocks if num_rows_of(b))
    kept = sorted(k for b in blocks for k in b.cols["k"].tolist())
    assert kept == list(range(50))


def test_drop_duplicates_rows_and_keyless(local_cluster):
    rows = [{"k": i % 5, "v": i % 3} for i in range(30)]
    ds = rd.from_items(rows, num_blocks=3)
    assert sorted(r["k"] for r in
                  ds.drop_duplicates("k").take_all()) == [0, 1, 2, 3, 4]
    # keyless: whole-row identity (15 distinct (k, v, item) combos)
    distinct = {tuple(sorted(r.items())) for r in rows}
    got = ds.drop_duplicates().take_all()
    assert len(got) == len(distinct)
    assert {tuple(sorted(r.items())) for r in got} == distinct


def test_hash_partition_and_dedup_callable_key(local_cluster):
    """Callable keys force the row path (the documented kernel rule) —
    on columnar AND row blocks — instead of crashing in key_values."""
    key_fn = lambda r: r["k"] % 3  # noqa: E731
    blk = NumpyBlock({"k": np.arange(12)})
    shards = hash_partition(blk, key_fn, 2)
    assert sum(len(s) for s in shards) == 12
    assert dedup_block(blk, key_fn) and len(dedup_block(blk, key_fn)) == 3
    # and end-to-end through the hash exchange
    execu = StreamingExecutor()
    refs = [rt.put([{"k": i} for i in range(b * 6, b * 6 + 6)])
            for b in range(2)]
    out = execu.dedup(refs, key_fn)
    kept = [r["k"] for ref in out for r in rt.get(ref)]
    assert len(kept) == 3 and sorted(k % 3 for k in kept) == [0, 1, 2]


def test_drop_duplicates_unorderable_object_keys(local_cluster):
    """Nullable/mixed object key columns (e.g. from JSON) aren't
    orderable: the columnar dedup kernel must not sort them — first
    occurrence via dict, matching the row path."""
    blk = NumpyBlock({"k": np.array(["a", None, "a", None, "b"],
                                    dtype=object),
                      "v": np.arange(5)})
    out = dedup_block(blk, "k")
    assert out.cols["k"].tolist() == ["a", None, "b"]
    ds = Dataset([rt.put(blk)])
    assert len(ds.drop_duplicates("k").take_all()) == 3
    got = ds.unique("k")  # unorderable mix: unsorted, but complete
    assert len(got) == 3 and set(map(str, got)) == {"a", "None", "b"}


def test_shuffle_ragged_multidim_blocks_degrade_to_rows(local_cluster):
    """Blocks whose 2-D columns have different trailing dims (per-batch
    padded token matrices) can't concat columnar — the exchange reduce
    degrades that partition to rows instead of failing the task."""
    refs = [rt.put(NumpyBlock({"t": np.full((4, w), w, np.int32)}))
            for w in (5, 7)]
    execu = StreamingExecutor()
    out = execu.random_shuffle(refs, seed=1)
    rows = [r for ref in out for r in block_rows(rt.get(ref))]
    assert len(rows) == 8
    widths = sorted(len(np.asarray(r["t"])) for r in rows)
    assert widths == [5] * 4 + [7] * 4


def test_dedup_object_column_with_unhashable_values():
    """Object key columns holding JSON lists or ndarrays dedup like the
    row path (bytes/pickle identity) instead of raising unhashable."""
    blk = NumpyBlock({"k": np.array([None, None, [1, 2], [1, 2], "x"],
                                    dtype=object),
                      "v": np.arange(5)})
    out = dedup_block(blk, "k")
    assert out.cols["v"].tolist() == [0, 2, 4]
    ragged = np.empty(3, dtype=object)
    ragged[0] = np.array([7, 8])
    ragged[1] = np.array([7, 8])
    ragged[2] = np.array([9])
    out2 = dedup_block(NumpyBlock({"k": ragged, "v": np.arange(3)}), "k")
    assert out2.cols["v"].tolist() == [0, 2]


def test_dedup_nan_keys_agree_across_block_flavors():
    """NaN keys (a nullable float column) dedup to ONE representative
    on BOTH paths: np.unique collapses NaNs on the numeric columnar
    path, and the row path must match (NaN != NaN would keep them all,
    making results depend on block flavor)."""
    k = np.array([1.0, np.nan, np.nan, 2.0])
    cols = dedup_block(NumpyBlock({"k": k, "v": np.arange(4)}), "k")
    rows = dedup_block([{"k": float(x), "v": i}
                        for i, x in enumerate(k)], "k")
    assert len(cols) == len(rows) == 3
    assert sorted(r["v"] for r in rows) == [0, 1, 3]


def test_dedup_multidim_key_column_row_path():
    """A multi-dim key column must not hit np.unique (flat indices are
    wrong/out of range): it routes to the row path with byte-wise key
    identity."""
    blk = NumpyBlock({"k": np.array([[1, 2], [1, 2], [3, 4]]),
                      "v": np.array([10, 11, 12])})
    out = dedup_block(blk, "k")
    assert [r["v"] for r in out] == [10, 12]


def test_unique_values(local_cluster):
    ds = rd.from_items([{"name": n} for n in
                        ["b", "a", "c", "a", "b", "a"]], num_blocks=2)
    assert ds.unique("name") == ["a", "b", "c"]


def test_groupby_on_columnar_blocks(local_cluster):
    """The grouped hash exchange keeps columnar blocks columnar on the
    wire (the fold still streams rows inside the reduce task)."""
    refs = [rt.put(NumpyBlock({"g": np.arange(100) % 3,
                               "v": np.arange(100, dtype=np.float64)}))]
    ds = Dataset(refs)
    out = {r["g"]: r["sum(v)"] for r in
           ds.groupby("g").sum("v").take_all()}
    want = {g: float(sum(v for v in range(100) if v % 3 == g))
            for g in range(3)}
    assert out == want


# --------------------------------------------------------- telemetry
def test_exchange_metrics_counters(local_cluster):
    from ray_tpu.util import builtin_metrics as bm

    before = bm.data_exchange_partitions.get(tags={"op": "shuffle"})
    execu = StreamingExecutor()
    refs = [rt.put(NumpyBlock({"x": np.arange(1000)})) for _ in range(3)]
    out = execu.random_shuffle(refs, seed=1)
    rt.wait(out, num_returns=len(out), timeout=60)
    after = bm.data_exchange_partitions.get(tags={"op": "shuffle"})
    assert after - before == 3
    assert bm.data_exchange_bytes.get(tags={"op": "shuffle"}) > 0
    assert execu.last_exchange is not None
    assert execu.last_exchange.bytes_total > 0
