"""util-layer tests: ActorPool and distributed Queue (ref analogs:
python/ray/tests/test_actor_pool.py, test_queue.py)."""

import pytest


def test_actor_pool_map(local_cluster):
    import ray_tpu as rt
    from ray_tpu.util import ActorPool

    @rt.remote
    class Doubler:
        def double(self, v):
            return v * 2

    pool = ActorPool([Doubler.remote() for _ in range(2)])
    assert list(pool.map(lambda a, v: a.double.remote(v), range(6))) == [
        0, 2, 4, 6, 8, 10]
    assert sorted(pool.map_unordered(
        lambda a, v: a.double.remote(v), range(4))) == [0, 2, 4, 6]

    pool.submit(lambda a, v: a.double.remote(v), 21)
    assert pool.get_next() == 42
    assert not pool.has_next()


def test_queue_basics(local_cluster):
    from ray_tpu.util import Queue
    from ray_tpu.util.queue import Empty

    q = Queue(maxsize=4)
    assert q.empty()
    for i in range(3):
        q.put(i)
    assert q.qsize() == 3
    assert [q.get() for _ in range(3)] == [0, 1, 2]
    with pytest.raises(Empty):
        q.get(block=False)
    with pytest.raises(Empty):
        q.get(timeout=0.1)
    q.put("x")
    assert q.get_nowait_batch(5) == ["x"]
    q.shutdown()


def test_queue_producers_consumers(local_cluster):
    import ray_tpu as rt
    from ray_tpu.util import Queue

    q = Queue()

    @rt.remote
    def producer(q, lo, hi):
        for i in range(lo, hi):
            q.put(i)
        return hi - lo

    @rt.remote
    def consumer(q, n):
        return sorted(q.get() for _ in range(n))

    p1 = producer.remote(q, 0, 5)
    p2 = producer.remote(q, 5, 10)
    c = consumer.remote(q, 10)
    assert rt.get(p1) + rt.get(p2) == 10
    assert rt.get(c) == list(range(10))
    q.shutdown()


# ------------------------------------------------ ecosystem shims (r4)
def _mp_square(x):
    return x * x


def _mp_add(a, b):
    return a + b


def test_multiprocessing_pool_api(local_cluster):
    """multiprocessing.Pool drop-in over cluster tasks (ref:
    util/multiprocessing/pool.py)."""
    from ray_tpu.util.multiprocessing import Pool

    with Pool(processes=2) as pool:
        assert pool.map(_mp_square, range(8)) == [x * x for x in range(8)]
        assert pool.starmap(_mp_add, [(1, 2), (3, 4)]) == [3, 7]
        assert pool.apply(_mp_add, (5, 6)) == 11
        ar = pool.apply_async(_mp_square, (9,))
        assert ar.get(timeout=60) == 81 and ar.ready() and ar.successful()
        assert sorted(pool.imap_unordered(_mp_square, range(5))) == \
            [0, 1, 4, 9, 16]
        assert list(pool.imap(_mp_square, range(5))) == [0, 1, 4, 9, 16]
    with pytest.raises(ValueError):
        pool.map(_mp_square, [1])  # closed


def test_joblib_backend(local_cluster):
    """scikit-style joblib fan-out over the cluster (ref: util/joblib)."""
    import joblib

    from ray_tpu.util.joblib_backend import register_rayt

    register_rayt()
    with joblib.parallel_backend("rayt", n_jobs=2):
        out = joblib.Parallel()(
            joblib.delayed(_mp_square)(i) for i in range(6))
    assert out == [i * i for i in range(6)]


def test_experimental_internal_kv_and_tqdm(local_cluster):
    import ray_tpu as rt
    from ray_tpu.experimental import internal_kv as kv
    from ray_tpu.experimental import tqdm

    assert kv._internal_kv_initialized()
    assert kv._internal_kv_put("k1", b"v1", overwrite=False)
    assert not kv._internal_kv_put("k1", b"v2", overwrite=False)
    assert kv._internal_kv_get("k1") == b"v1"
    assert kv._internal_kv_exists(b"k1")
    assert b"k1" in kv._internal_kv_list("k")
    assert kv._internal_kv_del("k1")
    assert not kv._internal_kv_exists("k1")

    @rt.remote
    def work():
        from ray_tpu.experimental import tqdm as rtqdm

        total = 0
        for i in rtqdm(range(10), desc="unit work"):
            total += i
        return total

    assert rt.get(work.remote(), timeout=60) == 45
    assert sum(tqdm(range(4), desc="driver")) == 6
