"""The request loop and the metric arithmetic, with a fake system."""

import collections
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, loop, registry, traffic


class FakeSystem:
    """Answers instantly; the calls listed in ``fail`` raise."""

    def __init__(self, fail=()):
        import jax.numpy as jnp

        self.calls = 0
        self.fail = set(fail)
        self.answer = jnp.zeros((1, 2)), jnp.zeros((1, 2), jnp.int32)

    def __call__(self, q):
        self.calls += 1
        if self.calls in self.fail:
            raise RuntimeError("planted")
        Q = q.shape[0]
        return SimpleNamespace(scores=self.answer[0], ids=self.answer[1],
                               stats={"bucket": 8, "padded_q": 8 - Q,
                                      "bytes_read": 10 * Q})


def _pool():
    return traffic.QueryPool(np.zeros((64, 4), np.float32))


def test_closed_loop_sends_whole_cycles_for_the_window():
    cycles = traffic.cycles({"batch": {"dist": "pareto", "alpha": 1.2, "min": 3, "max": 3},
                             "cycle": 4}, np.random.default_rng(0))
    recs = loop.closed(FakeSystem(), _pool(), cycles, 0.05)
    assert recs and len(recs) % 4 == 0
    assert all(r["size"] == 3 and r["error"] is None for r in recs)
    assert recs[-1]["start"] - recs[0]["start"] < 0.06


def test_closed_loop_counts_a_failed_call_and_goes_on():
    cycles = traffic.cycles({"batch": {"dist": "pareto", "alpha": 1.2, "min": 2, "max": 2},
                             "cycle": 3}, np.random.default_rng(0))
    recs = loop.closed(FakeSystem(fail={2}), _pool(), cycles, 1e-3)
    assert [r["done"] is None for r in recs[:3]] == [False, True, False]
    assert recs[1]["error"] == "RuntimeError: planted"
    assert [r["offset"] for r in recs[:3]] == [0, 2, 4]


def _fixed(size, cycle, seed=0):
    return traffic.cycles({"batch": {"dist": "pareto", "alpha": 1.2,
                                     "min": size, "max": size},
                           "cycle": cycle}, np.random.default_rng(seed))


def test_closed_pool_sends_whole_cycles_per_client_for_the_window():
    # client c sends requests of c + 2 queries, so its records are known
    t0 = time.perf_counter()
    recs = loop.closed_pool(FakeSystem(), _pool(),
                            [_fixed(c + 2, 4) for c in range(3)], 0.05)
    by_client = collections.Counter(r["size"] for r in recs)
    assert sorted(by_client) == [2, 3, 4]
    assert all(count % 4 == 0 for count in by_client.values())
    assert all(r["error"] is None and r["done"] is not None for r in recs)
    assert [r["start"] for r in recs] == sorted(r["start"] for r in recs)
    # no cycle starts after the window's end
    starts = collections.defaultdict(list)
    for r in recs:
        starts[r["size"]].append(r["start"])
    assert all(max(s[0::4]) < t0 + 0.05 + 0.01 for s in starts.values())


def test_concurrent_takes_get_disjoint_wrapping_slices():
    sizes, rounds = (5, 7, 11, 13, 5, 7, 11, 13), 300   # 21,600 rows
    total = sum(sizes) * rounds
    pool = traffic.QueryPool(np.arange(total + 400, dtype=np.float32)[:, None])
    got = [[] for _ in sizes]
    start = threading.Barrier(len(sizes))

    def client(size, out):
        start.wait()
        for _ in range(rounds):
            out.append((size, *pool.take(size)))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=client, args=(s, out))
                   for s, out in zip(sizes, got)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    # the slices tile the first rows of the pool, each where its offset
    # says: no two clients got the same rows
    end = 0
    for size, offset, q in sorted((g for out in got for g in out),
                                  key=lambda g: g[1]):
        assert offset == end
        assert q[:, 0].tolist() == list(range(offset, offset + size))
        end += size
    assert end == total
    # the next take wraps at the pool's end
    offset, q = pool.take(500)
    assert offset == total
    assert q[:, 0].tolist() == (list(range(total, total + 400))
                                + list(range(100)))
    assert pool.take(1)[0] == 100


def test_one_client_in_the_pool_sends_what_the_closed_loop_sends():
    mix = {"batch": {"dist": "pareto", "alpha": 1.2, "min": 2, "max": 9},
           "cycle": 5}
    rng = lambda: np.random.default_rng([2 ** 33 + 1, 1])
    seconds = 0.02
    one = loop.closed(FakeSystem(), _pool(), traffic.cycles(mix, rng()),
                      seconds)
    pool = loop.closed_pool(FakeSystem(), _pool(),
                            [traffic.cycles(mix, rng())], seconds)
    keep = ("size", "offset", "stats", "error")
    n = min(len(one), len(pool))
    assert n >= 5
    assert ([{k: r[k] for k in keep} for r in one[:n]]
            == [{k: r[k] for k in keep} for r in pool[:n]])
    assert len(one) % 5 == 0 and len(pool) % 5 == 0


def _records(lat_ms, sizes, failed=0):
    recs = [{"start": 10.0 + i, "done": 10.0 + i + ms / 1e3, "size": s,
             "stats": {"padded_q": 8 - s, "bytes_read": 128 * 50 * s}}
            for i, (ms, s) in enumerate(zip(lat_ms, sizes))]
    recs += [{"start": 99.0, "done": None, "size": 1, "stats": {}}] * failed
    return recs


def _view(records):
    return harness.RunView(records=records, n=1000, d=128, row_bytes=128,
                           device_kind="TPU v5 lite")


def test_qps_is_all_queries_over_the_whole_window():
    recs = _records([500.0, 500.0], sizes=[10, 30], failed=1)
    # window opens at 10.0; the last answer is ready at 11.5, and the
    # failed request answered nothing
    e2e = harness.end_to_end(_view(recs), 10.0, None, 1.0, 1.0)
    assert e2e["qps"] == pytest.approx(40 / 1.5)


def test_pad_share_and_rows_read():
    view = _view(_records([1.0, 1.0], sizes=[2, 6]))
    assert registry.reader("pad_share.batch")(view) == pytest.approx(
        100.0 * 8 / 16)
    assert registry.reader("ivf.rows_read_per_query")(view) == pytest.approx(50)


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = {"batch": {"dist": "pareto", "alpha": 1.2, "min": 8, "max": 256},
           "cycle": 32}
    first = next(traffic.cycles(mix, np.random.default_rng(1)))
    second = next(traffic.cycles(mix, np.random.default_rng(2)))
    assert first != second and sorted(first) == sorted(second)
    assert min(first) >= 8 and max(first) <= 256
    assert traffic.warm_sizes(mix) == sorted(set(first))
