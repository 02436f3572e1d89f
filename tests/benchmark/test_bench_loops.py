"""The request loop and the metric arithmetic, with a fake system."""

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
