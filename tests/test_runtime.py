"""Production runtime subsystem tests (DESIGN.md §12): profile
resolution/round-trip, cache tiers (bit-parity with uncached search),
the admission shed ladder under synthetic overload, structured
telemetry, background compaction's atomic-swap exact-parity invariant,
the rebuilt serve loop, and the bench trend gate."""

import io
import json
import os
import shutil
import subprocess
import sys
import time
import warnings

import jax
import numpy as np
import pytest

from repro import engine
from repro.data import synthetic
from repro.knn import SearchParams, make_index
from repro.runtime import (
    ADMIT,
    DEGRADE,
    MISS,
    SHED,
    AdmissionController,
    CachedSearcher,
    DegradePolicy,
    LUTCache,
    MaintenanceScheduler,
    RuntimeProfile,
    Telemetry,
    TTLLRUCache,
    fingerprint,
)
from repro.runtime import profile as rtprofile
from repro.runtime import telemetry

K = 10
D = 24
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def corpus():
    c, _q, _m = synthetic.load("product", 600, 8)
    return np.asarray(c[:, :D])


@pytest.fixture(scope="module")
def extra():
    c, _q, _m = synthetic.load("product", 400, 8, key=jax.random.PRNGKey(3))
    return np.asarray(c[:, :D])


@pytest.fixture(scope="module")
def queries(corpus):
    _c, q, _m = synthetic.load("product", 64, 8)
    return np.asarray(q[:, :D])


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# profiles


class TestRuntimeProfile:
    def test_resolve_default_and_explicit(self):
        assert rtprofile.resolve().name == "default"
        assert rtprofile.resolve("ci-cpu").host_device_count == 1
        assert rtprofile.resolve("cpu-mesh4").host_device_count == 4

    def test_resolve_env(self, monkeypatch):
        monkeypatch.setenv(rtprofile.ENV_VAR, "cpu-dev")
        assert rtprofile.resolve().name == "cpu-dev"
        # explicit name wins over the env var
        assert rtprofile.resolve("default").name == "default"

    def test_resolve_unknown_lists_registry(self):
        with pytest.raises(ValueError, match="ci-cpu"):
            rtprofile.resolve("nope")

    def test_round_trip(self):
        p = RuntimeProfile(name="x", platform="cpu", host_device_count=2,
                           xla_flags=("--flag=1",), seed=7,
                           deterministic=False)
        assert RuntimeProfile.from_dict(p.to_dict()) == p
        with pytest.raises(ValueError, match="unknown"):
            RuntimeProfile.from_dict({"name": "x", "bogus": 1})

    def test_stamp_keys(self):
        s = rtprofile.stamp(rtprofile.resolve("default"))
        for key in ("profile", "backend", "device_kind", "interpret",
                    "jax_version", "seed", "deterministic", "n_devices"):
            assert key in s
        assert s["profile"] == "default"
        # this container is CPU: every Pallas number is interpret-mode
        assert s["interpret"] == (jax.default_backend() != "tpu")

    def test_apply_idempotent_and_sticky(self):
        rtprofile._reset_for_tests()
        try:
            p = rtprofile.apply(rtprofile.resolve("default"))
            assert rtprofile.active() is p
            assert rtprofile.apply(p) is p          # same profile: no-op
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                got = rtprofile.apply(rtprofile.resolve("cpu-dev"))
            assert got.name == "default"            # first apply wins
            assert any("already applied" in str(x.message) for x in w)
            assert rtprofile.stamp()["applied"] is True
        finally:
            rtprofile._reset_for_tests()

    def test_key_is_seeded(self):
        k7 = rtprofile.key(RuntimeProfile(name="s7", seed=7))
        assert np.array_equal(np.asarray(k7),
                              np.asarray(jax.random.PRNGKey(7)))

    def test_register(self):
        p = rtprofile.register(RuntimeProfile(name="_test_prof", seed=3))
        try:
            assert rtprofile.resolve("_test_prof") is p
        finally:
            rtprofile.PROFILES.pop("_test_prof")

    def test_platform_profile_refuses_other_backend(self):
        """tpu-serve on a CPU backend raises instead of falling back, and
        leaves the process on the platform it had."""
        rtprofile._reset_for_tests()
        before = jax.config.read("jax_platform_name")
        try:
            with pytest.raises(RuntimeError, match="tpu"):
                rtprofile.apply(rtprofile.resolve("tpu-serve"))
            assert rtprofile._ACTIVE is None
            assert jax.config.read("jax_platform_name") == before
            with pytest.raises(RuntimeError, match="tpu"):
                rtprofile.stamp(rtprofile.resolve("tpu-serve"))
        finally:
            rtprofile._reset_for_tests()

    def test_compile_cache_placement(self, monkeypatch):
        """The cache goes where JAX_COMPILATION_CACHE_DIR says; without
        it, to one fixed directory inside the checkout."""
        prev = jax.config.jax_compilation_cache_dir
        rtprofile._reset_for_tests()
        try:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            rtprofile.apply(rtprofile.resolve("default"))
            assert jax.config.jax_compilation_cache_dir == os.path.join(
                ROOT, ".jax_cache")
            rtprofile._reset_for_tests()
            jax.config.update("jax_compilation_cache_dir", prev)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
            rtprofile.apply(rtprofile.resolve("default"))
            assert jax.config.jax_compilation_cache_dir == prev
        finally:
            rtprofile._reset_for_tests()
            jax.config.update("jax_compilation_cache_dir", prev)


def _run_py(args, cwd, pythonpath=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    if pythonpath:
        env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_result(out: str) -> bool:
    lines = out.strip().splitlines()
    return bool(lines) and lines[-1].startswith('{"ok"')


class TestNoSilentFallback:
    """The chip entry points fail loudly on a CPU-only backend."""

    def test_serve_tpu_profile_exits_nonzero(self):
        r = _run_py(["-m", "repro.launch.serve", "--profile", "tpu-serve",
                     "--n", "64", "--d", "8", "--requests", "1"], ROOT)
        assert r.returncode != 0
        assert "tpu" in r.stderr and "QPS" not in r.stdout

    def test_chip_smoke_exits_nonzero(self):
        r = _run_py(["chip_smoke.py"], ROOT)
        assert r.returncode != 0 and not _printed_result(r.stdout)
        assert "tpu" in r.stderr

    def test_serve_path_leaves_xla_flags_alone(self):
        """launch/dryrun.py sets XLA_FLAGS when imported; nothing the
        chip check imports may pull it in."""
        r = _run_py(["-c", (
            "import os, sys; before = os.environ.get('XLA_FLAGS'); "
            "from repro.launch import serve; from repro import engine; "
            "from repro.runtime import profile; "
            "assert 'repro.launch.dryrun' not in sys.modules; "
            "assert os.environ.get('XLA_FLAGS') == before")], ROOT)
        assert r.returncode == 0, r.stderr[-2000:]

    def test_chip_smoke_alone_exits_nonzero(self, tmp_path):
        """Copied out of the checkout, the script finds no program to
        drive: it fails on the missing package and prints no result."""
        shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
        r = _run_py(["chip_smoke.py"], str(tmp_path), pythonpath=False)
        assert r.returncode != 0 and not _printed_result(r.stdout)
        assert "No module named 'repro'" in r.stderr


# ---------------------------------------------------------------------------
# cache tiers


class TestTTLLRUCache:
    def test_hit_miss_and_lru_eviction(self):
        c = TTLLRUCache(capacity=2)
        assert c.get("a") is MISS
        c.put("a", 1)
        c.put("b", 2)
        assert c.get("a") == 1                  # refreshes a's recency
        c.put("c", 3)                           # evicts b (LRU)
        assert c.get("b") is MISS
        assert c.get("a") == 1 and c.get("c") == 3
        st = c.stats()
        assert (st["hits"], st["misses"], st["evictions"]) == (3, 2, 1)
        assert st["entries"] == 2

    def test_ttl_expiry(self):
        clk = FakeClock()
        c = TTLLRUCache(capacity=4, ttl_s=1.0, clock=clk)
        c.put("a", 1)
        clk.advance(0.5)
        assert c.get("a") == 1
        clk.advance(0.6)                        # 1.1s since put
        assert c.get("a") is MISS
        assert c.counters["expirations"] == 1

    def test_get_or_build(self):
        c = TTLLRUCache(capacity=2)
        calls = []
        build = lambda: calls.append(1) or "v"  # noqa: E731
        assert c.get_or_build("k", build) == "v"
        assert c.get_or_build("k", build) == "v"
        assert len(calls) == 1

    def test_validation(self):
        with pytest.raises(ValueError):
            TTLLRUCache(capacity=0)
        with pytest.raises(ValueError):
            TTLLRUCache(capacity=1, ttl_s=0.0)


class TestFingerprint:
    def test_array_identity_and_sensitivity(self):
        a = np.arange(12, dtype=np.float32)
        assert fingerprint(a) == fingerprint(a.copy())
        assert fingerprint(a) != fingerprint(a.astype(np.float64))
        assert fingerprint(a) != fingerprint(a.reshape(3, 4))
        b = a.copy()
        b[3] += 1e-3
        assert fingerprint(a) != fingerprint(b)

    def test_mixed_parts(self):
        a = np.zeros(3, np.float32)
        assert fingerprint(a, 10, "l2") == fingerprint(a, 10, "l2")
        assert fingerprint(a, 10, "l2") != fingerprint(a, 11, "l2")


class TestCachedSearcher:
    def test_hit_is_bit_identical(self, corpus, queries):
        idx = make_index("flat,lpq8", corpus)
        s = idx.searcher(K, SearchParams(), batch_sizes=(8,))
        cs = CachedSearcher(s, TTLLRUCache(capacity=8))
        q = queries[:8]
        r1 = cs(q)
        assert r1.stats["cache"] == "miss"
        r2 = cs(q)
        assert r2.stats["cache"] == "hit"
        assert np.array_equal(np.asarray(r1.ids), np.asarray(r2.ids))
        assert np.array_equal(np.asarray(r1.scores), np.asarray(r2.scores))
        # a hit reads nothing
        assert r2.stats["bytes_read"] == 0 and r2.stats["chunks"] == 0
        # parity with the raw searcher
        r0 = s(q)
        assert np.array_equal(np.asarray(r0.ids), np.asarray(r2.ids))

    def test_version_invalidates(self, corpus, queries):
        idx = make_index("flat,lpq8", corpus)
        s = idx.searcher(K, SearchParams(), batch_sizes=(8,))
        cache = TTLLRUCache(capacity=8)
        gen = [0]
        cs = CachedSearcher(s, cache, version=lambda: gen[0])
        q = queries[:8]
        cs(q)
        assert cs(q).stats["cache"] == "hit"
        gen[0] += 1                              # simulated re-plan
        assert cs(q).stats["cache"] == "miss"
        assert cache.counters["misses"] == 2

    def test_proxies_plan_surface(self, corpus):
        idx = make_index("flat,lpq8", corpus)
        s = idx.searcher(K, SearchParams(), batch_sizes=(8,))
        cs = CachedSearcher(s, TTLLRUCache(capacity=2))
        assert cs.n_shards == s.n_shards
        assert cs.rerank is s.rerank
        assert cs.buckets_for(5) == s.buckets_for(5)


class TestLUTCacheTier:
    def test_eager_pq_search_hits_and_matches(self, corpus, queries):
        idx = make_index("pq4x4+lpq", corpus, kmeans_iters=2,
                         key=jax.random.PRNGKey(0))
        q = queries[:8]
        baseline = idx.search(q, K)              # uncached
        cache = LUTCache(capacity=4)
        engine.set_lut_cache(cache)
        try:
            r1 = idx.search(q, K)
            r2 = idx.search(q, K)
        finally:
            engine.set_lut_cache(None)
        assert cache.counters["misses"] == 1
        assert cache.counters["hits"] == 1
        for r in (r1, r2):
            assert np.array_equal(np.asarray(baseline.ids), np.asarray(r.ids))
            assert np.array_equal(np.asarray(baseline.scores),
                                  np.asarray(r.scores))

    def test_jitted_searcher_bypasses_cache(self, corpus, queries):
        # inside a compiled Searcher bucket queries are tracers: the
        # engine hook must stand aside (caching a tracer would poison
        # every later batch)
        idx = make_index("pq4x4+lpq", corpus, kmeans_iters=2,
                         key=jax.random.PRNGKey(0))
        s = idx.searcher(K, SearchParams(), batch_sizes=(8,))
        cache = LUTCache(capacity=4)
        engine.set_lut_cache(cache)
        try:
            r = s(queries[:8])
        finally:
            engine.set_lut_cache(None)
        assert np.asarray(r.ids).shape == (8, K)
        assert len(cache) == 0                   # nothing cached under jit


# ---------------------------------------------------------------------------
# admission


class TestAdmission:
    def _ctrl(self, **kw):
        clk = FakeClock()
        kw.setdefault("rate_qps", 10.0)
        kw.setdefault("burst", 8.0)
        kw.setdefault("max_queue", 4)
        kw.setdefault("degrade_queue", 2)
        return AdmissionController(clock=clk, **kw), clk

    def test_ladder_under_overload(self):
        ctrl, _clk = self._ctrl()
        d1 = ctrl.admit(4, queue_depth=0)
        d2 = ctrl.admit(4, queue_depth=0)
        assert (d1.action, d2.action) == (ADMIT, ADMIT)   # burst covers 8
        d3 = ctrl.admit(4, queue_depth=0)                  # bucket empty
        assert (d3.action, d3.reason) == (SHED, "budget")
        assert ctrl.counters["admission_shed_queries"] == 4

    def test_degrade_on_budget_and_watermark(self):
        ctrl, _clk = self._ctrl(burst=5.0)
        assert ctrl.admit(4, queue_depth=0).action == ADMIT   # 1 token left
        d = ctrl.admit(4, queue_depth=0)       # full cost 4 > 1, 0.25*4=1 ok
        assert (d.action, d.reason) == (DEGRADE, "budget")
        ctrl2, _ = self._ctrl()
        d = ctrl2.admit(4, queue_depth=2)      # at the degrade watermark
        assert (d.action, d.reason) == (DEGRADE, "queue")

    def test_hard_queue_bound_and_refill(self):
        ctrl, clk = self._ctrl()
        d = ctrl.admit(1, queue_depth=4)
        assert (d.action, d.reason) == (SHED, "queue")
        ctrl.admit(8, queue_depth=0)                      # drain the bucket
        assert ctrl.admit(8, queue_depth=0).action == SHED
        clk.advance(1.0)                                  # +10 tokens
        assert ctrl.admit(8, queue_depth=0).action == ADMIT

    def test_deadline_at_arrival_and_recheck(self):
        ctrl, clk = self._ctrl()
        assert ctrl.admit(1, 0, deadline=clk() - 0.1).action == SHED
        d = ctrl.admit(1, 0, deadline=clk() + 1.0)
        assert d.action == ADMIT
        # queue aging past the deadline sheds at dequeue
        clk.advance(2.0)
        assert ctrl.recheck(d, deadline=clk() - 1.0).action == SHED
        # remaining budget below the latency EMA degrades
        d = ctrl.admit(1, 0, deadline=clk() + 0.05)
        ctrl.observe(0.2)
        out = ctrl.recheck(d, deadline=clk() + 0.05)
        assert (out.action, out.reason) == (DEGRADE, "deadline")
        assert ctrl.counters["admission_rechecks"] == 2

    def test_degrade_policy_scaling(self):
        pol = DegradePolicy()
        sp = pol.params(SearchParams(nprobe=8, ef_search=100))
        assert (sp.nprobe, sp.ef_search) == (4, 50)
        assert pol.params(SearchParams(nprobe=1, ef_search=1)).nprobe == 1
        assert pol.rerank_depth(40, k=10) == 10
        assert pol.rerank_depth(100, k=10) == 25
        assert pol.rerank_depth(0, k=10) == 0     # no tail stays no tail
        assert pol.rerank_depth(12, k=10) == 10   # never below k

    def test_ema(self):
        ctrl, _ = self._ctrl()
        ctrl.observe(0.1)
        assert ctrl.ema_latency == pytest.approx(0.1)
        ctrl.observe(0.2)
        assert ctrl.ema_latency == pytest.approx(0.125)


# ---------------------------------------------------------------------------
# telemetry


class TestTelemetry:
    def test_request_trace_and_summary(self):
        clk = FakeClock()
        t = Telemetry(clock=clk, meta={"runtime": {"profile": "default"}})
        tr = t.request(0)
        with tr.span("execute"):
            clk.advance(0.010)
        tr.phase("queue_wait", 0.005)
        tr.annotate(outcome="served", bucket=8)
        tr.finish()
        tr.finish()                              # idempotent
        assert t.counters["requests"] == 1
        assert len(t.events) == 1
        ev = t.events[0]
        assert ev["execute_s"] == pytest.approx(0.010)
        assert ev["queue_wait_s"] == pytest.approx(0.005)
        assert ev["outcome"] == "served"
        assert t.summary()["execute"]["count"] == 1
        assert t.percentiles("execute")["p50_ms"] == pytest.approx(10.0)

    def test_adhoc_span_and_events(self):
        clk = FakeClock()
        t = Telemetry(clock=clk)
        with t.span("maintenance/compact", trigger="drift"):
            clk.advance(0.5)
        t.event("write", op="delete", rows=4)
        kinds = [e["type"] for e in t.events]
        assert kinds == ["span", "write"]
        assert t.events[0]["dur_s"] == pytest.approx(0.5)

    def test_to_json_round_trip(self):
        t = Telemetry(meta={"runtime": {"profile": "ci-cpu"}})
        t.counters["queries_served"] += np.int64(8)      # numpy survives
        t.event("shed", reason="queue", queries=np.int32(4))
        buf = io.StringIO()
        payload = t.to_json(buf)
        parsed = json.loads(buf.getvalue())
        assert parsed["meta"]["runtime"]["profile"] == "ci-cpu"
        assert parsed["counters"]["queries_served"] == 8
        assert parsed["events"][0]["queries"] == 4
        assert payload["counters"] == parsed["counters"]

    def test_to_json_path(self, tmp_path):
        t = Telemetry()
        out = tmp_path / "tel.json"
        t.to_json(out)
        assert set(json.loads(out.read_text())) == {
            "meta", "counters", "summary", "events"}

    def test_spans_kept_only_while_recording(self):
        t0 = time.perf_counter_ns()
        with telemetry.span("outside"):
            pass
        assert telemetry.recorded_spans(t0) == []
        with telemetry.recording():
            with telemetry.span("outer", a=1) as fields:
                fields["b"] = 2
                with telemetry.span("inner"):
                    pass
        with telemetry.span("after"):
            pass
        inner, outer = sorted(telemetry.recorded_spans(t0),
                              key=lambda r: r["name"])
        assert [outer["name"], inner["name"]] == ["outer", "inner"]
        assert outer["fields"] == {"a": 1, "b": 2}
        assert outer["parent"] is None and inner["parent"] == outer["id"]
        assert inner["request"] == outer["request"] == outer["id"]
        assert (outer["start_ns"] <= inner["start_ns"] <= inner["end_ns"]
                <= outer["end_ns"])

    def test_spans_kept_under_a_profiler_session(self, tmp_path):
        t0 = time.perf_counter_ns()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with telemetry.span("profiled"):
                pass
        finally:
            jax.profiler.stop_trace()
        with telemetry.span("unprofiled"):
            pass
        assert [r["name"] for r in telemetry.recorded_spans(t0)] == [
            "profiled"]

    def test_to_json_adds_the_session_spans(self):
        clk = FakeClock()
        t = Telemetry(clock=clk)
        with telemetry.recording():
            with t.span("maintenance/compact", trigger="drift"):
                clk.advance(0.5)
            tr = t.request(7)
            with tr.span("execute"):
                pass
            tr.finish()
        payload = t.to_json(io.StringIO())
        kinds = [e["type"] for e in payload["events"]]
        assert kinds == ["span", "request", "host_span", "host_span"]
        compact, execute = payload["events"][2:]
        assert compact["name"] == "maintenance/compact"
        assert compact["fields"] == {"trigger": "drift"}
        assert execute["name"] == "execute"
        assert execute["fields"] == {"req_id": 7}
        assert payload["events"][0]["dur_s"] == pytest.approx(0.5)

    def test_to_json_writes_a_device_field_as_its_number(self):
        """A span field left on the device (the Searcher's merge counter)
        is read when the log is written, and written as its number."""
        t = Telemetry(clock=FakeClock())
        with telemetry.recording():
            with telemetry.span("searcher.call") as f:
                f["merge_steps"] = jax.numpy.int32(130)
        buf = io.StringIO()
        t.to_json(buf)
        event, = json.loads(buf.getvalue())["events"]
        assert event["fields"] == {"merge_steps": 130}


# ---------------------------------------------------------------------------
# background compaction + maintenance


def _map_ids(scratch_ids: np.ndarray, ext_ids: np.ndarray) -> np.ndarray:
    return np.asarray(ext_ids)[np.asarray(scratch_ids)]


class TestBackgroundCompaction:
    def _make(self, corpus, extra, n_extra=200):
        idx = make_index("stream(flat,lpq4)", corpus, seal_threshold=100,
                         auto_compact=False)
        idx.upsert(np.arange(2000, 2000 + n_extra), extra[:n_extra])
        idx.delete(np.arange(0, 8))
        return idx

    def test_full_snapshot_parity_with_from_scratch(self, corpus, extra,
                                                    queries):
        idx = self._make(corpus, extra)
        pending = idx.compact_snapshot(full=True)
        assert pending is not None and pending.recalibrated
        assert idx.apply_compaction(pending)
        st = idx.stats()
        assert st["segments"] == 1 and st["tombstones"] == 0
        # the exact-parity invariant through the background path: the
        # swapped-in segment scores bit-identically to a from-scratch
        # build on the surviving rows
        ext_ids, vecs = idx.live_items()
        ref = make_index("flat,lpq4", vecs)
        res_ref = ref.search(queries, K)
        res = idx.search(queries, K)
        np.testing.assert_array_equal(
            _map_ids(np.asarray(res_ref.ids), ext_ids), np.asarray(res.ids))
        np.testing.assert_allclose(np.asarray(res_ref.scores),
                                   np.asarray(res.scores))

    def test_background_matches_synchronous_compact(self, corpus, extra,
                                                    queries):
        idx_a = self._make(corpus, extra)
        idx_b = self._make(corpus, extra)
        pending = idx_a.compact_snapshot(full=True)
        assert idx_a.apply_compaction(pending)
        idx_b.compact(full=True)
        ra, rb = idx_a.search(queries, K), idx_b.search(queries, K)
        assert np.array_equal(np.asarray(ra.ids), np.asarray(rb.ids))
        assert np.array_equal(np.asarray(ra.scores), np.asarray(rb.scores))

    def test_concurrent_delete_survives_swap(self, corpus, extra):
        # rows deleted while the merge builds off-lock must stay dead
        # after the swap (the snapshot re-applies them as tombstones)
        idx = self._make(corpus, extra)
        pending = idx.compact_snapshot(full=True)
        killed = idx.delete(np.arange(20, 24))
        assert killed == 4
        n_before = idx.n
        assert idx.apply_compaction(pending)
        assert idx.n == n_before
        ext_ids, _vecs = idx.live_items()
        assert not np.isin(np.arange(20, 24), ext_ids).any()

    def test_competing_swap_is_dropped(self, corpus, extra):
        idx = self._make(corpus, extra)
        p1 = idx.compact_snapshot(full=True)
        p2 = idx.compact_snapshot(full=True)
        assert idx.apply_compaction(p1)
        assert not idx.apply_compaction(p2)      # group no longer current
        assert idx.counters["swap_conflicts"] == 1

    def test_epoch_tracks_structural_change(self, corpus, extra):
        idx = make_index("stream(flat,lpq4)", corpus, seal_threshold=100,
                         auto_compact=False)
        e0 = idx.epoch
        idx.upsert(np.arange(2000, 2010), extra[:10])    # memtable-only
        assert idx.epoch == e0
        idx.delete([999_999])                            # no-op delete
        assert idx.epoch == e0
        idx.delete([3])                                  # real tombstone
        assert idx.epoch > e0


class TestMaintenanceScheduler:
    def test_rejects_immutable_index(self, corpus):
        with pytest.raises(TypeError, match="mutable"):
            MaintenanceScheduler(make_index("flat,lpq8", corpus))

    def test_run_once_idle_and_forced(self, corpus, extra):
        idx = make_index("stream(flat,lpq4)", corpus, seal_threshold=100,
                         auto_compact=False)
        idx.upsert(np.arange(2000, 2200), extra[:200])
        t = Telemetry()
        sched = MaintenanceScheduler(idx, telemetry=t)
        out = sched.run_once(force_full=True)
        assert out["swapped"] and out["trigger"] == "forced"
        assert idx.stats()["segments"] == 1
        assert t.counters["maintenance_swaps"] == 1
        # nothing left to do
        assert sched.run_once() == {"ran": False}

    def test_segment_trigger_and_thread(self, corpus, extra):
        idx = make_index("stream(flat,lpq4)", corpus, seal_threshold=50,
                         auto_compact=False, max_segments=2)
        for i in range(4):                       # one sealed segment each
            idx.upsert(np.arange(2000 + i * 50, 2050 + i * 50),
                       extra[i * 50:(i + 1) * 50])
        assert idx.stats()["segments"] > 2
        with MaintenanceScheduler(idx, interval_s=0.01) as sched:
            deadline = 200
            while (idx.compactor.should_compact(idx.manifest.segments)
                   and deadline):
                deadline -= 1
                import time
                time.sleep(0.01)
        assert sched.counters["maintenance_swaps"] >= 1
        assert idx.stats()["segments"] <= 2


# ---------------------------------------------------------------------------
# serve loop (rebuilt on the subsystem)


class TestServeLoop:
    def test_smoke_cache_mutate_telemetry(self, tmp_path):
        from repro.launch import serve

        out = tmp_path / "tel.json"
        serve.main([
            "--index", "stream(flat,lpq4)", "--n", "500", "--d", "24",
            "--requests", "6", "--batch", "8", "--mutate",
            "--cache", "16", "--hot-repeat", "2",
            "--telemetry-out", str(out),
        ])
        tel = json.loads(out.read_text())
        c = tel["counters"]
        assert tel["meta"]["runtime"]["profile"]
        # memtable-only upsert skipped its re-plan; the real delete did not
        assert c["replans_avoided"] >= 1
        assert c["replans"] >= 1
        assert c.get("cache_hits", 0) or any(
            e.get("cache") == "hit" for e in tel["events"]
            if e["type"] == "request")
        assert c["queries_served"] > 0
        # the session recorded every Searcher call's span tree
        spans = [e for e in tel["events"] if e["type"] == "host_span"]
        calls = {e["id"] for e in spans if e["name"] == "searcher.call"}
        assert calls
        for e in spans:
            if e["name"].startswith("searcher.") and e["id"] not in calls:
                assert e["parent"] in calls

    def test_failed_maintenance_exits_nonzero(self, monkeypatch):
        """A background round that raises is counted, and the session
        then exits non-zero instead of reporting success."""
        from repro.launch import serve

        def broken(self):
            raise RuntimeError("injected maintenance fault")

        monkeypatch.setattr(MaintenanceScheduler, "run_once", broken)
        with pytest.raises(SystemExit, match="maintenance"):
            serve.main([
                "--index", "stream(flat,lpq8)", "--n", "300", "--d", "16",
                "--requests", "6", "--batch", "8", "--maintenance",
                "--maintenance-interval", "0.001",
            ])

    def test_overload_degrades_and_sheds_cleanly(self, tmp_path):
        from repro.launch import serve

        out = tmp_path / "tel.json"
        serve.main([
            "--index", "flat,lpq8", "--n", "500", "--d", "24",
            "--requests", "8", "--batch", "8",
            "--admission", "--rate", "64", "--burst", "20",
            "--max-queue", "4",
            "--telemetry-out", str(out),
        ])
        tel = json.loads(out.read_text())
        c = tel["counters"]
        # the ladder engaged: some degraded, some shed, none crashed
        assert c["admission_shed"] >= 1
        assert c["admission_degrade"] >= 1
        assert c["admission_shed_queries"] >= 8
        sheds = [e for e in tel["events"] if e["type"] == "shed"]
        assert len(sheds) == c["admission_shed"]
        # served + shed covers every query request issued
        assert c["queries_served"] + c["admission_shed_queries"] == 64


# ---------------------------------------------------------------------------
# trend gate


class TestTrendGate:
    def _doc(self):
        return {
            "meta": {"smoke": True, "backend": "cpu",
                     "runtime": {"profile": "ci-cpu", "backend": "cpu",
                                 "interpret": True, "deterministic": True}},
            "cells": {"flat,lpq8": {"qps": 1000.0, "recall_at_10": 0.95,
                                    "p95_ms": 3.0}},
        }

    def test_walk_classifies_metrics(self):
        trend = pytest.importorskip("benchmarks.trend")
        got = {p: kind for p, kind, _v in trend.walk_metrics(self._doc())}
        assert got == {"cells/flat,lpq8/qps": "qps",
                       "cells/flat,lpq8/recall_at_10": "recall"}

    def test_gate_trips_on_injected_regression(self, tmp_path):
        trend = pytest.importorskip("benchmarks.trend")
        base_dir = tmp_path / "baseline"
        base_dir.mkdir()
        doc = self._doc()
        (base_dir / "BENCH_x.json").write_text(json.dumps(doc))
        fresh = tmp_path / "BENCH_x.json"

        fresh.write_text(json.dumps(doc))
        (r,) = trend.run_gate([str(fresh)], str(base_dir))
        assert r["status"] == "compared" and not r["regressions"]

        doc["cells"]["flat,lpq8"]["qps"] = 700.0
        doc["cells"]["flat,lpq8"]["recall_at_10"] = 0.93
        fresh.write_text(json.dumps(doc))
        (r,) = trend.run_gate([str(fresh)], str(base_dir))
        assert sorted(g["kind"] for g in r["regressions"]) == [
            "qps", "recall"]

    def test_gate_refuses_cross_backend(self, tmp_path):
        trend = pytest.importorskip("benchmarks.trend")
        base_dir = tmp_path / "baseline"
        base_dir.mkdir()
        doc = self._doc()
        (base_dir / "BENCH_x.json").write_text(json.dumps(doc))
        doc["meta"]["runtime"]["interpret"] = False
        doc["cells"]["flat,lpq8"]["qps"] = 1.0   # huge "regression"...
        fresh = tmp_path / "BENCH_x.json"
        fresh.write_text(json.dumps(doc))
        (r,) = trend.run_gate([str(fresh)], str(base_dir))
        assert r["status"] == "skipped"          # ...refused, not failed

    def test_self_test(self, capsys):
        trend = pytest.importorskip("benchmarks.trend")
        trend._self_test()
        assert "self-test OK" in capsys.readouterr().out
