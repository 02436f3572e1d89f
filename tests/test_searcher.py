"""Searcher query-plan API (DESIGN.md §9): plan-once/execute-many parity
with eager search across every kind and mixed batch sizes, compilation
bucketing (trace counts), plan-time validation, the rerank tail's recall
recovery, sharded-vs-unsharded id parity, the ``+rN`` factory suffix, and
the save/load -> searcher round-trip."""

import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.preserve import recall_at_k
from repro.data import synthetic
from repro.knn import (
    Rerank,
    SearchParams,
    Searcher,
    load_index,
    make_index,
    parse_factory,
)
from repro.runtime import telemetry

K = 10

# per-kind factory string + build overrides kept small for CI; the lpq4
# arms exercise packed stores through the plan path
CASES = {
    "flat": ("flat,lpq4+r32", {}),
    "ivf": ("ivf8,lpq4", {"kmeans_iters": 4}),
    "hnsw": ("hnsw8,lpq8@gaussian:3", {"ef_construction": 40, "batch_size": 128}),
    "graph": ("graph16,lpq8@gaussian:3", {"n_seeds": 16}),
    "pq": ("pq16+lpq,r32", {"kmeans_iters": 4}),
}

SP = SearchParams(nprobe=8, ef_search=40, chunk=256)


@pytest.fixture(scope="module")
def corpus_queries():
    corpus = jax.random.normal(jax.random.PRNGKey(0), (512, 32)) * 0.05
    queries = jax.random.normal(jax.random.PRNGKey(1), (32, 32)) * 0.05
    return corpus, queries


@pytest.fixture(scope="module")
def built(corpus_queries):
    corpus, _q = corpus_queries
    return {
        kind: make_index(factory, corpus, key=jax.random.PRNGKey(0), **over)
        for kind, (factory, over) in CASES.items()
    }


# --------------------------------------------------------------------------
# plan/execute parity + bucketing
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(CASES))
def test_one_plan_serves_mixed_batches(kind, corpus_queries, built):
    """The acceptance property: a plan built once serves batch sizes
    1 / 7 / 32 with ids identical to eager ``index.search``."""
    _corpus, queries = corpus_queries
    idx = built[kind]
    searcher = idx.searcher(K, SP, batch_sizes=(1, 8, 32))
    for q in (queries[:1], queries[:7], queries):
        eager = idx.search(q, K, SP)
        planned = searcher(q)
        np.testing.assert_array_equal(
            np.asarray(eager.ids), np.asarray(planned.ids)
        )
        np.testing.assert_allclose(
            np.asarray(eager.scores), np.asarray(planned.scores), rtol=1e-6
        )
        # the Searcher accounting block rides on every result
        for field in ("bucket", "padded_q", "shards", "reranked"):
            assert field in planned.stats, (kind, field)
    # 7 queries pad into the 8-bucket
    assert searcher(queries[:7]).stats["bucket"] == 8
    assert searcher(queries[:7]).stats["padded_q"] == 1


def test_same_bucket_calls_do_not_retrace(corpus_queries, built):
    """Repeated same-bucket requests reuse the compiled executable; a new
    bucket compiles exactly one more."""
    _corpus, queries = corpus_queries
    searcher = built["flat"].searcher(K, SP, batch_sizes=(8, 32))
    for _ in range(4):
        searcher(queries[:5])                    # all pad into bucket 8
    assert searcher.trace_counts == {8: 1}
    searcher(queries[:20])                       # bucket 32: one new trace
    searcher(queries[:32])
    assert searcher.trace_counts == {8: 1, 32: 1}


def _call_tree(spans):
    """{call id: (call record, [its children])} of ``searcher.call`` spans."""
    calls = {r["id"]: (r, []) for r in spans if r["name"] == "searcher.call"}
    for r in spans:
        if r["parent"] in calls:
            calls[r["parent"]][1].append(r)
    return calls


def test_call_spans_nest_under_one_request(corpus_queries, built):
    """Each call records one ``searcher.call`` holding its phases, all of
    one request; ``built`` marks only a bucket's first dispatch."""
    _corpus, queries = corpus_queries
    searcher = built["flat"].searcher(K, SP, batch_sizes=(8, 32))
    t0 = time.perf_counter_ns()
    with telemetry.recording():
        for n in (5, 5, 20, 40):
            searcher(queries[:n] if n <= 32 else jnp.tile(queries, (2, 1))[:n])
    spans = telemetry.recorded_spans(t0)
    calls = _call_tree(spans)
    assert len(calls) == 4
    assert all(r["parent"] in calls for r in spans
               if r["name"] != "searcher.call")
    built_flags = []
    for call, children in sorted(calls.values(), key=lambda c: c[0]["id"]):
        assert call["parent"] is None
        assert {c["request"] for c in children} == {call["request"]}
        assert all(call["start_ns"] <= c["start_ns"] <= c["end_ns"]
                   <= call["end_ns"] for c in children)
        names = [c["name"] for c in sorted(children, key=lambda c: c["id"])]
        slices = call["fields"]["slices"]
        assert names == (["searcher.prepare"]
                         + ["searcher.dispatch", "searcher.assemble",
                            "searcher.wait"] * slices
                         + ["searcher.assemble"])
        built_flags.append([c["fields"].get("built", False)
                            for c in children
                            if c["name"] == "searcher.dispatch"])
    assert [c[0]["fields"] for c in sorted(calls.values(),
                                           key=lambda c: c[0]["id"])] == [
        {"queries": 5, "slices": 1}, {"queries": 5, "slices": 1},
        {"queries": 20, "slices": 1}, {"queries": 40, "slices": 2}]
    # bucket 8 built on the first call, 32 on the third; 40 queries run
    # as 32 + 8, both built already
    assert built_flags == [[True], [False], [True], [False, False]]


def test_calls_keep_no_spans_while_recording_is_off(corpus_queries, built):
    _corpus, queries = corpus_queries
    searcher = built["flat"].searcher(K, SP, batch_sizes=(8,))
    t0 = time.perf_counter_ns()
    searcher(queries[:5])
    assert telemetry.recorded_spans(t0) == []


def test_named_scopes_reach_the_compiled_ops(built, monkeypatch):
    """The ivf plan's phases and the fused kernel's codes pad carry their
    ``named_scope`` into the compiled program's op metadata."""
    text = built["ivf"].searcher(K, SP, batch_sizes=(8,)).lower(8) \
        .compile().as_text()
    for name in ("ivf.coarse", "ivf.gather", "ivf.fine"):
        assert f"/{name}/" in text, name
    # steer the flat scan onto the fused kernel, in interpret mode: the
    # engine takes it only on a TPU
    from repro.kernels import ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    idx = make_index("flat,lpq8", np.asarray(
        jax.random.normal(jax.random.PRNGKey(2), (1000, 32))))
    text = idx.searcher(K, SearchParams(chunk=256), batch_sizes=(8,)) \
        .lower(8).compile().as_text()
    assert "fused_topk" in text
    assert "/kernels.pad_codes/" in text


def test_fused_merge_counter_rides_the_stats_unread(monkeypatch):
    """On the fused kernel's path a call's stats carry the kernel's merge
    counter as device values, summed over the request's slices, and the
    ``searcher.call`` span's fields carry the same values; the one-shot
    search carries them too."""
    from repro.kernels import ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    idx = make_index("flat,lpq8", np.asarray(
        jax.random.normal(jax.random.PRNGKey(2), (1000, 32))))
    queries = jax.random.normal(jax.random.PRNGKey(3), (12, 32))
    searcher = idx.searcher(K, SearchParams(chunk=256), batch_sizes=(8,))
    t0 = time.perf_counter_ns()
    with telemetry.recording():
        res = searcher(queries)                     # two slices of 8
    call, = [s for s in telemetry.recorded_spans(t0)
             if s["name"] == "searcher.call"]
    steps, tiles = res.stats["merge_steps"], res.stats["merge_tiles"]
    assert isinstance(steps, jax.Array) and isinstance(tiles, jax.Array)
    assert call["fields"]["merge_steps"] is steps
    assert call["fields"]["merge_tiles"] is tiles
    # 4 corpus tiles of 256 rows for each slice's query tile; the first
    # tile of each takes k steps, and no tile takes more
    assert int(tiles) == 8
    assert 2 * K <= int(steps) <= 8 * K
    one = idx.search(queries[:8], K, SearchParams(chunk=256))
    assert int(one.stats["merge_tiles"]) == 4


# the graph walks close over their store inside the jitted beam search,
# below the Searcher, so their buckets still carry it as a constant
@pytest.mark.parametrize("kind", ["flat", "ivf", "pq"])
def test_bucket_takes_the_index_as_arguments(kind, built):
    """The compiled bucket receives the index's arrays as arguments: jit
    would otherwise bake them into the program as constants, a copy of
    the corpus per bucket."""
    idx = built[kind]
    lowered = idx.searcher(K, SP, batch_sizes=(8,)).lower(8)
    n_args = len(jax.tree.leaves(lowered.args_info))
    assert n_args >= 2, kind                     # queries + index arrays
    rows = idx.store.data if hasattr(idx.store, "data") else idx.store.codes
    shape = "x".join(str(x) for x in rows.shape)
    dtype = {"int8": "i8", "uint8": "ui8", "float32": "f32"}[str(rows.dtype)]
    assert f"tensor<{shape}x{dtype}>" in lowered.as_text(), kind
    assert "dense<" not in "".join(
        line for line in lowered.as_text().splitlines()
        if f"tensor<{shape}x" in line and "constant" in line), kind


def test_oversized_requests_run_in_max_bucket_slices(corpus_queries, built):
    _corpus, queries = corpus_queries
    idx = built["flat"]
    searcher = idx.searcher(K, SP, batch_sizes=(1, 8))
    res = searcher(queries[:27])                 # 8+8+8+(3 padded to 8)
    assert res.ids.shape == (27, K)
    assert searcher.trace_counts == {8: 1}       # every slice hit one bucket
    np.testing.assert_array_equal(
        np.asarray(res.ids), np.asarray(idx.search(queries[:27], K, SP).ids)
    )
    assert res.stats["padded_q"] == 5


# --------------------------------------------------------------------------
# plan-time validation
# --------------------------------------------------------------------------

def test_plan_time_validation(corpus_queries, built):
    _corpus, queries = corpus_queries
    idx = built["flat"]
    with pytest.raises(ValueError, match="k must be a positive int"):
        idx.searcher(0)
    with pytest.raises(ValueError, match="k must be a positive int"):
        idx.searcher(-3)
    with pytest.raises(ValueError, match="exceeds the corpus size"):
        idx.searcher(idx.n + 1)
    with pytest.raises(ValueError, match="chunk must be a positive int"):
        idx.searcher(K, SearchParams(chunk=0))
    with pytest.raises(ValueError, match="nprobe must be a positive int"):
        idx.searcher(K, SearchParams(nprobe=-1))
    with pytest.raises(ValueError, match="ef_search must be a positive int"):
        idx.searcher(K, SearchParams(ef_search=0))
    with pytest.raises(ValueError, match="batch_sizes"):
        idx.searcher(K, batch_sizes=())
    searcher = idx.searcher(K, SP)
    with pytest.raises(ValueError, match="empty query batch"):
        searcher(np.zeros((0, 32), np.float32))
    with pytest.raises(ValueError, match="query dim"):
        searcher(np.zeros((4, 16), np.float32))
    with pytest.raises(ValueError, match=r"queries must be \[Q, d\]"):
        searcher(np.zeros((32,), np.float32))


def test_rerank_argument_validation(corpus_queries, built):
    corpus, _q = corpus_queries
    plain = make_index("flat,lpq8@gaussian:3", corpus)
    with pytest.raises(ValueError, match="no rerank store"):
        plain.searcher(K, rerank=64)
    with pytest.raises(ValueError, match="no rerank store"):
        plain.searcher(K, rerank=True)
    from repro.engine import CodeStore

    with pytest.raises(ValueError, match="id space"):
        plain.searcher(K, rerank=Rerank(64, CodeStore.dense(corpus[:100])))
    # explicit Rerank over a matching store works without a +rN build
    s = plain.searcher(K, rerank=Rerank(64, CodeStore.dense(corpus)))
    assert s.rerank is not None and s.rerank.depth == 64


# --------------------------------------------------------------------------
# rerank: §3.4 recall recovery
# --------------------------------------------------------------------------

def test_rerank_strictly_improves_lpq4_recall():
    """``flat,lpq4+r32`` > ``flat,lpq4`` recall@10 on the synthetic
    benchmark corpus (the acceptance criterion)."""
    corpus, queries, metric = synthetic.load("product", 2000, 64)
    corpus, queries = corpus[:, :64], queries[:64, :64]
    gt = np.asarray(make_index("flat", corpus, metric=metric).search(queries, K).ids)
    plain = make_index("flat,lpq4", corpus, metric=metric)
    rer = make_index("flat,lpq4+r32", corpus, metric=metric)
    r_plain = float(recall_at_k(gt, plain.searcher(K)(queries).ids))
    r_rer = float(recall_at_k(gt, rer.searcher(K)(queries).ids))
    assert r_rer > r_plain, (r_plain, r_rer)
    # the tail reports its accounting
    stats = rer.searcher(K)(queries[:8]).stats
    assert stats["reranked"] > 0 and stats["rerank_bits"] == 32


def test_full_depth_rerank_equals_exact_search(corpus_queries):
    """Rerank over the whole corpus == the fp32 exhaustive scan: the
    quantized stage only selects candidates, the fp32 stage orders them."""
    corpus, queries = corpus_queries
    gt = make_index("flat", corpus).search(queries, K)
    rer = make_index("flat,lpq8@gaussian:3,r32", corpus)
    res = rer.searcher(K, rerank=rer.n)(queries)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(gt.ids))
    np.testing.assert_allclose(
        np.asarray(res.scores), np.asarray(gt.scores), rtol=1e-5
    )


def test_rerank_composes_with_every_kind(corpus_queries, built):
    """hnsw/graph walk + compiled rerank tail; ivf probe + tail; pq ADC +
    tail — the tail must keep ids within the walked candidate set and
    never lose recall against ground truth."""
    corpus, queries = corpus_queries
    gt = np.asarray(make_index("flat", corpus).search(queries, K).ids)
    from repro.engine import CodeStore

    store = CodeStore.dense(corpus)
    for kind, idx in built.items():
        base = idx.searcher(K, SP, rerank=False)(queries)
        rer = idx.searcher(K, SP, rerank=Rerank(4 * K, store))(queries)
        r_base = float(recall_at_k(gt, base.ids))
        r_rer = float(recall_at_k(gt, rer.ids))
        assert r_rer >= r_base - 1e-6, (kind, r_base, r_rer)


# --------------------------------------------------------------------------
# sharding
# --------------------------------------------------------------------------

def test_sharded_plan_matches_unsharded(corpus_queries):
    """Row-sharded flat plan == unsharded ids/scores over the devices this
    host exposes (1-device mesh degenerates to the same merge path)."""
    corpus, queries = corpus_queries
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    for factory in ("flat", "flat,lpq8@gaussian:3", "flat,lpq4+r32"):
        idx = make_index(factory, corpus)
        un = idx.searcher(K, SP)(queries)
        sh = idx.searcher(K, SP, shards=mesh)(queries)
        np.testing.assert_array_equal(np.asarray(un.ids), np.asarray(sh.ids))
        np.testing.assert_allclose(
            np.asarray(un.scores), np.asarray(sh.scores), rtol=1e-6
        )
        assert sh.stats["shards"] == len(jax.devices())


def test_sharded_plan_every_kind_matches_unsharded(corpus_queries, built):
    """Every registry kind now shards (lists / rows / replicated fan-out)
    and must bit-match its unsharded twin — ids AND scores."""
    corpus, queries = corpus_queries
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    for kind, idx in built.items():
        un = idx.searcher(K, SP)(queries)
        sh = idx.searcher(K, SP, shards=mesh)(queries)
        np.testing.assert_array_equal(
            np.asarray(un.ids), np.asarray(sh.ids), err_msg=kind
        )
        np.testing.assert_array_equal(
            np.asarray(un.scores), np.asarray(sh.scores), err_msg=kind
        )
        assert sh.stats["placement"] in (
            "rows", "lists", "segments", "replicated"
        ), kind


def test_ivf_gather_in_query_blocks_matches(corpus_queries, built,
                                            monkeypatch):
    """Under a tiny gather budget the ivf fine scan runs one query per
    block, unsharded and list-placed, and answers exactly as in one
    block."""
    from repro.engine import scorer

    _corpus, queries = corpus_queries
    idx = built["ivf"]
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    want = idx.searcher(K, SP)(queries)
    monkeypatch.setattr(scorer, "GATHER_BYTES", 1)
    jax.clear_caches()
    try:
        for shards in (None, mesh):
            got = idx.searcher(K, SP, shards=shards)(queries)
            np.testing.assert_array_equal(np.asarray(want.ids),
                                          np.asarray(got.ids))
            np.testing.assert_array_equal(np.asarray(want.scores),
                                          np.asarray(got.scores))
    finally:
        jax.clear_caches()


def test_sharded_plan_rejects_mismatched_placement(corpus_queries, built):
    """A pinned placement must match the index's shard unit — an ivf plan
    refuses a row placement, a graph walk refuses anything non-replicated."""
    from repro.dist.placement import Placement

    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    n_dev = len(jax.devices())
    with pytest.raises(ValueError, match="place whole lists"):
        built["ivf"].plan(K, SP, mesh=mesh,
                          placement=Placement.rows(built["ivf"].n, n_dev))
    with pytest.raises(ValueError, match="only replicates"):
        built["graph"].plan(K, SP, mesh=mesh,
                            placement=Placement.rows(built["graph"].n, n_dev))


@pytest.mark.slow
def test_sharded_plan_multihost_subprocess():
    """≥2-way host mesh: forces XLA_FLAGS device multiplication in a
    subprocess (the in-process backend is already initialized 1-device)."""
    prog = textwrap.dedent("""
        import jax, numpy as np
        from repro.knn import make_index, SearchParams
        assert len(jax.devices()) == 2, jax.devices()
        corpus = np.random.RandomState(0).randn(300, 16).astype("float32")
        queries = np.random.RandomState(1).randn(9, 16).astype("float32")
        mesh = jax.make_mesh((2,), ("data",))
        # chunk=128 over 150-row shards forces tile padding whose gids
        # alias the next shard's rows (regression: they must be id-masked
        # locally); the int4 arm makes unmasked zero rows actually score
        for factory in ("flat,lpq8@gaussian:3", "flat,lpq4"):
            idx = make_index(factory, corpus)
            un = idx.searcher(20, SearchParams(chunk=128))(queries)
            sh = idx.searcher(20, SearchParams(chunk=128), shards=mesh)(queries)
            np.testing.assert_array_equal(np.asarray(un.ids), np.asarray(sh.ids))
            assert sh.stats["shards"] == 2
        print("OK")
    """)
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "PYTHONPATH": "src" + os.pathsep + os.environ.get("PYTHONPATH", "")}
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# --------------------------------------------------------------------------
# factory suffix + save/load round-trip
# --------------------------------------------------------------------------

def test_rerank_factory_fragment_parses_and_roundtrips():
    spec = parse_factory("flat,lpq4+r32")
    assert spec.rerank_bits == 32 and spec.quant.bits == 4
    assert spec.to_factory() == "flat,lpq4+r32"
    spec = parse_factory("ivf64,lpq8+r8,l2")
    assert spec.rerank_bits == 8 and spec.metric == "l2"
    assert parse_factory(spec.to_factory()) == spec
    spec = parse_factory("pq16+lpq,r32")
    assert spec.rerank_bits == 32 and spec.params["lpq_tables"]
    assert parse_factory(spec.to_factory()) == spec
    assert parse_factory("flat,lpq8").rerank_bits is None


@pytest.mark.parametrize("bad", ["flat,lpq4+r16", "flat,r0", "flat,r32,r8",
                                 "flat,lpq4+r32,r8"])
def test_rerank_factory_rejects_garbage(bad):
    with pytest.raises(ValueError):
        parse_factory(bad)


def test_rerank_store_counted_in_memory(corpus_queries):
    corpus, _q = corpus_queries
    plain = make_index("flat,lpq4", corpus)
    rer = make_index("flat,lpq4+r32", corpus)
    # honest accounting: +r32 carries the fp32 corpus on top of the codes
    assert rer.memory_bytes() >= plain.memory_bytes() + corpus.size * 4


@pytest.mark.parametrize("kind", sorted(CASES))
def test_save_load_searcher_roundtrip(kind, corpus_queries, built, tmp_path):
    """Every registered kind: save -> load_index -> plan on the loaded
    copy -> ids/scores identical to the pre-save plan (incl. packed lpq4
    stores and +rN rerank stores)."""
    _corpus, queries = corpus_queries
    idx = built[kind]
    path = str(tmp_path / f"{kind}.npz")
    idx.save(path)
    restored = load_index(path)
    assert restored.kind == kind
    assert (restored.rerank_store is None) == (idx.rerank_store is None)
    a = idx.searcher(K, SP, batch_sizes=(8, 32))(queries)
    b = restored.searcher(K, SP, batch_sizes=(8, 32))(queries)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_allclose(np.asarray(a.scores), np.asarray(b.scores),
                               rtol=1e-6)
    assert restored.memory_bytes() == idx.memory_bytes()


# --------------------------------------------------------------------------
# serving loop (in-process smoke: the queue/percentile/aggregation path)
# --------------------------------------------------------------------------

def test_serve_main_runs_mixed(capsys):
    from repro.launch import serve

    serve.main(["--index", "flat,lpq4+r32", "--n", "1024", "--d", "32",
                "--batch", "8", "--requests", "6", "--mixed"])
    out = capsys.readouterr().out
    assert "QPS" in out
    assert "p95" in out and "p99" in out
    assert "stats/request mean" in out
    # mixed traffic pads 1-query and 2-query requests into buckets
    assert "padded_q=" in out
