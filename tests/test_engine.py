"""Scoring-engine tests: CodeStore storage/accounting, int4 pack round-trip
and packed-vs-unpacked score parity, fused score+top-k kernel parity vs the
jnp oracles + ``jax.lax.top_k``, the centralized pad/mask contract (the L2
zero-sentinel regression), lpq4 factory strings, and the uniform per-search
stats every kind emits.  Kernels run in interpret mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # no hypothesis on this container: see pyproject [test]
    from _hypothesis_compat import given, settings, strategies as st

from repro import engine
from repro.core import pack as PK
from repro.core import quant as Qz
from repro.core.preserve import recall_at_k
from repro.kernels import ops as K
from repro.kernels import ref
from repro.knn import QuantSpec, SearchParams, make_index


# --------------------------------------------------------------------------
# int4 packing: round-trip + packed-vs-unpacked score parity (properties)
# --------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16), n=st.integers(1, 48),
       half_d=st.integers(1, 24))
def test_int4_roundtrip_through_store(seed, n, half_d):
    key = jax.random.PRNGKey(seed)
    codes = jax.random.randint(key, (n, half_d * 2), -8, 8, dtype=jnp.int8)
    params = Qz.QuantParams(
        lo=jnp.full((half_d * 2,), -1.0), hi=jnp.full((half_d * 2,), 1.0),
        zero=jnp.zeros((half_d * 2,)), bits=4, scheme="absmax",
    )
    store = engine.CodeStore.from_codes(codes, params, pack=True)
    assert store.data.dtype == jnp.uint8
    assert store.data.shape == (n, half_d)
    np.testing.assert_array_equal(np.asarray(store.unpacked()),
                                  np.asarray(codes))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16), n=st.integers(1, 64),
       d=st.integers(1, 40), metric=st.sampled_from(["ip", "l2"]))
def test_packed_scores_match_unpacked(seed, n, d, metric):
    """qmip4/ql24 over packed bytes == qmip/ql2 over full-width codes."""
    d = d * 2  # kernels take the even/odd split; odd-d goes via CodeStore
    kq, kx = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.randint(kq, (3, d), -8, 8, dtype=jnp.int8)
    x = jax.random.randint(kx, (n, d), -8, 8, dtype=jnp.int8)
    packed = PK.pack_int4(x)
    if metric == "ip":
        got, want = K.qmip4(q, packed), ref.qmip_ref(q, x)
    else:
        got, want = K.ql24(q, packed), ref.ql2_ref(q, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------
# fused score+top-k kernel vs oracle scoring + lax.top_k
# --------------------------------------------------------------------------

FUSED_SHAPES = [
    (1, 1, 8),          # degenerate
    (1, 700, 64),       # single query, pad tail
    (7, 333, 100),      # ragged everything
    (37, 1000, 96),
    (9, 513, 128),      # one row over a tile
]


def _assert_topk_consistent(scores, ids, full, k):
    """Exact score parity; ids must reproduce their reported score (ties
    may legally reorder between selection algorithms)."""
    want_s = np.sort(np.asarray(full), axis=1)[:, ::-1][:, :k]
    np.testing.assert_array_equal(np.asarray(scores), want_s)
    got_i = np.asarray(ids)
    got_s = np.asarray(scores)
    for r in range(got_i.shape[0]):
        assert (got_i[r] >= 0).all()
        np.testing.assert_array_equal(np.asarray(full)[r][got_i[r]], got_s[r])


@pytest.mark.parametrize("q_rows,n_rows,d", FUSED_SHAPES)
@pytest.mark.parametrize("metric", ["ip", "l2"])
def test_fused_topk_matches_ref_int8(q_rows, n_rows, d, metric):
    kq, kx = jax.random.split(jax.random.PRNGKey(q_rows * 31 + n_rows))
    q = jax.random.randint(kq, (q_rows, d), -128, 128, dtype=jnp.int8)
    x = jax.random.randint(kx, (n_rows, d), -128, 128, dtype=jnp.int8)
    k = min(10, n_rows)
    s, i = K.fused_topk(q, x, k, metric)
    full = ref.qmip_ref(q, x) if metric == "ip" else ref.ql2_ref(q, x)
    _assert_topk_consistent(s, i, full, k)
    # and against lax.top_k end-to-end (scores sorted identically)
    ls, _li = jax.lax.top_k(full.astype(jnp.float32), k)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(ls))


@pytest.mark.parametrize("metric", ["ip", "l2"])
def test_fused_topk_matches_ref_int4_packed(metric):
    kq, kx = jax.random.split(jax.random.PRNGKey(5))
    q = jax.random.randint(kq, (6, 50), -8, 8, dtype=jnp.int8)
    x = jax.random.randint(kx, (777, 50), -8, 8, dtype=jnp.int8)
    s, i = K.fused_topk(q, PK.pack_int4(x), 17, metric, packed=True)
    full = ref.qmip_ref(q, x) if metric == "ip" else ref.ql2_ref(q, x)
    _assert_topk_consistent(s, i, full, 17)


def test_fused_topk_fp32_matches_xla():
    kq, kx = jax.random.split(jax.random.PRNGKey(7))
    q = jax.random.normal(kq, (5, 48))
    x = jax.random.normal(kx, (600, 48))
    for metric in ("ip", "l2"):
        s, i = K.fused_topk(q, x, 12, metric)
        ws, _ = K.fused_topk(q, x, 12, metric, use_pallas=False)
        np.testing.assert_allclose(np.asarray(s), np.asarray(ws),
                                   rtol=1e-5, atol=1e-5)


def test_fused_topk_l2_padding_never_wins():
    """The zero-sentinel regression: every corpus row is far from the
    origin, so an unmasked zero pad row would out-score all of them under
    negated L2.  The engine id-masks in-kernel — only valid ids return."""
    x = jnp.ones((1000, 16), jnp.float32) * 50.0       # pads to 1024 rows
    q = jnp.ones((4, 16), jnp.float32) * 49.0
    s, i = K.fused_topk(q, x, 10, "l2")
    ids = np.asarray(i)
    assert ids.min() >= 0 and ids.max() < 1000
    st = engine.CodeStore.dense(x)
    _s2, i2, _ = engine.topk(q, st, 10, "l2")
    assert np.asarray(i2).max() < 1000 and np.asarray(i2).min() >= 0


def test_fused_merge_orders_ties_by_id():
    """Equal scores leave the in-kernel merge lower id first, whichever
    lane holds them — the order ``lax.top_k`` gives over an id-ordered
    scan, so fused, XLA and sharded plans agree bit for bit.  The tile's
    ids all follow the best set's, as the kernel's id-ordered grid makes
    them; once the candidates run out, the sort pads with (NEG, -1)."""
    from repro.kernels.fused_topk import NEG, _merge_tile, _sort_best

    best_s = jnp.array([[5.0] + [NEG] * 4])          # the running [1, k]
    best_i = jnp.array([[3] + [-1] * 4], jnp.int32)
    s = jnp.array([[5.0, 7.0, 5.0, NEG]])
    ids = jnp.array([[12, 15, 9, -1]], jnp.int32)
    bs, bi, steps = _merge_tile(best_s, best_i, s, ids, 5)
    assert int(steps) == 3                           # three beat the NEG pads
    out_s, out_i = _sort_best(bs, bi, 5)
    np.testing.assert_array_equal(np.asarray(out_s),
                                  [[7.0, 5.0, 5.0, 5.0, NEG]])
    np.testing.assert_array_equal(np.asarray(out_i), [[15, 3, 9, 12, -1]])


def test_fused_merge_evicts_the_largest_id_among_the_worst():
    """A full, unsorted best set: only a tile candidate strictly above the
    k-th best enters (an equal one has a larger id, so ranks after it),
    and it evicts the lowest score with the largest id.  One candidate
    beats the k-th best, so the merge takes one step."""
    from repro.kernels.fused_topk import NEG, _merge_tile, _sort_best

    best_s = jnp.array([[5.0, 8.0, 5.0]])
    best_i = jnp.array([[2, 0, 4]], jnp.int32)
    s = jnp.array([[5.0, 6.0, 5.0, NEG]])
    ids = jnp.array([[9, 11, 7, -1]], jnp.int32)
    bs, bi, steps = _merge_tile(best_s, best_i, s, ids, 3)
    assert int(steps) == 1
    out_s, out_i = _sort_best(bs, bi, 3)
    np.testing.assert_array_equal(np.asarray(out_s), [[8.0, 6.0, 5.0]])
    np.testing.assert_array_equal(np.asarray(out_i), [[0, 11, 2]])
    # no candidate above the k-th best: no step, the set is unchanged
    bs, bi, steps = _merge_tile(best_s, best_i, jnp.full_like(s, 5.0), ids, 3)
    assert int(steps) == 0
    np.testing.assert_array_equal(np.asarray(bi), np.asarray(best_i))


# the corpus orders the data-driven merge must answer exactly in; each is
# (rows, queries, k, mask rows kept or None)
MERGE_ORDERS = {
    "ascending": (1300, 3, 100, None),     # every tile engages k steps
    "descending": (1300, 3, 100, None),    # only the first tile engages
    "ties": (1300, 3, 100, None),          # k-th best tied across tiles
    "few_valid": (37, 3, 50, None),        # fewer rows than k
    "masked": (1300, 3, 100, 40),          # a mask leaves fewer than k
    "k1": (1300, 3, 1, None),
    "k100_ragged": (1300, 130, 100, None),  # N % bn != 0, Q % bq != 0
}
MERGE_CODES = {"int8-ip": (8, "ip"), "int8-l2": (8, "l2"),
               "int4-ip": (4, "ip"), "int4-l2": (4, "l2")}


def _full_scores(q, x, metric):
    return ref.qmip_ref(q, x) if metric == "ip" else ref.ql2_ref(q, x)


def _ordered_corpus(order, n, n_q, bits, metric, seed=11):
    """Codes in ``order`` for query 0 (the others see them at random)."""
    lo, hi = (-128, 127) if bits == 8 else (-8, 7)
    kq, kx = jax.random.split(jax.random.PRNGKey(seed))
    d = 16
    q = jax.random.randint(kq, (n_q, d), lo, hi + 1, dtype=jnp.int8)
    x = np.array(jax.random.randint(kx, (n, d), lo, hi + 1, dtype=jnp.int8))
    if order in ("ascending", "descending"):
        s0 = np.asarray(_full_scores(q[:1], jnp.asarray(x), metric))[0]
        rank = np.argsort(s0, kind="stable")
        x = x[rank if order == "ascending" else rank[::-1]]
    elif order == "ties":
        # 300 equal rows straddle the tile boundary at 512: query 0 sits on
        # them, so they hold its k-th best under l2; under ip only rows
        # with a larger first code beat them
        tie = np.zeros(d, np.int8)
        tie[0] = hi - 1
        x[400:700] = tie
        q = q.at[0].set(jnp.asarray(tie))
    return q, jnp.asarray(x)


@pytest.mark.parametrize("codes", sorted(MERGE_CODES))
@pytest.mark.parametrize("order", sorted(MERGE_ORDERS))
def test_fused_merge_is_exact(order, codes):
    """The data-driven merge returns what the XLA reference does, bit for
    bit (scores and ids), whatever the corpus order: ascending (every
    tile engages k steps), descending (only the first does), ties at
    the k-th best across a tile boundary, fewer rows than k, a mask
    that leaves fewer than k, k = 1, and ragged Q and N at k = 100."""
    bits, metric = MERGE_CODES[codes]
    n, n_q, k, kept = MERGE_ORDERS[order]
    q, x = _ordered_corpus(order, n, n_q, bits, metric)
    mask = None
    if kept is not None:
        mask = jnp.zeros(n, bool).at[
            jax.random.permutation(jax.random.PRNGKey(3), n)[:kept]].set(True)
    packed = bits == 4
    corpus = PK.pack_int4(x) if packed else x
    got = K.fused_topk(q, corpus, k, metric, packed=packed, mask=mask,
                       interpret=True)
    want = K.fused_topk(q, corpus, k, metric, packed=packed, mask=mask,
                        use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    if kept is not None:
        assert (np.asarray(got[1])[:, kept:] == -1).all()


def _ascending_ip(n):
    """One query and n int8 rows whose inner products with it are
    distinct and rise with the row id: 100 * x0 + x1 = id - n // 2."""
    v = np.arange(n) - n // 2
    x0 = np.round(v / 100).astype(np.int64)
    x = np.zeros((n, 8), np.int8)
    x[:, 0], x[:, 1] = x0, v - 100 * x0
    q = np.zeros((1, 8), np.int8)
    q[0, :2] = (100, 1)
    return jnp.asarray(q), jnp.asarray(x)


@pytest.mark.parametrize("n,k", [(1536, 100), (1300, 7), (40, 50)])
def test_fused_merge_counts_follow_the_order(n, k):
    """The kernel's counter: descending rows take min(k, valid rows) merge
    steps, all in the first tile; ascending rows take k in every tile
    (each tile holds at least k).  Either way every corpus tile is
    visited once per query tile."""
    from repro.kernels.fused_topk import BN

    q, x = _ascending_ip(n)
    tiles = -(-n // BN)
    for rows, steps in ((x[::-1], min(k, n)),
                        (x, min(k, n) * tiles)):
        s, i, (got_steps, got_tiles) = K.fused_topk(
            q, rows, k, "ip", interpret=True, merge_counts=True)
        want = K.fused_topk(q, rows, k, "ip", use_pallas=False)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(want[1]))
        assert (int(got_steps), int(got_tiles)) == (steps, tiles)


def test_fused_kernel_pads_past_the_valid_rows():
    """Fewer valid rows than k inside the kernel (the tile's tail is
    padding): the valid rows come back in order, then (NEG, -1) pads,
    after one merge step per valid row."""
    from repro.kernels.fused_topk import fused_topk_pallas, merge_counts

    q, x = _ascending_ip(512)
    qp = jnp.pad(q, ((0, 7), (0, 0)))
    s, i, counts = fused_topk_pallas(qp, x, k=50, metric="ip", n_valid=30,
                                     bq=8, bn=512, interpret=True)
    want_s, want_i = ref.topk_ref(ref.qmip_ref(qp, x), 50, 30)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))
    assert (np.asarray(i)[:, 30:] == -1).all()
    assert [int(c) for c in merge_counts(counts)] == [30, 1]


# --------------------------------------------------------------------------
# engine.topk over stores: precision arms agree with exact search
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def corpus_queries():
    corpus = jax.random.normal(jax.random.PRNGKey(0), (900, 32)) * 0.05
    queries = jax.random.normal(jax.random.PRNGKey(1), (16, 32)) * 0.05
    return corpus, queries


def test_engine_topk_packed_equals_unpacked(corpus_queries):
    """Bit-packing is a storage layout, not a math change: identical
    scores (exact integer parity) from packed and unpacked int4 stores."""
    corpus, queries = corpus_queries
    params = Qz.learn_params(corpus, bits=4, scheme="gaussian", sigmas=3.0)
    codes = Qz.quantize(corpus, params)
    packed = engine.CodeStore.from_codes(codes, params, pack=True)
    unpacked = engine.CodeStore.from_codes(codes, params, pack=False)
    for metric in ("ip", "l2", "angular"):
        sp, ip_ = engine.topk(queries, packed, 10, metric)[:2]
        su, iu = engine.topk(queries, unpacked, 10, metric)[:2]
        np.testing.assert_allclose(np.asarray(sp), np.asarray(su), rtol=1e-6)
    assert packed.memory_bytes() < 0.6 * unpacked.memory_bytes()


def test_engine_fused_path_matches_scan_path(corpus_queries):
    """interpret=True forces the fused Pallas kernel through engine.topk
    (the TPU hot path, interpreted); it must agree exactly with the XLA
    streaming scan the engine uses off-TPU."""
    corpus, queries = corpus_queries
    params = Qz.learn_params(corpus, bits=8, scheme="gaussian", sigmas=3.0)
    store = engine.CodeStore.from_codes(Qz.quantize(corpus, params), params)
    for metric in ("ip", "l2"):
        sf, idf, stf = engine.topk(queries, store, 10, metric, chunk=256,
                                   interpret=True)
        ss, ids, sts = engine.topk(queries, store, 10, metric, chunk=256)
        np.testing.assert_array_equal(np.asarray(sf), np.asarray(ss))
        np.testing.assert_array_equal(np.asarray(idf), np.asarray(ids))
        assert stf["bytes_read"] > 0 and sts["bytes_read"] > 0


def test_engine_store_base_rebases_ids(corpus_queries):
    """Shard-local stores rebase ids for the distributed merge."""
    corpus, queries = corpus_queries
    st = engine.CodeStore.dense(corpus, base=10_000)
    _s, i, _ = engine.topk(queries, st, 5, "ip")
    ids = np.asarray(i)
    assert ids.min() >= 10_000 and ids.max() < 10_000 + corpus.shape[0]


@pytest.mark.parametrize("block", [1, 3, 16])
def test_query_blocks_equal_one_block(corpus_queries, block, monkeypatch):
    """Candidate scans run over query blocks, which bound the gathered
    rows; any block size, padded or not, gives the one-block answer."""
    from repro.engine import scorer

    corpus, queries = corpus_queries
    params = Qz.learn_params(corpus, bits=8, scheme="gaussian", sigmas=3.0)
    store = engine.CodeStore.from_codes(Qz.quantize(corpus, params), params)
    qq = store.encode_queries(queries)
    cand = jax.random.randint(jax.random.PRNGKey(2), (16, 50), -1, 900)

    def fn(q, c):
        return engine.topk_among(q, store, c, 10, "l2")

    want = fn(qq, cand)
    monkeypatch.setattr(scorer, "GATHER_BYTES", block * 4 * 50 * qq.shape[1])
    got = engine.by_query_block(fn, qq, cand)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_odd_dim_packs(corpus_queries):
    """Odd d packs via the zero-code pad column without score drift."""
    corpus, queries = corpus_queries
    corpus = corpus[:, :31]
    queries = queries[:, :31]
    params = Qz.learn_params(corpus, bits=4, scheme="gaussian", sigmas=3.0)
    codes = Qz.quantize(corpus, params)
    packed = engine.CodeStore.from_codes(codes, params, pack=True)
    unpacked = engine.CodeStore.from_codes(codes, params, pack=False)
    assert packed.data.shape == (900, 16)
    sp = engine.topk(queries, packed, 10, "l2")[0]
    su = engine.topk(queries, unpacked, 10, "l2")[0]
    np.testing.assert_allclose(np.asarray(sp), np.asarray(su), rtol=1e-6)


# --------------------------------------------------------------------------
# lpq4 factory arm: half the lpq8 bytes, recall parity with unpacked int4
# --------------------------------------------------------------------------

@pytest.mark.parametrize("factory8,factory4", [
    ("flat,lpq8@gaussian:3", "flat,lpq4@gaussian:3"),
    ("ivf8,lpq8@gaussian:3", "ivf8,lpq4@gaussian:3"),
])
def test_lpq4_memory_halves_vs_lpq8(corpus_queries, factory8, factory4):
    corpus, _queries = corpus_queries
    idx8 = make_index(factory8, corpus, key=jax.random.PRNGKey(0),
                      **({"kmeans_iters": 4} if "ivf" in factory8 else {}))
    idx4 = make_index(factory4, corpus, key=jax.random.PRNGKey(0),
                      **({"kmeans_iters": 4} if "ivf" in factory4 else {}))
    assert idx4.store.packed and idx4.store.bits == 4
    # payload is exactly half; the shared constants/centroids dilute the
    # total slightly — stay under 0.65x end to end
    ratio = idx4.memory_bytes() / idx8.memory_bytes()
    assert ratio < 0.65, ratio


@pytest.mark.parametrize("kind", ["flat", "ivf8"])
def test_lpq4_recall_parity_with_unpacked_int4(corpus_queries, kind):
    """Packed lpq4 returns the same neighbors as an unpacked-int4 build
    (identical integer scores; ties may reorder)."""
    corpus, queries = corpus_queries
    gt = np.asarray(make_index(kind.rstrip("8") if kind == "flat" else kind,
                               corpus).search(queries, 10).ids)
    packed_idx = make_index(f"{kind},lpq4@gaussian:3", corpus,
                            key=jax.random.PRNGKey(0))
    spec_unpacked = QuantSpec(bits=4, scheme="gaussian", sigmas=3.0,
                              packed=False)
    from repro.knn import IndexSpec

    params = {"nlist": 8} if kind == "ivf8" else {}
    unpacked_idx = make_index(
        IndexSpec(kind="flat" if kind == "flat" else "ivf",
                  quant=spec_unpacked, params=params),
        corpus, key=jax.random.PRNGKey(0),
    )
    assert not unpacked_idx.store.packed
    sp = SearchParams(nprobe=8)
    ids_p = np.asarray(packed_idx.search(queries, 10, sp).ids)
    ids_u = np.asarray(unpacked_idx.search(queries, 10, sp).ids)
    parity = float(recall_at_k(jnp.asarray(ids_u), jnp.asarray(ids_p)))
    assert parity > 0.99, parity
    # and the 4-bit arm still finds mostly-true neighbors
    rec = float(recall_at_k(jnp.asarray(gt), jnp.asarray(ids_p)))
    assert rec > 0.5, rec


def test_lpq4_hnsw_and_graph_build_and_search(corpus_queries):
    """Packed storage behind the graph walks: gather-unpack scoring."""
    corpus, queries = corpus_queries
    corpus, queries = corpus[:400], queries[:8]
    gt = np.asarray(make_index("flat", corpus).search(queries, 10).ids)
    for factory, over in (
        ("hnsw8,lpq4@gaussian:3", {"ef_construction": 40, "batch_size": 128}),
        ("graph16,lpq4@gaussian:3", {"n_seeds": 16}),
    ):
        idx = make_index(factory, corpus, key=jax.random.PRNGKey(0), **over)
        assert idx.store.packed and idx.store.bits == 4
        ids = np.asarray(idx.search(queries, 10,
                                    SearchParams(ef_search=80)).ids)
        overlap = np.mean([len(set(a) & set(b)) / 10 for a, b in zip(gt, ids)])
        assert overlap > 0.4, (factory, overlap)


# --------------------------------------------------------------------------
# uniform stats + accounting fixes
# --------------------------------------------------------------------------

def test_stats_uniform_across_kinds(corpus_queries):
    """Every kind reports the engine accounting block (satellite: real
    per-search stats surfaced uniformly)."""
    corpus, queries = corpus_queries
    cases = {
        "flat": ("flat,lpq8@gaussian:3", {}),
        "ivf": ("ivf8,lpq8@gaussian:3", {"kmeans_iters": 4}),
        "hnsw": ("hnsw8,lpq8@gaussian:3",
                 {"ef_construction": 40, "batch_size": 128}),
        "graph": ("graph16,lpq8@gaussian:3", {"n_seeds": 16}),
        "pq": ("pq16+lpq", {"kmeans_iters": 4}),
    }
    sp = SearchParams(nprobe=4, ef_search=40, chunk=256)
    for kind, (factory, over) in cases.items():
        idx = make_index(factory, corpus[:512], key=jax.random.PRNGKey(0),
                         **over)
        stats = idx.search(queries, 5, sp).stats
        for field in ("kind", "candidates", "chunks", "bytes_read",
                      "bits", "packed"):
            assert field in stats, (kind, field, stats)
        assert stats["kind"] == kind
        assert stats["candidates"] > 0 and stats["bytes_read"] > 0


def test_flat_memory_bytes_honest_at_4_bits(corpus_queries):
    """Regression: FlatIndex.memory_bytes hard-coded 1 byte/code, so the
    4-bit arm misreported Table 1 memory by 2x.  CodeStore accounting
    reports true packed bytes."""
    corpus, _q = corpus_queries
    n, d = corpus.shape
    idx4 = make_index("flat,lpq4@gaussian:3", corpus)
    idx8 = make_index("flat,lpq8@gaussian:3", corpus)
    consts = 3 * d * 4
    assert idx8.memory_bytes() == n * d + consts
    assert idx4.memory_bytes() == n * d // 2 + consts


def test_topk_pads_uniformly_when_k_exceeds_n(corpus_queries):
    """Every kind honors the [Q, k] / -1-pad SearchResult contract."""
    corpus, queries = corpus_queries
    small = corpus[:6]
    for factory in ("flat", "flat,lpq4@gaussian:3"):
        res = make_index(factory, small).search(queries, 10)
        assert res.ids.shape == (queries.shape[0], 10)
        assert (np.asarray(res.ids)[:, 6:] == -1).all()
    res = make_index("pq16", small, kmeans_iters=2).search(queries, 10)
    assert res.ids.shape == (queries.shape[0], 10)
    assert (np.asarray(res.ids)[:, 6:] == -1).all()


def test_wide_bits_rejected_early(corpus_queries):
    """B > 8 would overflow the engine's int32 score accumulation
    (d * (2^15)^2 > 2^31 at d >= 2) — rejected at parse/build, not by a
    kernel assert deep in the first search."""
    corpus, _q = corpus_queries
    with pytest.raises(ValueError, match=r"\[1, 8\]"):
        make_index("flat,lpq16@gaussian:3", corpus)
    with pytest.raises(ValueError, match="B <= 8"):
        QuantSpec(bits=16).build_store(corpus)


def test_pq_rejects_angular_at_build(corpus_queries):
    corpus, _q = corpus_queries
    with pytest.raises(ValueError, match="ip and l2"):
        make_index("pq16,angular", corpus[:256], kmeans_iters=2)


def test_store_roundtrips_through_save_load(corpus_queries, tmp_path):
    corpus, queries = corpus_queries
    idx = make_index("flat,lpq4@gaussian:3", corpus)
    path = str(tmp_path / "lpq4.npz")
    idx.save(path)
    from repro.knn import load_index

    back = load_index(path)
    assert back.store.packed and back.store.bits == 4
    a = idx.search(queries, 10)
    b = back.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    assert back.memory_bytes() == idx.memory_bytes()
