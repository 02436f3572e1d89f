"""The blocked reference on regenerated rows equals an unblocked one, and
its comparison tells sound answers from broken ones."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, reference

N = 2 * data.MAX_BLOCK          # two blocks of rows


@pytest.mark.parametrize("kind,metric,quant", [
    ("product", "ip", {"bits": 8, "scheme": "gaussian", "sigmas": 3.0}),
    ("sift_mixture", "l2", {"bits": 8, "scheme": "minmax"}),
])
def test_blocked_reference_equals_unblocked(kind, metric, quant):
    gen = data.Generator(kind=kind, d=16, components=8, zipf_s=1.0,
                         noise_sd=18.0)
    key = data.key_from_seed(2 ** 33 + 7)
    consts = gen.consts(key)
    assert data.block_rows(N) == data.MAX_BLOCK
    whole = data.corpus(gen, key, N, consts)       # what set-up serves
    assert whole.shape == (N, 16)
    # the rows regenerated block by block, as the reference draws them,
    # are the served corpus bit for bit
    x = jnp.concatenate([data.block(gen, key, b, data.MAX_BLOCK, consts)
                         for b in range(2)])
    np.testing.assert_array_equal(np.asarray(x), np.asarray(whole))
    q = np.asarray(data.queries(gen, jax.random.PRNGKey(5), 24, consts))
    k = 10

    # unblocked: fit, encode and score the whole corpus at once
    xs = np.asarray(x, np.float64)
    if quant["scheme"] == "minmax":
        lo = jnp.asarray(xs.min(0), jnp.float32)
        hi = jnp.asarray(xs.max(0), jnp.float32)
        fit = ((lo + hi) / 2.0, lo, hi)
    else:
        mu = jnp.asarray(xs.mean(0), jnp.float32)
        sd = jnp.maximum(jnp.asarray(xs.std(0) * quant["sigmas"],
                                     jnp.float32), 1e-12)
        fit = (mu, mu - sd, mu + sd)
    sc = np.asarray(reference.int_scores(reference.eq1(jnp.asarray(q), fit, 8),
                                         reference.eq1(x, fit, 8), metric))
    sf = np.asarray(reference.float_scores(jnp.asarray(q), x, metric))
    prog_ids = np.argsort(-sf, axis=1, kind="stable")[:, :k].astype(np.int32)
    prog_ids[0, -1] = -1

    ref = reference.exact(gen, key, consts, N, metric, quant, q, prog_ids)
    fit_b = reference.eq1_fit(gen, key, N, consts, quant)
    for a, b in zip(fit, fit_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_array_equal(ref["top"], -np.sort(-sc, axis=1)[:, :k])
    got = np.take_along_axis(sc, np.maximum(prog_ids, 0), axis=1)
    np.testing.assert_array_equal(ref["at_ids"][prog_ids >= 0],
                                  got[prog_ids >= 0])
    np.testing.assert_allclose(ref["spread"], sc.astype(np.float64).std(1),
                               rtol=1e-4)
    np.testing.assert_array_equal(ref["float_ids"],
                                  np.argsort(-sf, axis=1, kind="stable")[:, :k])


def _ref(top, at, spread=1.0, in_probe=None):
    top = np.asarray(top, np.int64)
    return {"top": top, "at_ids": np.asarray(at, np.int64),
            "in_probe": (np.ones(top.shape, bool) if in_probe is None
                         else np.asarray(in_probe)),
            "misplaced": 0, "spread": np.full(top.shape[0], spread),
            "float_ids": np.zeros_like(top)}


def test_compare_sound_and_broken_answers():
    ref = _ref([[9, 7, 5]], [[9, 7, 5]])
    ids = np.array([[3, 1, 2]])
    ok = reference.compare(ref, ids, np.array([[9.0, 7.0, 5.0]]))
    assert ok == {"rank_gap": 0.0, "score_err": 0.0}
    # a worse row in third place
    worse = reference.compare(_ref([[9, 7, 5]], [[9, 7, 4]]), ids,
                              np.array([[9.0, 7.0, 4.0]]))
    assert worse["rank_gap"] == 1.0
    # best-first order broken
    swapped = reference.compare(_ref([[9, 7, 5]], [[7, 9, 5]]), ids,
                                np.array([[7.0, 9.0, 5.0]]))
    assert swapped["rank_gap"] == 2.0
    # a returned score that is not the row's
    assert reference.compare(ref, ids, np.array([[9.0, 7.0, 6.0]]))[
        "score_err"] == 1.0
    # a repeated id, or an id after a missing one, is infinitely wrong
    for bad in ([[3, 3, 2]], [[3, -1, 2]]):
        assert reference.compare(ref, np.array(bad), np.array(
            [[9.0, 7.0, 5.0]]))["rank_gap"] == float("inf")
    # a missing tail: no score error, but a rank gap
    tail = reference.compare(_ref([[9, 7, 5]], [[9, 7, reference.INT_MIN]]),
                             np.array([[3, 1, -1]]),
                             np.array([[9.0, 7.0, -3.4e38]]))
    assert tail["score_err"] == 0.0
    assert tail["rank_gap"] == float("inf")


def test_compare_rows_outside_the_probed_lists_and_a_broken_table():
    ids = np.array([[3, 1, 2]])
    scores = np.array([[9.0, 7.0, 5.0]])
    # the third row is scored right but lies in a list the query does
    # not probe
    out = reference.compare(_ref([[9, 7, 5]], [[9, 7, 5]],
                                 in_probe=[[True, True, False]]), ids, scores)
    assert out == {"rank_gap": float("inf"), "score_err": 0.0}
    broken = dict(_ref([[9, 7, 5]], [[9, 7, 5]]), misplaced=1)
    assert reference.compare(broken, ids, scores)["rank_gap"] == float("inf")


def test_ivf_table_and_probe_lists():
    lists = np.array([[0, 3, -1], [1, 2, 4]])
    table = reference.ivf_table(np.zeros((2, 2)), lists, 5)
    assert table["row_list"].tolist() == [0, 1, 1, 0, 1]
    assert table["misplaced"] == 0
    # a row in two lists, and a row in none
    assert reference.ivf_table(np.zeros((2, 2)), np.array(
        [[0, 3, 1], [1, 2, -1]]), 5)["misplaced"] == 2
    cents = np.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0], [10.0, 10.0]])
    q = np.array([[2.0, 1.0], [9.0, 8.0]])
    l2 = reference.probe_lists(q, cents, 2, "l2")
    want = [[True, True, False, False], [False, True, False, True]]
    # a clear edge: every rounding probes the same lists
    for key in ("exact", "sure", "allowed"):
        assert l2[key].tolist() == want, key
    assert reference.probe_lists(q, cents, 1, "ip")["exact"].tolist() == [
        [False, False, False, True], [False, False, False, True]]
    # the query is as near list 1 as list 2: either may be the second
    # list probed, so neither is sure and both are allowed
    q_tie = np.array([[1.0, 1.0]])
    c_tie = np.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0], [30.0, 30.0]])
    tie = reference.probe_lists(q_tie, c_tie, 2, "l2")
    assert tie["exact"].tolist() == [[True, True, False, False]]
    assert tie["sure"].tolist() == [[True, False, False, False]]
    assert tie["allowed"].tolist() == [[True, True, True, False]]


def test_probe_lists_allows_a_bfloat16_rounded_probe():
    rng = np.random.default_rng(0)
    c = rng.uniform(0, 200, (64, 32))
    q = np.floor(rng.uniform(0, 200, (50, 32)))
    got = reference.probe_lists(q, c, 8, "l2")

    def top8(scores):
        out = np.zeros(scores.shape, bool)
        np.put_along_axis(out, np.argsort(-scores, axis=1)[:, :8], True, axis=1)
        return out

    # the probe of a matrix unit that rounds its operands to bfloat16,
    # with float32 norms, and the float32 probe
    def bf16(x):
        return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16),
                          np.float64)

    cc = np.sum(c * c, axis=1)
    for probe in (top8(2.0 * bf16(q) @ bf16(c).T - cc),
                  top8(np.asarray(2.0 * jnp.asarray(q, jnp.float32)
                                  @ jnp.asarray(c, jnp.float32).T, np.float64)
                       - cc)):
        assert (got["sure"] <= probe).all() and (probe <= got["allowed"]).all()
    assert (got["sure"] <= got["exact"]).all()
    assert (got["exact"] <= got["allowed"]).all()


def test_blocked_ivf_reference_searches_the_probed_lists_only():
    gen = data.Generator(kind="sift_mixture", d=16, components=8,
                         zipf_s=1.0, noise_sd=18.0)
    key = data.key_from_seed(2 ** 32 + 3)
    consts = gen.consts(key)
    quant = {"bits": 8, "scheme": "minmax"}
    x = data.corpus(gen, key, N, consts)
    q = np.asarray(data.queries(gen, jax.random.PRNGKey(9), 12, consts))
    nlist, k, nprobe = 8, 10, 3
    cents = np.asarray(x[:nlist], np.float64)
    assign = np.argmax(reference.probe_lists(np.asarray(x), cents, 1,
                                             "l2")["exact"], axis=1)
    width = int(np.bincount(assign).max())
    lists = np.full((nlist, width), -1, np.int32)
    for c in range(nlist):
        members = np.where(assign == c)[0]
        lists[c, :members.size] = members
    table = reference.ivf_table(cents, lists, N)
    assert table["misplaced"] == 0
    prog_ids = np.zeros((q.shape[0], k), np.int32)
    ref = reference.exact(gen, key, consts, N, "l2", quant, q, prog_ids,
                          ivf=table, nprobe=nprobe)

    fit = reference.eq1_fit(gen, key, N, consts, quant)
    sc = np.asarray(reference.int_scores(reference.eq1(jnp.asarray(q), fit, 8),
                                         reference.eq1(x, fit, 8), "l2"))
    probed = reference.probe_lists(q, cents, nprobe, "l2")
    sure = probed["sure"][:, assign]
    want = np.sort(np.where(sure, sc, reference.INT_MIN), axis=1)[:, ::-1][:, :k]
    np.testing.assert_array_equal(ref["top"], want)
    np.testing.assert_array_equal(ref["in_probe"], probed["allowed"][
        :, assign[:1]].repeat(k, 1))


def test_recall_counts_shared_ids():
    ref = {"float_ids": np.array([[1, 2, 3, 4], [5, 6, 7, 8]])}
    assert reference.recall(ref, np.array([[4, 3, 9, -1], [5, 6, 7, 8]])) \
        == pytest.approx((0.5 + 1.0) / 2)
