"""The plain reference: the same search on the same rows, written here.

It imports nothing of the program.  It regenerates the corpus from the
seed block by block (``data``), fits the Eq. 1 constants of
arXiv:2110.08919 itself (``eq1_fit``), encodes rows and queries, and
scores every row exactly in integers.  The one thing it takes from the
program is an IVF index's coarse quantizer, its centroids and list
membership read back as operands (k-means is not reproducible apart):
it checks that every row sits in exactly one list, picks each query's
``nprobe`` nearest lists itself (``probe_lists``, allowing either
outcome where rounding decides a list at the edge), and searches
exactly over those lists' rows.

From one pass over the blocks it returns, for a sample of queries:

* the exact top-k integer scores under the configuration's codes, over
  the rows the search may return (every row, or the probed lists' rows);
* whether each id the program returned lies in the query's probed lists;
* the reference's integer score of each id the program returned;
* the spread (standard deviation) of each query's scores over the
  corpus, which puts every gap on one scale;
* the exact float32 top-k ids at ``precision=HIGHEST``, for recall.

``compare`` turns those into the numbers that decide ``correct``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import data

HIGHEST = jax.lax.Precision.HIGHEST
INT_MIN = int(np.iinfo(np.int32).min)
#: a program's "no answer" score lies below this (it pads with float32 min)
NO_SCORE = -1e30


def eq1_fit(gen: data.Generator, key, n: int, consts, quant: dict):
    """(k, S_b, S_e) of Eq. 1 per dimension, from one pass over the
    blocks.  ``gaussian``: k = mu, S_b, S_e = mu -+ sigmas * sigma, the
    mean and population standard deviation accumulated in float64 (each
    block's sums taken about the first block's mean).  ``minmax``: S_b and
    S_e the least and greatest value, k their midpoint."""
    size = data.block_rows(n)
    shift = np.asarray(_moments(data.block(gen, key, 0, size, consts), 0.0))
    shift = shift[0] / size
    s1 = np.zeros(gen.d, np.float64)
    s2 = np.zeros(gen.d, np.float64)
    lo = np.full(gen.d, np.inf)
    hi = np.full(gen.d, -np.inf)
    for b in range(n // size):
        x = np.asarray(_moments(data.block(gen, key, b, size, consts), shift),
                       np.float64)
        s1 += x[0]
        s2 += x[1]
        lo = np.minimum(lo, x[2])
        hi = np.maximum(hi, x[3])
    scheme = quant.get("scheme", "gaussian")
    if scheme == "minmax":
        lo32 = jnp.asarray(lo, jnp.float32)
        hi32 = jnp.asarray(hi, jnp.float32)
        hi32 = jnp.where(hi32 - lo32 < 1e-12, lo32 + 1e-12, hi32)
        return (lo32 + hi32) / 2.0, lo32, hi32
    if scheme != "gaussian":
        raise ValueError(f"the reference has no Eq. 1 scheme {scheme!r}")
    mean = s1 / n
    sd = np.sqrt(np.maximum(s2 / n - mean ** 2, 0.0)) * float(quant["sigmas"])
    mu = jnp.asarray(shift + mean, jnp.float32)
    sd = jnp.maximum(jnp.asarray(sd, jnp.float32), 1e-12)
    return mu, mu - sd, mu + sd


@jax.jit
def _moments(x, shift):
    c = x - jnp.asarray(shift, jnp.float32)
    return jnp.stack([jnp.sum(c, axis=0), jnp.sum(c * c, axis=0),
                      jnp.min(x, axis=0), jnp.max(x, axis=0)])


def eq1(x, fit, bits: int):
    """Eq. 1: round(2^B (x - k) / (S_e - S_b)), clamped to the B-bit range."""
    mu, lo, hi = fit
    q = jnp.round((2.0 ** bits) * (x - mu) / jnp.maximum(hi - lo, 1e-12))
    return jnp.clip(q, -(2 ** (bits - 1)), 2 ** (bits - 1) - 1).astype(jnp.int32)


def int_scores(qc, xc, metric: str):
    """Exact larger-is-closer integer scores, [S, d] x [B, d] -> [S, B]."""
    dot = jnp.dot(qc, xc.T, preferred_element_type=jnp.int32)
    if metric == "ip":
        return dot
    if metric == "l2":
        qq = jnp.sum(qc * qc, axis=1, keepdims=True)
        xx = jnp.sum(xc * xc, axis=1)[None, :]
        return -(qq + xx - 2 * dot)
    raise ValueError(f"metric {metric!r} has no reference here")


def float_scores(q, x, metric: str):
    dot = jnp.dot(q, x.T, precision=HIGHEST)
    if metric == "ip":
        return dot
    qq = jnp.sum(q * q, axis=1, keepdims=True)
    xx = jnp.sum(x * x, axis=1)[None, :]
    return -(qq + xx - 2.0 * dot)


def ivf_table(centroids, lists, n: int) -> dict:
    """The coarse quantizer as the reference uses it, from the index's
    centroids ([nlist, d]) and lists ([nlist, width], -1 = empty slot)
    read back to the host: float64 centroids, each row's list, and how
    many rows sit in no list or in more than one."""
    lists = np.asarray(lists)
    filled = lists >= 0
    members = lists[filled].astype(np.int64)
    count = np.bincount(members, minlength=n)
    row_list = np.full(n, -1, np.int32)
    row_list[members] = np.repeat(np.arange(lists.shape[0], dtype=np.int32),
                                  filled.sum(axis=1))
    return {"centroids": np.asarray(centroids, np.float64),
            "row_list": row_list,
            "misplaced": int((count[:n] != 1).sum() + (members >= n).sum())}


#: float32 rounding slack of a coarse score, relative to the size of the
#: terms it is summed from
COARSE_SLACK = 2.0 ** -16


def _coarse_scores(dot: np.ndarray, c: np.ndarray, metric: str) -> np.ndarray:
    """Larger-is-closer centroid scores from the query-centroid dots (for
    l2 the squared distance less the query's own norm, which orders the
    lists alike)."""
    if metric == "l2":
        return 2.0 * dot - np.sum(c * c, axis=1)[None, :]
    if metric == "ip":
        return dot
    raise ValueError(f"metric {metric!r} has no reference here")


def _bf16(x: np.ndarray) -> np.ndarray:
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)


def probe_lists(queries, centroids, nprobe: int, metric: str) -> dict:
    """Each query's probed lists, [S, nlist] bool each:

    exact    its ``nprobe`` nearest lists by float64 centroid score
    sure     lists in its top ``nprobe`` however the score is rounded
    allowed  lists that may be in its top ``nprobe``

    A coarse probe may take its dots in float32, or from bfloat16
    operands into float32 sums, as the TPU's matrix unit takes float32
    by default.  Under either, a list whose score lies within float32
    slack of the ``nprobe``-th may go either way: ``sure`` holds the lists
    that beat every list outside the top ``nprobe`` by more than the
    slack under both roundings, ``allowed`` those within the slack of the
    top ``nprobe`` under either."""
    q = np.asarray(queries, np.float64)
    c = np.asarray(centroids, np.float64)
    p = min(nprobe, c.shape[0])
    rows = np.arange(q.shape[0])[:, None]
    dot = q @ c.T
    slack = COARSE_SLACK * (np.sum(q * q, axis=1, keepdims=True)
                            + np.max(np.sum(c * c, axis=1))
                            + 2.0 * (np.abs(q) @ np.abs(c).T).max(axis=1,
                                                                  keepdims=True))
    sure = np.ones(dot.shape, bool)
    allowed = np.zeros(dot.shape, bool)
    for d in (dot, _bf16(q) @ _bf16(c).T):
        s = _coarse_scores(d, c, metric)
        order = np.argsort(-s, axis=1, kind="stable")
        last = np.take_along_axis(s, order[:, p - 1:p], axis=1)
        after = (np.take_along_axis(s, order[:, p:p + 1], axis=1)
                 if p < c.shape[0] else np.full_like(last, -np.inf))
        sure &= s > after + slack
        allowed |= s >= last - slack
    exact = np.zeros(dot.shape, bool)
    np.put_along_axis(exact, np.argsort(-_coarse_scores(dot, c, metric), axis=1,
                                        kind="stable")[:, :p], True, axis=1)
    return {"exact": exact, "sure": sure, "allowed": allowed}


@partial(jax.jit, static_argnames=("metric", "bits"))
def _step(carry, x, start, qf, qc, fit, prog_ids, probe, *, metric, bits):
    """Fold one block of rows (ids from ``start``) into the running state.
    ``probe`` is None (every row may be returned) or (each row's list
    [n], each query's sure and allowed lists [S, nlist]; ``probe_lists``):
    the top-k is over the sure lists' rows, and a returned row counts as
    probed in an allowed list."""
    top_c, top_fs, top_fi, got, in_probe, s1, s2 = carry
    k = top_c.shape[1]
    size = x.shape[0]
    sc = int_scores(qc, eq1(x, fit, bits), metric)
    sf = float_scores(qf, x, metric)
    local = prog_ids - start
    inside = (local >= 0) & (local < size)
    at_pos = jnp.clip(local, 0, size - 1)
    allowed = sc
    if probe is not None:
        row_list, sure, may = probe
        lists = jax.lax.dynamic_slice_in_dim(row_list, start, size)
        allowed = jnp.where(jnp.take(sure, lists, axis=1), sc, INT_MIN)
        may = jnp.take(may, lists, axis=1)
        in_probe = jnp.where(inside, jnp.take_along_axis(may, at_pos, axis=1),
                             in_probe)
    top_c = jax.lax.top_k(jnp.concatenate([top_c, allowed], axis=1), k)[0]
    fs, pos = jax.lax.top_k(jnp.concatenate([top_fs, sf], axis=1), k)
    ids = start + jnp.arange(size, dtype=jnp.int32)
    fi = jnp.take_along_axis(
        jnp.concatenate([top_fi, jnp.broadcast_to(ids, sf.shape)], axis=1),
        pos, axis=1)
    got = jnp.where(inside, jnp.take_along_axis(sc, at_pos, axis=1), got)
    scf = sc.astype(jnp.float32)
    return (top_c, fs, fi, got, in_probe, s1 + jnp.sum(scf, axis=1),
            s2 + jnp.sum(scf * scf, axis=1))


def exact(gen, key, consts, n: int, metric: str, quant: dict,
          queries: np.ndarray, prog_ids: np.ndarray, fit=None,
          ivf: dict | None = None, nprobe: int = 0) -> dict:
    """One blocked pass over the regenerated corpus for ``queries``
    ([S, d] float32) and the ids the program returned for them
    ([S, k] int32, -1 = none).  With ``ivf`` (``ivf_table``) the exact
    top-k is over the rows of each query's ``nprobe`` nearest lists (its
    sure ones, ``probe_lists``).
    Returns host arrays (see module doc)."""
    if fit is None:
        fit = eq1_fit(gen, key, n, consts, quant)
    bits = int(quant["bits"])
    S, k = prog_ids.shape
    qf = jnp.asarray(queries, jnp.float32)
    qc = eq1(qf, fit, bits)
    pid = jnp.asarray(prog_ids, jnp.int32)
    probe = None
    if ivf is not None:
        lists = probe_lists(queries, ivf["centroids"], nprobe, metric)
        probe = (jnp.asarray(ivf["row_list"]), jnp.asarray(lists["sure"]),
                 jnp.asarray(lists["allowed"]))
    carry = (jnp.full((S, k), INT_MIN, jnp.int32),
             jnp.full((S, k), -jnp.inf, jnp.float32),
             jnp.full((S, k), -1, jnp.int32),
             jnp.full((S, k), INT_MIN, jnp.int32),
             jnp.ones((S, k), bool),
             jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.float32))
    size = data.block_rows(n)
    for b in range(n // size):
        carry = _step(carry, data.block(gen, key, b, size, consts),
                      b * size, qf, qc, fit, pid, probe, metric=metric,
                      bits=bits)
    top_c, _fs, top_fi, got, in_probe, s1, s2 = carry
    s1, s2 = np.asarray(s1, np.float64), np.asarray(s2, np.float64)
    spread = np.sqrt(np.maximum(s2 / n - (s1 / n) ** 2, 0.0))
    return {"top": np.asarray(top_c, np.int64),
            "at_ids": np.asarray(got, np.int64),
            "in_probe": np.asarray(in_probe),
            "misplaced": 0 if ivf is None else ivf["misplaced"],
            "spread": np.maximum(spread, 1.0),
            "float_ids": np.asarray(top_fi)}


def compare(ref: dict, prog_ids: np.ndarray, prog_scores: np.ndarray) -> dict:
    """The numbers that decide ``correct``, each a worst case over the
    sample and in units of the query's score spread:

    rank_gap   how far the j-th returned row's reference score lies below
               the reference's own j-th best over the rows the search may
               return (0); a missing answer, or a row outside the query's
               probed lists, scores below every row
    score_err  how far a returned score lies from the reference's score
               of the same row (0)

    A repeated or out-of-range id, a valid id after a missing one, or a
    row in no list or in two, makes every number infinite."""
    ids = np.asarray(prog_ids, np.int64)
    scores = np.asarray(prog_scores, np.float64)
    spread = ref["spread"][:, None]
    at = ref["at_ids"].astype(np.float64)
    valid = ids >= 0
    broken = np.zeros(ids.shape[0], bool)
    for r in range(ids.shape[0]):
        row = ids[r][valid[r]]
        n_valid = row.size
        broken[r] = (len(set(row.tolist())) != n_valid        # repeated
                     or not valid[r, :n_valid].all()          # gap, then id
                     or (at[r][valid[r]] == INT_MIN).any())   # not a row
    if broken.any() or ref["misplaced"]:
        return {"rank_gap": float("inf"), "score_err": float("inf")}
    top = ref["top"].astype(np.float64)
    # a missing answer, or a row the search may not return, scores as
    # nothing: below every row it may return
    mine = np.where(valid & ref["in_probe"], at, -np.inf)
    # (over the sure lists alone the program may do better: no gap)
    rank_gap = float(max(0.0, np.max((top - mine) / spread)))
    err = np.where(valid, np.abs(scores - at) / spread,
                   np.where(scores <= NO_SCORE, 0.0, np.inf))
    return {"rank_gap": rank_gap, "score_err": float(np.max(err))}


def recall(ref: dict, prog_ids: np.ndarray) -> float:
    """Mean recall@k of the program's ids against the float32 exact top-k."""
    truth = ref["float_ids"]
    hits = [len(set(p.tolist()) & set(t.tolist()) - {-1}) / truth.shape[1]
            for p, t in zip(np.asarray(prog_ids), truth)]
    return float(np.mean(hits))
