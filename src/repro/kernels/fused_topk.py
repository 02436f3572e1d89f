"""Pallas TPU kernel: fused corpus scan + running top-k.

The unfused hot path writes a [Q, chunk] score tile to memory for every
corpus chunk and merges it with `lax.top_k` afterwards — the score matrix
round-trips HBM even though only k survivors per query matter.  This
kernel fuses the reduction into the scan: the grid walks corpus tiles
sequentially (grid = (Q/bq, N/bn), corpus axis innermost) while the
output block — the [bq, k] best (scores, ids) set — stays VMEM-resident
across every tile of a query row (its index map is constant in the
corpus axis, the standard Pallas accumulation pattern).  The [Q, N]
score matrix never exists in HBM.

Per tile the merge first counts, per query row, the candidates that beat
the row's running k-th best (strictly: the grid visits corpus tiles in id
order, so a tile's ids follow every id in the best set and an equal score
cannot enter).  The most any row holds, capped at k, is the tile's number
of select-and-evict steps, each a handful of dense VPU reductions (no
sorts, no dynamic stores); a tile that beats no k-th best does no merge
at all.  The best set stays unsorted during the scan and is sorted once,
by a k-step select-and-mask sweep, on the last corpus tile.  On rows in
no particular order, tile j brings about k/j entrants, so the steps
follow the data rather than k.  The worst case is a corpus in ascending
score order: every tile then takes k steps, the cost of a fixed k-step
sweep per tile plus one count pass.  A small counter output carries, per
query tile, the merge steps taken and the corpus tiles visited.  Padding
rows are id-masked *inside* the kernel (score -> -inf, id -> -1), so
zero-padding can never win under L2 — callers get only valid ids back,
no sentinel hazard.

Supported score tiles (dispatch in ops.fused_topk):
  * f32 / int8 codes, metric ip or l2 (one dot per tile),
  * bit-packed int4 codes with the unpack-in-kernel nibble planes of
    :mod:`repro.kernels.packed` (queries pre-split even/odd).
Angular stays on the unfused path (needs per-row norm rescale, see
engine.scorer's dispatch table).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.packed import qmip4_tile, ql24_tile

BQ = 128    # query rows per tile
BN = 512    # corpus rows per tile

NEG = float(jnp.finfo(jnp.float32).min)


# --------------------------------------------------------------------------
# tile score functions (values in, values out — shared with interpret mode)
# --------------------------------------------------------------------------

def _acc_dtype(dtype) -> jnp.dtype:
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


def _ip_tile(q: jax.Array, x: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        q, x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=_acc_dtype(q.dtype),
    )


def _l2_tile(q: jax.Array, x: jax.Array) -> jax.Array:
    acc = _acc_dtype(q.dtype)
    dot = _ip_tile(q, x)
    qa = q.astype(acc)
    xa = x.astype(acc)
    qq = jnp.sum(qa * qa, axis=-1, keepdims=True)
    xx = jnp.sum(xa * xa, axis=-1)[None, :]
    return -(qq + xx - 2 * dot)


# packed-int4 tile math is shared with kernels/packed.py (one copy of the
# nibble-unpack + two-MXU-pass scoring)
_TILE_FNS = {("ip", False): _ip_tile, ("l2", False): _l2_tile,
             ("ip", True): qmip4_tile, ("l2", True): ql24_tile}


# --------------------------------------------------------------------------
# in-kernel running top-k merge
# --------------------------------------------------------------------------

_NO_ID = jnp.iinfo(jnp.int32).max
_LOW_ID = jnp.iinfo(jnp.int32).min

#: the counter block of one query tile, one int32 vreg: [0, 0] holds the
#: merge steps taken, [0, 1] the corpus tiles visited
_COUNT_BLOCK = (8, 128)


def _merge_steps(best_s, s, k: int):
    """The steps a tile's merge needs: the most candidates any row holds
    that beat that row's running k-th best, capped at k.  Strictly
    greater: the tile's ids all follow the best set's, so a candidate
    equal to the k-th best ranks after it and cannot enter."""
    kth = jnp.min(best_s, axis=1, keepdims=True)
    entrants = jnp.sum((s > kth).astype(jnp.int32), axis=1)
    return jnp.minimum(jnp.max(entrants), k)


def _merge_tile(best_s, best_i, s, ids, k: int):
    """Merge a [bq, bn] score tile into the running, unsorted [bq, k]
    best set; returns the new set and the steps taken.

    The grid visits corpus tiles in id order, so every id in ``ids``
    exceeds every id already in the set.  Each step moves the tile's
    best remaining candidate (the row max, smallest id among equal
    scores) into the set if it beats the set's worst entry (the lowest
    score, largest id among equal scores), which it evicts; a row with
    no such candidate left keeps its set.  The loop runs as many steps
    as the tile can contribute (``_merge_steps``), none at all when no
    candidate beats a k-th best.  Ties are decided by id, not by lane
    position, so the set is the one ``lax.top_k`` keeps over the
    id-ordered corpus whatever order the compiler's reductions visit
    the lanes in.  Pad slots (NEG, -1) may repeat; the last column
    among equal ones is evicted first.
    """
    kcols = jax.lax.broadcasted_iota(jnp.int32, best_s.shape, 1)
    steps = _merge_steps(best_s, s, k)

    def step(_, carry):
        s, bs, bi = carry
        m = jnp.max(s, axis=1, keepdims=True)               # [bq, 1]
        sel = jnp.min(jnp.where(s == m, ids, _NO_ID), axis=1, keepdims=True)
        worst = jnp.min(bs, axis=1, keepdims=True)
        at_w = bs == worst
        wid = jnp.max(jnp.where(at_w, bi, _LOW_ID), axis=1, keepdims=True)
        col = jnp.max(jnp.where(at_w & (bi == wid), kcols, -1), axis=1,
                      keepdims=True)
        slot = (m > worst) & (kcols == col)
        bs = jnp.where(slot, m, bs)
        bi = jnp.where(slot, sel, bi)
        return jnp.where((s == m) & (ids == sel), NEG, s), bs, bi

    _, best_s, best_i = jax.lax.fori_loop(0, steps, step, (s, best_s, best_i))
    return best_s, best_i, steps


def _sort_best(best_s, best_i, k: int):
    """The [bq, k] best set sorted best-first, lower id first among
    equal scores, by a k-step select-and-mask sweep: each step takes the
    row max and, among the entries holding it, the smallest id.  Once
    only pad entries remain, the step emits the (NEG, -1) pad."""
    kcols = jax.lax.broadcasted_iota(jnp.int32, best_s.shape, 1)

    def step(j, carry):
        cs, out_s, out_i = carry
        m = jnp.max(cs, axis=1, keepdims=True)             # [bq, 1]
        at_m = cs == m
        sel = jnp.min(jnp.where(at_m, best_i, _NO_ID), axis=1, keepdims=True)
        out_s = jnp.where(kcols == j, m, out_s)
        out_i = jnp.where(kcols == j, jnp.where(m > NEG, sel, -1), out_i)
        return jnp.where(at_m & (best_i == sel), NEG, cs), out_s, out_i

    _, out_s, out_i = jax.lax.fori_loop(
        0, k, step,
        (best_s, jnp.full_like(best_s, NEG), jnp.full_like(best_i, -1)),
    )
    return out_s, out_i


def merge_counts(counts: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(merge steps, corpus tiles visited), summed over the query tiles
    of a call, from the kernel's counter output."""
    return jnp.sum(counts[:, 0]), jnp.sum(counts[:, 1])


def _make_kernel(score_tile, k: int, bn: int, n_valid: int,
                 with_mask: bool = False):
    def kernel(*refs):
        *in_refs, os_ref, oi_ref, cnt_ref = refs
        if with_mask:
            *in_refs, m_ref = in_refs
        j = pl.program_id(1)                               # corpus-tile index

        @pl.when(j == 0)
        def _init():
            os_ref[...] = jnp.full(os_ref.shape, NEG, jnp.float32)
            oi_ref[...] = jnp.full(oi_ref.shape, -1, jnp.int32)
            cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.int32)

        s = score_tile(*[r[...] for r in in_refs]).astype(jnp.float32)
        gid = j * bn + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = gid < n_valid
        if with_mask:
            # predicate bitmap rides the corpus grid axis as a [1, bn]
            # int8 row, lane-major like the score tile's columns — the
            # filter ANDs into the same pad fence, so a filtered row dies
            # exactly like a pad row (DESIGN.md §16)
            ok = ok & (m_ref[...] != 0)
        s = jnp.where(ok, s, NEG)
        ids = jnp.where(ok, gid, -1)
        bs, bi, steps = _merge_tile(os_ref[...], oi_ref[...], s, ids, k)
        os_ref[...] = bs
        oi_ref[...] = bi
        row = jax.lax.broadcasted_iota(jnp.int32, cnt_ref.shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, cnt_ref.shape, 1)
        cnt_ref[...] += jnp.where((row == 0) & (lane == 0), steps, 0)
        cnt_ref[...] += ((row == 0) & (lane == 1)).astype(jnp.int32)

        @pl.when(j == pl.num_programs(1) - 1)
        def _sort():
            os_ref[...], oi_ref[...] = _sort_best(os_ref[...], oi_ref[...], k)

    return kernel


def _fused_call(score_tile, inputs, corpus, *, k, n_valid, bq, bn, interpret,
                mask=None):
    Q = inputs[0].shape[0]
    N = corpus.shape[0]
    assert Q % bq == 0 and N % bn == 0, (Q, N, bq, bn)
    q_specs = [
        pl.BlockSpec((bq, a.shape[1]), lambda i, j: (i, 0)) for a in inputs
    ]
    x_spec = pl.BlockSpec((bn, corpus.shape[1]), lambda i, j: (j, 0))
    operands = list(inputs) + [corpus]
    in_specs = q_specs + [x_spec]
    if mask is not None:
        assert mask.shape[0] == N, (mask.shape, N)
        operands.append(mask.reshape(1, N).astype(jnp.int8))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
    out_spec = pl.BlockSpec((bq, k), lambda i, j: (i, 0))
    cnt_spec = pl.BlockSpec(_COUNT_BLOCK, lambda i, j: (i, 0))
    rows, lanes = _COUNT_BLOCK
    return pl.pallas_call(
        _make_kernel(score_tile, k, bn, n_valid, with_mask=mask is not None),
        grid=(Q // bq, N // bn),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec, cnt_spec],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
            jax.ShapeDtypeStruct((Q // bq * rows, lanes), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "n_valid", "bq", "bn", "interpret")
)
def fused_topk_pallas(
    q: jax.Array,
    x: jax.Array,
    *,
    k: int,
    metric: str,
    n_valid: int,
    bq: int = BQ,
    bn: int = BN,
    interpret: bool = False,
    mask: jax.Array | None = None,
):
    """[Q, d] x [N, d] -> ([Q, k] f32 scores, [Q, k] i32 ids, counters),
    streaming; ``merge_counts`` reads the counters.

    Rows with global id >= n_valid (padding) are masked in-kernel; an
    optional [N] ``mask`` (nonzero = allowed) ANDs into the same fence.
    """
    return _fused_call(_TILE_FNS[(metric, False)], [q], x,
                       k=k, n_valid=n_valid, bq=bq, bn=bn, interpret=interpret,
                       mask=mask)


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "n_valid", "bq", "bn", "interpret")
)
def fused_topk4_pallas(
    q_even: jax.Array,
    q_odd: jax.Array,
    packed: jax.Array,
    *,
    k: int,
    metric: str,
    n_valid: int,
    bq: int = BQ,
    bn: int = BN,
    interpret: bool = False,
    mask: jax.Array | None = None,
):
    """Packed-int4 variant: [Q, d/2] (x2) vs [N, d/2] uint8 -> top-k and
    counters."""
    return _fused_call(_TILE_FNS[(metric, True)], [q_even, q_odd], packed,
                       k=k, n_valid=n_valid, bq=bq, bn=bn, interpret=interpret,
                       mask=mask)
