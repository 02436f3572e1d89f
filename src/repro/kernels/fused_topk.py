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

Per tile the merge is a k-step select-and-mask sweep over the
concatenated [bq, k + bn] candidates: max, smallest id among the maxima
and a mask, all dense VPU ops (no sorts, no dynamic stores), O(k (k + bn))
per tile against the tile's O(bn d) MXU score work.  Padding rows are id-masked
*inside* the kernel (score -> -inf, id -> -1), so zero-padding can never
win under L2 — callers get only valid ids back, no sentinel hazard.

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

def _merge_tile(best_s, best_i, s, ids, k: int):
    """Merge a [bq, bn] score tile into the running [bq, k] best set.

    k-step select-and-mask: each step takes the row max of the
    concatenated candidates and, among the entries holding it, the
    smallest id, then masks that entry out — everything stays a dense
    2-D op.  Ties are ordered by id, not by lane position, so the result
    (sorted best-first, lower id first among equal scores) is the same
    as ``lax.top_k`` over the id-ordered corpus whatever order the
    compiler's reductions visit the lanes in.  Once only masked entries
    remain, the step emits the (NEG, -1) pad.
    """
    cs = jnp.concatenate([best_s, s], axis=1)              # [bq, k + bn]
    ci = jnp.concatenate([best_i, ids], axis=1)
    kcols = jax.lax.broadcasted_iota(jnp.int32, best_s.shape, 1)
    no_id = jnp.iinfo(jnp.int32).max

    def step(j, carry):
        cs, out_s, out_i = carry
        m = jnp.max(cs, axis=1, keepdims=True)             # [bq, 1]
        at_m = cs == m
        sel = jnp.min(jnp.where(at_m, ci, no_id), axis=1, keepdims=True)
        out_s = jnp.where(kcols == j, m, out_s)
        out_i = jnp.where(kcols == j, jnp.where(m > NEG, sel, -1), out_i)
        return jnp.where(at_m & (ci == sel), NEG, cs), out_s, out_i

    _, out_s, out_i = jax.lax.fori_loop(
        0, k, step,
        (cs, jnp.full_like(best_s, NEG), jnp.full_like(best_i, -1)),
    )
    return out_s, out_i


def _make_kernel(score_tile, k: int, bn: int, n_valid: int,
                 with_mask: bool = False):
    def kernel(*refs):
        *in_refs, os_ref, oi_ref = refs
        if with_mask:
            *in_refs, m_ref = in_refs
        j = pl.program_id(1)                               # corpus-tile index

        @pl.when(j == 0)
        def _init():
            os_ref[...] = jnp.full(os_ref.shape, NEG, jnp.float32)
            oi_ref[...] = jnp.full(oi_ref.shape, -1, jnp.int32)

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
        bs, bi = _merge_tile(os_ref[...], oi_ref[...], s, ids, k)
        os_ref[...] = bs
        oi_ref[...] = bi

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
    return pl.pallas_call(
        _make_kernel(score_tile, k, bn, n_valid, with_mask=mask is not None),
        grid=(Q // bq, N // bn),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
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
    """[Q, d] x [N, d] -> ([Q, k] f32 scores, [Q, k] i32 ids), streaming.

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
    """Packed-int4 variant: [Q, d/2] (x2) vs [N, d/2] uint8 -> top-k."""
    return _fused_call(_TILE_FNS[(metric, True)], [q_even, q_odd], packed,
                       k=k, n_valid=n_valid, bq=bq, bn=bn, interpret=interpret,
                       mask=mask)
