"""Pallas TPU kernels: int4 *unpack-in-kernel* scoring over bit-packed codes.

The paper's B=4 arm stored at honest width: two 4-bit codes per byte
(`core.pack`), unpacked with a VPU shift-mask *inside* the kernel so the
packed corpus streams HBM -> VMEM at half the int8 byte volume and the
full-width codes never exist in HBM at all (Quick-ADC / Bolt's
unpack-in-register discipline).

Layout trick: a packed byte holds dims (2t, 2t+1) as (lo, hi) nibbles, so

    q . unpack(x)  =  q_even . lo  +  q_odd . hi

The wrapper (ops.qmip4 / ops.ql24) pre-splits the *query* codes into the
even/odd halves once per batch; the kernel then runs two (BQ, d/2) x
(BN, d/2) int8 MXU passes per tile instead of materializing the
interleaved (BN, d) tile — no in-kernel shuffle, just mask/shift/sub on
the streamed bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BQ = 128   # query rows per tile
BN = 512   # corpus rows per tile


def unpack_nibbles(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """uint8 tile -> (lo, hi) int8 nibble planes in [-8, 7] (VPU shift-mask).

    The shift-mask runs on int32 lanes: Mosaic has no 8-bit vector shift
    or subtract.
    """
    x = x.astype(jnp.int32)
    lo = ((x & 0x0F) - 8).astype(jnp.int8)
    hi = (((x >> 4) & 0x0F) - 8).astype(jnp.int8)
    return lo, hi


def _dot_i32(a: jax.Array, b: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        a, b,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def qmip4_tile(qe: jax.Array, qo: jax.Array, x: jax.Array) -> jax.Array:
    """(BQ, d/2) int8 query halves x (BN, d/2) uint8 packed -> (BQ, BN)
    int32 MIP.  Values in, values out — shared by the score-matrix kernel
    here and the fused score+top-k kernel."""
    lo, hi = unpack_nibbles(x)
    return _dot_i32(qe, lo) + _dot_i32(qo, hi)


def ql24_tile(qe: jax.Array, qo: jax.Array, x: jax.Array) -> jax.Array:
    """Packed-int4 negated-squared-L2 tile (see :func:`qmip4_tile`)."""
    lo, hi = unpack_nibbles(x)
    dot = _dot_i32(qe, lo) + _dot_i32(qo, hi)
    qe32 = qe.astype(jnp.int32)
    qo32 = qo.astype(jnp.int32)
    qq = jnp.sum(qe32 * qe32 + qo32 * qo32, axis=-1, keepdims=True)  # (BQ, 1)
    lo32 = lo.astype(jnp.int32)
    hi32 = hi.astype(jnp.int32)
    xx = jnp.sum(lo32 * lo32 + hi32 * hi32, axis=-1)[None, :]        # (1, BN)
    return -(qq + xx - 2 * dot)


def _qmip4_kernel(qe_ref, qo_ref, x_ref, o_ref):
    """One (BQ, BN) int32 MIP tile over packed int4 corpus codes."""
    o_ref[...] = qmip4_tile(qe_ref[...], qo_ref[...], x_ref[...])


def _ql24_kernel(qe_ref, qo_ref, x_ref, o_ref):
    """One (BQ, BN) int32 negated-squared-L2 tile over packed int4 codes."""
    o_ref[...] = ql24_tile(qe_ref[...], qo_ref[...], x_ref[...])


def _packed_call(kernel, q_even, q_odd, packed, *, bq, bn, interpret):
    Q, half = q_even.shape
    N, half2 = packed.shape
    assert half == half2, (half, half2)
    assert Q % bq == 0 and N % bn == 0, (Q, N, bq, bn)
    grid = (Q // bq, N // bn)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, half), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, half), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, half), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Q, N), jnp.int32),
        interpret=interpret,
    )(q_even, q_odd, packed)


@functools.partial(jax.jit, static_argnames=("bq", "bn", "interpret"))
def qmip4_pallas(
    q_even: jax.Array,
    q_odd: jax.Array,
    packed: jax.Array,
    *,
    bq: int = BQ,
    bn: int = BN,
    interpret: bool = False,
) -> jax.Array:
    """[Q, d/2] int8 (x2) vs [N, d/2] uint8 packed -> [Q, N] int32 MIP."""
    return _packed_call(_qmip4_kernel, q_even, q_odd, packed,
                        bq=bq, bn=bn, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bq", "bn", "interpret"))
def ql24_pallas(
    q_even: jax.Array,
    q_odd: jax.Array,
    packed: jax.Array,
    *,
    bq: int = BQ,
    bn: int = BN,
    interpret: bool = False,
) -> jax.Array:
    """[Q, d/2] int8 (x2) vs [N, d/2] uint8 packed -> [Q, N] int32 neg-L2."""
    return _packed_call(_ql24_kernel, q_even, q_odd, packed,
                        bq=bq, bn=bn, interpret=interpret)
