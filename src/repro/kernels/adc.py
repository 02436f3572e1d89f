"""Pallas TPU kernel: fused ADC — in-kernel LUT scoring over PQ codes
with the running top-k of :mod:`repro.kernels.fused_topk`.

Asymmetric distance computation is ``s[q, n] = sum_m lut[q, m, c[n, m]]``
— a per-row gather the MXU cannot run directly.  With
``onehot(c)[n, m*K + j] = (c[n, m] == j)`` the same sum is one int8
contraction over the (m, j)-flattened axis:

    s = lut2d . onehot(c)^T          # [bq, M*K] x [bn, M*K] -> [bq, bn]

Bolt / Quick-ADC's gather-in-register discipline recast as a matmul: the
int8-quantized LUT block ([bq, M*K]; Eq. 1 abs-max per query's table —
see ``engine.quantize_pq_lut``) stays VMEM-resident across
every corpus tile of a query row (its index map is constant in the
corpus grid axis), the one-hot is a VPU compare over the streamed codes,
and accumulation is exact int32.

4-bit codebooks (K = 16) stream *packed* — two codewords per byte — and
are shift-masked into nibble planes in-kernel.  The (even, odd) subspace
split of :mod:`repro.kernels.packed` applies unchanged: lo nibbles hold
even subspaces, hi nibbles odd ones, so the two planes contract against
the even/odd LUT halves with no in-kernel interleave:

    s = lut_even . onehot(lo)^T + lut_odd . onehot(hi)^T

The scored tile feeds the running top-k merge of
``fused_topk`` (the [bq, k] best set rides in the output block), so the
[Q, N] ADC matrix never exists in HBM.  Pure-jnp oracles live in
:mod:`repro.kernels.ref` (``adc_ref`` / ``adc4_ref``) and deliberately
share no code with this module.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fused_topk import _fused_call

BQ = 64    # query rows per tile (each carries an M*K-entry LUT block)
BN = 512   # corpus code rows per tile


def _onehot_codes(codes: jax.Array, n_codewords: int) -> jax.Array:
    """[bn, M] uint codewords -> [bn, M*K] int8 one-hot, m-major flatten."""
    c = codes.astype(jnp.int32)[:, :, None]
    j = jax.lax.broadcasted_iota(
        jnp.int32, (codes.shape[0], codes.shape[1], n_codewords), 2
    )
    return (c == j).astype(jnp.int8).reshape(codes.shape[0], -1)


def _dot_i32(lut2d: jax.Array, onehot: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        lut2d, onehot,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def make_adc_tile(n_codewords: int):
    """(lut2d [bq, M*K] int8, codes [bn, M] uint8) -> [bq, bn] int32."""

    def tile(lut2d: jax.Array, codes: jax.Array) -> jax.Array:
        return _dot_i32(lut2d, _onehot_codes(codes, n_codewords))

    return tile


def make_adc4_tile(n_codewords: int):
    """Packed variant: (lut_even, lut_odd [bq, (M/2)*K] int8,
    packed [bn, M/2] uint8) -> [bq, bn] int32."""

    def tile(lut_even: jax.Array, lut_odd: jax.Array,
             packed: jax.Array) -> jax.Array:
        packed = packed.astype(jnp.int32)     # Mosaic: no 8-bit vector shift
        lo = packed & 0x0F
        hi = (packed >> 4) & 0x0F
        return (_dot_i32(lut_even, _onehot_codes(lo, n_codewords))
                + _dot_i32(lut_odd, _onehot_codes(hi, n_codewords)))

    return tile


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_codewords", "n_valid", "bq", "bn", "interpret"),
)
def fused_adc_pallas(
    lut2d: jax.Array,
    codes: jax.Array,
    *,
    k: int,
    n_codewords: int,
    n_valid: int,
    bq: int = BQ,
    bn: int = BN,
    interpret: bool = False,
    mask: jax.Array | None = None,
):
    """[Q, M*K] int8 LUT x [N, M] uint8 codes -> ([Q, k] f32, [Q, k] i32).

    Streaming fused ADC + top-k; rows with id >= ``n_valid`` (padding)
    are masked in-kernel, as is an optional [N] predicate ``mask``.
    """
    s, i, _counts = _fused_call(make_adc_tile(n_codewords), [lut2d], codes,
                                k=k, n_valid=n_valid, bq=bq, bn=bn,
                                interpret=interpret, mask=mask)
    return s, i


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_codewords", "n_valid", "bq", "bn", "interpret"),
)
def fused_adc4_pallas(
    lut_even: jax.Array,
    lut_odd: jax.Array,
    packed: jax.Array,
    *,
    k: int,
    n_codewords: int,
    n_valid: int,
    bq: int = BQ,
    bn: int = BN,
    interpret: bool = False,
    mask: jax.Array | None = None,
):
    """Packed-nibble variant: [Q, (M/2)*K] int8 LUT planes x [N, M/2]
    uint8 packed codes -> top-k, unpacking two-codewords-per-byte
    in-kernel."""
    s, i, _counts = _fused_call(make_adc4_tile(n_codewords),
                                [lut_even, lut_odd], packed, k=k,
                                n_valid=n_valid, bq=bq, bn=bn,
                                interpret=interpret, mask=mask)
    return s, i
