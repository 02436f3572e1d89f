"""Distance functions phi — full-precision references and their integer
counterparts (paper §3.1: phi : Z^d x Z^d -> Z).

The quantized variants take *integer codes* (int8/int16) and accumulate in
int32 via ``lax.dot_general(..., preferred_element_type=int32)``, which on
TPU lowers to the MXU's native int8 x int8 -> int32 path (2x bf16 peak on
v5e) and on CPU to VNNI-style integer dot products.  This is the
implementation-level substitution the paper makes inside HNSW/FAISS/NGT.

Convention: all ``*_scores`` functions are batched [Q, d] x [N, d] -> [Q, N]
and return *larger-is-closer* scores (inner product; negated L2) so that a
single top-k applies to every metric.  Float dots run at ``HIGHEST``
precision: a TPU's default f32 dot rounds its inputs to bf16, which an
fp32 arm or an exact rerank pass must not do.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

Metric = str  # "ip" | "l2" | "angular"

_F32 = jax.lax.Precision.HIGHEST

_VALID_METRICS = ("ip", "l2", "angular")


# --------------------------------------------------------------------------
# Full-precision references
# --------------------------------------------------------------------------

def ip_scores(q: jax.Array, x: jax.Array) -> jax.Array:
    """Maximum-inner-product scores, [Q, N] f32."""
    return jnp.dot(q.astype(jnp.float32), x.astype(jnp.float32).T,
                   precision=_F32)


def l2_scores(q: jax.Array, x: jax.Array) -> jax.Array:
    """Negated squared L2 (larger = closer), [Q, N] f32."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qq = jnp.sum(q * q, axis=-1, keepdims=True)          # [Q, 1]
    xx = jnp.sum(x * x, axis=-1)[None, :]                # [1, N]
    return -(qq + xx - 2.0 * jnp.dot(q, x.T, precision=_F32))


def angular_scores(q: jax.Array, x: jax.Array) -> jax.Array:
    """Cosine similarity, [Q, N] f32."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    return jnp.dot(qn, xn.T, precision=_F32)


# --------------------------------------------------------------------------
# Quantized (integer-domain) counterparts
# --------------------------------------------------------------------------

def _int_matmul(a: jax.Array, b_t: jax.Array) -> jax.Array:
    """[Q, d] int  x  [N, d] int  ->  [Q, N] int32 via one dot_general.

    ``preferred_element_type=int32`` is what turns this into the MXU's
    int8 path instead of a float fallback.
    """
    return jax.lax.dot_general(
        a,
        b_t,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def qip_scores(qc: jax.Array, xc: jax.Array) -> jax.Array:
    """phi_IP over codes: int32 inner product, [Q, N].

    Order-equivalence: with shared constants (k, s) per dim,
    IP(Q(a),Q(q)) ~= (IP(a,q) - k·sum(a) - k·sum(q) + d·k^2) / s^2, a
    positive-affine map of IP(a,q) for fixed q when k ~ 0 (narrow-band,
    zero-centred corpora — Fig. 1), hence Definition-2 preservation up to
    rounding/clamping.
    """
    return _int_matmul(qc, xc)


def ql2_scores(qc: jax.Array, xc: jax.Array) -> jax.Array:
    """Negated squared L2 over codes, int32 [Q, N].

    ||a-b||^2 = ||a||^2 + ||b||^2 - 2 a·b, all in int32.  d * (2^{B} - 1)^2
    must stay below 2^31: fine for d <= 32k at B=8.
    """
    qi = qc.astype(jnp.int32)
    xi = xc.astype(jnp.int32)
    qq = jnp.sum(qi * qi, axis=-1, keepdims=True)
    xx = jnp.sum(xi * xi, axis=-1)[None, :]
    return -(qq + xx - 2 * _int_matmul(qc, xc))


def qangular_scores(qc: jax.Array, xc: jax.Array) -> jax.Array:
    """Cosine over codes: int32 dot, f32 norm rescale, [Q, N] f32.

    The integer part (the O(Q·N·d) work) runs on the int8 MXU path; the
    O(Q+N) norms are f32.
    """
    dot = _int_matmul(qc, xc).astype(jnp.float32)
    qn = jnp.sqrt(jnp.sum(qc.astype(jnp.float32) ** 2, axis=-1, keepdims=True))
    xn = jnp.sqrt(jnp.sum(xc.astype(jnp.float32) ** 2, axis=-1))[None, :]
    return dot / jnp.maximum(qn * xn, 1e-12)


# --------------------------------------------------------------------------
# Dispatch
# --------------------------------------------------------------------------

_FP: dict[str, Callable] = {"ip": ip_scores, "l2": l2_scores, "angular": angular_scores}
_Q: dict[str, Callable] = {"ip": qip_scores, "l2": ql2_scores, "angular": qangular_scores}


def scores(q: jax.Array, x: jax.Array, metric: Metric, quantized: bool = False) -> jax.Array:
    """Batched larger-is-closer scores for any supported metric."""
    if metric not in _VALID_METRICS:
        raise ValueError(f"metric must be one of {_VALID_METRICS}, got {metric!r}")
    fn = (_Q if quantized else _FP)[metric]
    return fn(q, x)


# --------------------------------------------------------------------------
# Per-query candidate scoring (q [Q, d] against gathered rows [Q, W, d])
# --------------------------------------------------------------------------

def _bmm(q: jax.Array, rows: jax.Array) -> jax.Array:
    """f32 batched row dot, [Q, W].  One einsum rather than a vmapped
    per-query matmul: XLA lowers the einsum identically inside and
    outside ``shard_map``, which is what makes sharded plans bit-match
    their unsharded twins (a vmapped [1, d] x [d, W] dot picks a
    different f32 accumulation order under ``shard_map``)."""
    return jnp.einsum(
        "qd,qwd->qw", q.astype(jnp.float32), rows.astype(jnp.float32),
        precision=_F32,
    )


def _int_bmm(q: jax.Array, rows: jax.Array) -> jax.Array:
    """int batched row dot with int32 accumulation (exact), [Q, W]."""
    return jax.lax.dot_general(
        q,
        rows,
        dimension_numbers=(((1,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )


def scores_among(
    q: jax.Array, rows: jax.Array, metric: Metric, quantized: bool = False
) -> jax.Array:
    """Per-query candidate scores: q [Q, d] vs rows [Q, W, d] -> [Q, W].

    The candidate-list twin of :func:`scores` — same metric semantics,
    but each query scores its *own* gathered row set.  All reductions
    are batched (einsum / dot_general), never per-query vmapped dots,
    so the lowering is stable across jit and ``shard_map`` contexts
    (DESIGN.md §15 bit-parity).
    """
    if metric not in _VALID_METRICS:
        raise ValueError(f"metric must be one of {_VALID_METRICS}, got {metric!r}")
    if quantized:
        if metric == "ip":
            return _int_bmm(q, rows)
        if metric == "l2":
            qi = q.astype(jnp.int32)
            xi = rows.astype(jnp.int32)
            qq = jnp.sum(qi * qi, axis=-1, keepdims=True)     # [Q, 1]
            xx = jnp.sum(xi * xi, axis=-1)                    # [Q, W]
            return -(qq + xx - 2 * _int_bmm(q, rows))
        dot = _int_bmm(q, rows).astype(jnp.float32)
        qn = jnp.sqrt(jnp.sum(q.astype(jnp.float32) ** 2, axis=-1,
                              keepdims=True))
        xn = jnp.sqrt(jnp.sum(rows.astype(jnp.float32) ** 2, axis=-1))
        return dot / jnp.maximum(qn * xn, 1e-12)
    qf = q.astype(jnp.float32)
    xf = rows.astype(jnp.float32)
    if metric == "ip":
        return _bmm(qf, xf)
    if metric == "l2":
        qq = jnp.sum(qf * qf, axis=-1, keepdims=True)
        xx = jnp.sum(xf * xf, axis=-1)
        return -(qq + xx - 2.0 * _bmm(qf, xf))
    qn = qf / jnp.maximum(jnp.linalg.norm(qf, axis=-1, keepdims=True), 1e-12)
    xn = xf / jnp.maximum(jnp.linalg.norm(xf, axis=-1, keepdims=True), 1e-12)
    return _bmm(qn, xn)


def pairwise_distance(a: jax.Array, b: jax.Array, metric: Metric, quantized: bool = False) -> jax.Array:
    """Single-pair convenience wrapper (used by graph-walk code paths)."""
    return scores(a[None, :], b[None, :], metric, quantized)[0, 0]
