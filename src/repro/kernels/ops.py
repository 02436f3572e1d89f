"""Public jit'd wrappers for the Pallas kernels.

Responsibilities:
  * pad ragged (Q, N) up to tile multiples and slice the result back,
  * pick sane tile sizes for small inputs,
  * run ``interpret=True`` automatically off-TPU (this container is CPU) so
    the same call sites work everywhere,
  * expose a ``use_pallas=False`` escape hatch that routes to the pure-jnp
    reference (used under ``shard_map`` cells where the XLA int8 dot is
    already optimal and for the dry-run, where kernel lowering to the host
    platform is not the point).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import adc as _adc
from repro.kernels import fused_topk as _fused
from repro.kernels import packed as _packed
from repro.kernels import qmip as _qmip
from repro.kernels import ql2 as _ql2
from repro.kernels import quantize as _quantize
from repro.kernels import ref as _ref
from repro.tune import table as _tune


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pick_tile(n: int, pref: int, unit: int = 8) -> int:
    """Largest tile <= pref that keeps padding waste small for tiny n.

    ``pref`` is rounded up to the unit first — a tuned (or caller-passed)
    tile that is off-unit would otherwise leak an illegal block shape
    into the kernel grid.
    """
    pref = max(unit, _round_up(pref, unit))
    if n >= pref:
        return pref
    return max(unit, _round_up(n, unit))


# -- registered fallback rows: today's constants, the dispatch floor -------
# (dispatch precedence is tuned table > these rows; DESIGN.md §13)
_tune.register_fallback("fused_topk", _tune.TuneConfig(
    "fused", bq=_fused.BQ, bn=_fused.BN, chunk=16384))
_tune.register_fallback("packed", _tune.TuneConfig(
    "fused", bq=_packed.BQ, bn=_packed.BN, chunk=16384))
_tune.register_fallback("fused_adc", _tune.TuneConfig(
    "fused", bq=_adc.BQ, bn=_adc.BN, chunk=16384))
_tune.register_fallback("scan", _tune.TuneConfig("scan", chunk=16384))


def _pad_rows(a: jax.Array, rows: int) -> jax.Array:
    pad = rows - a.shape[0]
    if pad == 0:
        return a
    return jnp.pad(a, ((0, pad), (0, 0)))


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def qmip(
    q_codes: jax.Array,
    x_codes: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """int8 MIP scores [Q, N] int32 — fused MXU kernel with padding."""
    if not use_pallas:
        return _ref.qmip_ref(q_codes, x_codes)
    interp = (not _on_tpu()) if interpret is None else interpret
    Q, _ = q_codes.shape
    N, _ = x_codes.shape
    bq = _pick_tile(Q, _qmip.BQ)
    bn = _pick_tile(N, _qmip.BN)
    qp = _pad_rows(q_codes, _round_up(Q, bq))
    xp = _pad_rows(x_codes, _round_up(N, bn))
    out = _qmip.qmip_pallas(qp, xp, bq=bq, bn=bn, interpret=interp)
    return out[:Q, :N]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def ql2(
    q_codes: jax.Array,
    x_codes: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """int8 negated squared-L2 scores [Q, N] int32."""
    if not use_pallas:
        return _ref.ql2_ref(q_codes, x_codes)
    interp = (not _on_tpu()) if interpret is None else interpret
    Q, _ = q_codes.shape
    N, _ = x_codes.shape
    bq = _pick_tile(Q, _ql2.BQ)
    bn = _pick_tile(N, _ql2.BN)
    qp = _pad_rows(q_codes, _round_up(Q, bq))
    xp = _pad_rows(x_codes, _round_up(N, bn))
    out = _ql2.ql2_pallas(qp, xp, bq=bq, bn=bn, interpret=interp)
    return out[:Q, :N]


def fused_query_tile(
    q: int | None = None,
    n: int | None = None,
    d: int | None = None,
    *,
    metric: str = "ip",
    bits: int = 8,
    packed: bool = False,
) -> int:
    """Query rows per fused-kernel tile — the corpus re-stream granularity
    (engine stats derive bytes_read from it; one source of truth).

    With a workload shape, the installed TuneTable is consulted first
    (the entry's ``bq``); without one — or on a table miss — the kernel
    family's registered fallback constant answers, exactly as before.
    """
    kernel = "packed" if packed else "fused_topk"
    if q is not None and n is not None and d is not None:
        cfg = _tune.lookup(kernel, metric, bits, q, n, d)
        if cfg is not None and cfg.bq is not None:
            return cfg.bq
    return _tune.fallback(kernel).bq


def fused_adc_query_tile(
    q: int | None = None,
    n: int | None = None,
    m: int | None = None,
    *,
    metric: str = "ip",
    bits: int = 8,
) -> int:
    """Query rows per fused-ADC tile (each carries its LUT block) —
    table-first, registered constant as the fallback row."""
    if q is not None and n is not None and m is not None:
        cfg = _tune.lookup("fused_adc", metric, bits, q, n, m)
        if cfg is not None and cfg.bq is not None:
            return cfg.bq
    return _tune.fallback("fused_adc").bq


def _split_nibble_queries(q_codes: jax.Array):
    """[Q, d] int4-valued codes -> the (even, odd) dim halves [Q, d/2]."""
    assert q_codes.shape[1] % 2 == 0, q_codes.shape
    return q_codes[:, 0::2], q_codes[:, 1::2]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def qmip4(
    q_codes: jax.Array,
    packed: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """int4 MIP scores [Q, N] int32 over bit-packed corpus codes.

    ``q_codes`` are full-width [Q, d] int4-valued int8 (queries stay
    unpacked — they are tiny); ``packed`` is [N, d/2] uint8.
    """
    if not use_pallas:
        return _ref.qmip4_ref(q_codes, packed)
    interp = (not _on_tpu()) if interpret is None else interpret
    Q = q_codes.shape[0]
    N = packed.shape[0]
    qe, qo = _split_nibble_queries(q_codes)
    bq = _pick_tile(Q, _packed.BQ)
    bn = _pick_tile(N, _packed.BN)
    qe = _pad_rows(qe, _round_up(Q, bq))
    qo = _pad_rows(qo, _round_up(Q, bq))
    xp = _pad_rows(packed, _round_up(N, bn))
    out = _packed.qmip4_pallas(qe, qo, xp, bq=bq, bn=bn, interpret=interp)
    return out[:Q, :N]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def ql24(
    q_codes: jax.Array,
    packed: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """int4 negated squared-L2 scores [Q, N] int32 over packed codes."""
    if not use_pallas:
        return _ref.ql24_ref(q_codes, packed)
    interp = (not _on_tpu()) if interpret is None else interpret
    Q = q_codes.shape[0]
    N = packed.shape[0]
    qe, qo = _split_nibble_queries(q_codes)
    bq = _pick_tile(Q, _packed.BQ)
    bn = _pick_tile(N, _packed.BN)
    qe = _pad_rows(qe, _round_up(Q, bq))
    qo = _pad_rows(qo, _round_up(Q, bq))
    xp = _pad_rows(packed, _round_up(N, bn))
    out = _packed.ql24_pallas(qe, qo, xp, bq=bq, bn=bn, interpret=interp)
    return out[:Q, :N]


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "packed", "bq", "bn", "use_pallas",
                     "interpret", "merge_counts"),
)
def fused_topk(
    q: jax.Array,
    x: jax.Array,
    k: int,
    metric: str,
    *,
    packed: bool = False,
    bq: int | None = None,
    bn: int | None = None,
    use_pallas: bool = True,
    interpret: bool | None = None,
    mask: jax.Array | None = None,
    merge_counts: bool = False,
):
    """Streaming fused score + top-k: ([Q, k] f32 scores, [Q, k] i32 ids).

    ``metric`` is ``ip`` or ``l2`` (angular needs norm rescale — engine
    routes it to the unfused scan).  With ``packed=True``, ``x`` is
    [N, d/2] uint8 int4 codes and ``q`` full-width [Q, d] int4-valued
    int8.  ``bq`` overrides the query tile and ``bn`` caps the corpus
    tile (the VMEM working-set knobs — tuned dispatch threads the
    TuneTable entry through both; bare calls keep the family constants).
    An optional [N] ``mask`` (nonzero = allowed) ANDs into the kernels'
    pad fence — filtered rows die like pad rows, at no extra bytes read.
    The [Q, N] score matrix never reaches HBM on the Pallas path;
    ``use_pallas=False`` is the XLA reference (materializes scores, used
    for parity tests and as the shard_map cell fallback).
    With ``merge_counts=True`` a third item follows: the kernel's
    (merge steps, corpus tiles visited) summed over its query tiles, as
    int32 device scalars (None on the reference, which merges nothing).
    """
    assert metric in ("ip", "l2"), metric
    Q = q.shape[0]
    N = x.shape[0]
    k = min(k, N)
    if not use_pallas:
        if packed:
            s = _ref.qmip4_ref(q, x) if metric == "ip" else _ref.ql24_ref(q, x)
        elif jnp.issubdtype(q.dtype, jnp.integer):
            s = _ref.qmip_ref(q, x) if metric == "ip" else _ref.ql2_ref(q, x)
        else:
            from repro.core import distances as D

            s = D.scores(q, x, metric)
        if mask is not None:
            # the NEG sentinel topk_ref already turns into id -1
            s = jnp.where(mask.astype(bool)[None, :], s.astype(jnp.float32),
                          jnp.finfo(jnp.float32).min)
        out = _ref.topk_ref(s, k, N)
        return (*out, None) if merge_counts else out
    interp = (not _on_tpu()) if interpret is None else interpret
    bq = _pick_tile(Q, bq or _fused.BQ)
    # an explicit bn is honored (tuned tiles may exceed the constant —
    # the tuning space owns the VMEM bound); bare calls keep the constant
    bn = _pick_tile(N, bn or _fused.BN)
    mp = (None if mask is None else
          jnp.pad(mask.astype(jnp.int8), (0, _round_up(N, bn) - N)))
    if packed:
        qe, qo = _split_nibble_queries(q)
        qe = _pad_rows(qe, _round_up(Q, bq))
        qo = _pad_rows(qo, _round_up(Q, bq))
        with jax.named_scope("kernels.pad_codes"):
            xp = _pad_rows(x, _round_up(N, bn))
        s, i, counts = _fused.fused_topk4_pallas(
            qe, qo, xp, k=k, metric=metric, n_valid=N,
            bq=bq, bn=bn, interpret=interp, mask=mp,
        )
    else:
        qp = _pad_rows(q, _round_up(Q, bq))
        with jax.named_scope("kernels.pad_codes"):
            xp = _pad_rows(x, _round_up(N, bn))
        s, i, counts = _fused.fused_topk_pallas(
            qp, xp, k=k, metric=metric, n_valid=N,
            bq=bq, bn=bn, interpret=interp, mask=mp,
        )
    if merge_counts:
        return s[:Q], i[:Q], _fused.merge_counts(counts)
    return s[:Q], i[:Q]


@functools.partial(
    jax.jit,
    static_argnames=("k", "packed", "bq", "bn", "use_pallas", "interpret"),
)
def fused_adc_topk(
    lut: jax.Array,
    codes: jax.Array,
    k: int,
    *,
    packed: bool = False,
    bq: int | None = None,
    bn: int | None = None,
    use_pallas: bool = True,
    interpret: bool | None = None,
    mask: jax.Array | None = None,
):
    """Streaming fused ADC + top-k: ([Q, k] f32 scores, [Q, k] i32 ids).

    ``lut`` is the [Q, M, K] int8-quantized lookup table (K = codewords
    per subspace); ``codes`` is [N, M] uint8, or — with ``packed=True`` —
    [N, ceil(M/2)] uint8 two-nibbles-per-byte (an odd logical M was
    padded with a zero-code column at pack time; the LUT grows a matching
    zero subspace slice here, so the pad contributes nothing).  The
    [Q, N] ADC matrix never reaches HBM on the Pallas path;
    ``use_pallas=False`` materializes it via the ref.py oracle (parity
    tests, XLA fallback).
    """
    Q, m, n_codewords = lut.shape
    N = codes.shape[0]
    k = min(k, N)
    if packed and m < 2 * codes.shape[1]:      # odd-M zero-code pad column
        lut = jnp.pad(lut, ((0, 0), (0, 2 * codes.shape[1] - m), (0, 0)))
    if not use_pallas:
        s = _ref.adc4_ref(lut, codes) if packed else _ref.adc_ref(lut, codes)
        if mask is not None:
            s = jnp.where(mask.astype(bool)[None, :], s.astype(jnp.float32),
                          jnp.finfo(jnp.float32).min)
        return _ref.topk_ref(s, k, N)
    interp = (not _on_tpu()) if interpret is None else interpret
    bq = _pick_tile(Q, bq or _adc.BQ)
    bn = _pick_tile(N, bn or _adc.BN)
    cp = _pad_rows(codes, _round_up(N, bn))
    mp = (None if mask is None else
          jnp.pad(mask.astype(jnp.int8), (0, _round_up(N, bn) - N)))
    if packed:
        le = lut[:, 0::2, :].reshape(Q, -1)
        lo = lut[:, 1::2, :].reshape(Q, -1)
        le = _pad_rows(le, _round_up(Q, bq))
        lo = _pad_rows(lo, _round_up(Q, bq))
        s, i = _adc.fused_adc4_pallas(
            le, lo, cp, k=k, n_codewords=n_codewords, n_valid=N,
            bq=bq, bn=bn, interpret=interp, mask=mp,
        )
    else:
        l2d = _pad_rows(lut.reshape(Q, -1), _round_up(Q, bq))
        s, i = _adc.fused_adc_pallas(
            l2d, cp, k=k, n_codewords=n_codewords, n_valid=N,
            bq=bq, bn=bn, interpret=interp, mask=mp,
        )
    return s[:Q], i[:Q]


@functools.partial(jax.jit, static_argnames=("bits", "use_pallas", "interpret"))
def quantize(
    x: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    zero: jax.Array,
    *,
    bits: int = 8,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Eq. 1 corpus compression [N, d] f32 -> int8."""
    if not use_pallas:
        return _ref.quantize_ref(x, lo, hi, zero, bits=bits)
    interp = (not _on_tpu()) if interpret is None else interpret
    N, _ = x.shape
    bn = _pick_tile(N, _quantize.BN, unit=8)
    xp = _pad_rows(x, _round_up(N, bn))
    out = _quantize.quantize_pallas(
        xp, lo, hi, zero, bits=bits, bn=bn, interpret=interp
    )
    return out[:N]
