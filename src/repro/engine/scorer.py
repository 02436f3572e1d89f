"""The scoring engine: every index's query hot path in one place.

``topk`` / ``topk_among`` / ``make_score_set`` own metric x bits dispatch,
chunking, corpus padding, invalid-id masking and streaming top-k, so index
classes hold *structure* (lists, graphs, codebooks) and delegate every
score to the engine.  Padding is id-masked here, centrally — the L2
zero-sentinel hazard (a zero pad row out-scoring real rows under negated
L2) cannot reach callers, because no caller sees pad rows at all.

Kernel dispatch table (metric x storage):

    storage          ip               l2               angular
    fp32             fused_topk       fused_topk       scan + angular
    int8             fused_topk       fused_topk       scan + qangular
    int4 packed      fused_topk4      fused_topk4      scan + unpack + qangular
    pq + int8 LUT    fused_adc_topk   fused_adc_topk   (unsupported)
    pq + fp32 LUT    ADC LUT scan     ADC LUT scan     (unsupported)

`fused_topk*` / `fused_adc_topk` are the streaming Pallas kernels (score
tiles + running top-k carried in VMEM, no [Q, N] matrix in HBM; the ADC
kernel additionally keeps the int8 LUT block VMEM-resident and unpacks
4-bit packed codewords in-kernel); the scan paths stream `lax.scan`
chunks through ``merge_topk`` with the same masking contract.

Row-id bases: shard-local stores carry ``base`` and the engine rebases
returned ids, so the distributed merge (``distributed_topk``, below)
composes without per-caller offset arithmetic.  ``remap_ids`` is the
id-remap gather segmented indexes use to turn internal row ids back into
caller-visible external ids.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import distances as D
from repro.core import pack as PK
from repro.engine.store import CodeStore, PQStore
from repro.kernels import ops as K
from repro.tune import table as T

NEG = float(jnp.finfo(jnp.float32).min)

#: corpus rows per fused-kernel tile — the *fallback* when no TuneTable
#: entry matches (dispatch precedence: tuned table > these constants;
#: the kernel may still shrink the tile for small corpora)
FUSED_TILE = 512


ScoreSet = Callable[[jax.Array, jax.Array], jax.Array]


# --------------------------------------------------------------------------
# generic streaming machinery (canonical home; knn.topk is a shim)
# --------------------------------------------------------------------------

def merge_topk(
    scores_a: jax.Array,
    ids_a: jax.Array,
    scores_b: jax.Array,
    ids_b: jax.Array,
    k: int,
):
    """Merge two [Q, ka]/[Q, kb] candidate sets into the best k."""
    s = jnp.concatenate([scores_a, scores_b], axis=-1)
    i = jnp.concatenate([ids_a, ids_b], axis=-1)
    top_s, pos = jax.lax.top_k(s, k)
    top_i = jnp.take_along_axis(i, pos, axis=-1)
    return top_s, top_i


def pad_rows(a: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    """Zero-pad rows to a multiple; engine paths id-mask the pad rows."""
    n = a.shape[0]
    target = ((n + multiple - 1) // multiple) * multiple
    if target == n:
        return a, n
    return jnp.pad(a, ((0, target - n), (0, 0))), n


def remap_ids(ids: jax.Array, id_map: jax.Array) -> jax.Array:
    """Gather ``id_map[ids]`` with -1 (no hit) passed through.

    The id-remap helper behind segmented/mutable indexes: engine paths
    return *internal* row ids (segment base + local row); the stream
    layer's plans map them to the caller's external ids through one
    gather — tombstoned / empty slots stay -1.
    """
    safe = jnp.clip(ids, 0, id_map.shape[0] - 1)
    return jnp.where(ids >= 0, id_map[safe].astype(jnp.int32), -1)


def _stream_topk(q, data, k, chunk, n_valid, tile_scores, mask=None):
    """THE streaming top-k loop: every scan-shaped top-k routes here.

    Scores ``data`` in ``chunk``-row tiles through ``tile_scores(q, tile)``
    with a running [Q, k] best set (``merge_topk``), id-masking rows
    >= ``n_valid`` at the source.  An optional [n] predicate ``mask``
    (True = allowed) ANDs into the same fence — the filter dataflow of
    DESIGN.md §16: filtered rows die exactly like pad rows, inside the
    tile the scan was reading anyway, so ``bytes_read`` is unchanged.
    Callers wrap it in their own jit (``_scan_topk`` specializes on the
    store pytree, ``chunked_topk`` on a static score_fn) so there is
    exactly one implementation of the chunked-merge formulation.
    """
    Q = q.shape[0]
    n = data.shape[0]

    if n <= chunk:
        s = tile_scores(q, data)
        gid = jnp.arange(n, dtype=jnp.int32)[None, :]
        ok = gid < n_valid
        if mask is not None:
            ok = ok & mask.astype(bool)[None, :]
        s = jnp.where(ok, s, NEG)
        ids = jnp.where(ok, jnp.broadcast_to(gid, s.shape), -1)
        return merge_topk(
            jnp.full((Q, k), NEG, jnp.float32), jnp.full((Q, k), -1, jnp.int32),
            s, ids, k,
        )

    padded, _ = pad_rows(data, chunk)
    n_chunks = padded.shape[0] // chunk
    tiles = padded.reshape(n_chunks, chunk, padded.shape[-1])

    init = (jnp.full((Q, k), NEG, jnp.float32), jnp.full((Q, k), -1, jnp.int32))

    if mask is not None:
        mtiles = jnp.pad(
            mask.astype(bool), (0, padded.shape[0] - n)
        ).reshape(n_chunks, chunk)

        def step_masked(carry, inp):
            best_s, best_i = carry
            tile, tile_idx, mrow = inp
            s = tile_scores(q, tile)
            gid = tile_idx * chunk + jnp.arange(chunk, dtype=jnp.int32)[None, :]
            ok = (gid < n_valid) & mrow[None, :]
            s = jnp.where(ok, s, NEG)
            ids = jnp.where(ok, jnp.broadcast_to(gid, s.shape), -1)
            return merge_topk(best_s, best_i, s, ids, k), None

        (best_s, best_i), _ = jax.lax.scan(
            step_masked, init,
            (tiles, jnp.arange(n_chunks, dtype=jnp.int32), mtiles),
        )
        return best_s, best_i

    def step(carry, inp):
        best_s, best_i = carry
        tile, tile_idx = inp
        s = tile_scores(q, tile)
        gid = tile_idx * chunk + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        ok = gid < n_valid                             # id-mask at the source
        s = jnp.where(ok, s, NEG)
        ids = jnp.where(ok, jnp.broadcast_to(gid, s.shape), -1)
        return merge_topk(best_s, best_i, s, ids, k), None

    (best_s, best_i), _ = jax.lax.scan(
        step, init, (tiles, jnp.arange(n_chunks, dtype=jnp.int32))
    )
    return best_s, best_i


@partial(jax.jit, static_argnames=("k", "score_fn", "chunk", "n_valid"))
def chunked_topk(
    queries: jax.Array,
    corpus: jax.Array,
    k: int,
    score_fn: Callable[[jax.Array, jax.Array], jax.Array],
    chunk: int = 16384,
    n_valid: int | None = None,
    mask: jax.Array | None = None,
):
    """Exact top-k of score_fn(queries, corpus) without materializing [Q, N].

    The generic score-fn entry point over ``_stream_topk`` (the index hot
    path uses ``engine.topk`` and the fused Pallas kernels instead).  Any
    corpus length works — rows are padded to the chunk internally and
    rows >= ``n_valid`` (default: all real rows valid) are id-masked at
    the source, so callers no longer pre-pad or post-mask.  ``score_fn``
    must be a stable (hashable) callable: it is a static jit argument.
    """
    n_valid = corpus.shape[0] if n_valid is None else n_valid

    def tile_scores(q, tile):
        return score_fn(q, tile).astype(jnp.float32)

    return _stream_topk(queries, corpus, k, chunk, n_valid, tile_scores,
                        mask=mask)


# --------------------------------------------------------------------------
# stats: uniform per-search accounting for SearchResult.stats
# --------------------------------------------------------------------------

def search_stats(store, *, candidates: int, chunks: int, rows_read: int) -> dict[str, Any]:
    """The uniform accounting block every kind reports.

    candidates  rows scored per query (an upper bound for graph walks,
                whose while-loops stop early on convergence)
    chunks      corpus tiles / scan chunks touched
    bytes_read  payload bytes gathered or streamed for the whole batch
    """
    return {
        "candidates": int(candidates),
        "chunks": int(chunks),
        "bytes_read": int(rows_read) * store.row_bytes,
        "bits": int(getattr(store, "bits", 8)),
        "packed": bool(getattr(store, "packed", False)),
    }


# --------------------------------------------------------------------------
# score-set closures (graph walks gather rows by id)
# --------------------------------------------------------------------------

def make_score_set(store: CodeStore, metric: str) -> ScoreSet:
    """(query [d], ids [m]) -> larger-is-closer [m] f32 over store rows."""

    def score_set(q: jax.Array, ids: jax.Array) -> jax.Array:
        vecs = store.take(ids)
        return D.scores(
            q[None], vecs, metric, quantized=store.quantized
        )[0].astype(jnp.float32)

    return score_set


# --------------------------------------------------------------------------
# full-corpus streaming top-k
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "metric", "chunk"))
def _scan_topk(q: jax.Array, store: CodeStore, k: int, metric: str, chunk: int,
               mask: jax.Array | None = None):
    """Unfused fallback: ``_stream_topk`` over the store's tiles.

    Used for metrics the fused kernel does not cover (angular needs the
    per-row norm rescale).  Packed tiles are unpacked chunk-by-chunk — the
    full-width corpus never materializes.
    """

    def tile_scores(qq, tile):
        rows = PK.unpack_int4(tile) if store.packed else tile
        return D.scores(qq, rows, metric, quantized=store.quantized).astype(
            jnp.float32
        )

    return _stream_topk(q, store.data, k, chunk, store.n, tile_scores,
                        mask=mask)


def topk(
    queries: jax.Array,
    store: "CodeStore | PQStore",
    k: int,
    metric: str,
    *,
    chunk: int = 16384,
    prepared: bool = False,
    use_pallas: bool = True,
    interpret: bool | None = None,
    mask: jax.Array | None = None,
):
    """Exact top-k of the whole store: (scores [Q, k] f32, ids, stats).

    When k > n the tail is padded with (-inf, -1) — the uniform
    ``SearchResult`` contract.  ``prepared=True`` means ``queries`` are
    already in the store's code space (skip ``encode_queries``).
    ``chunk`` sizes the scan chunks on the unfused path and caps the
    fused kernel's corpus tile (the working-set bound either way).
    An optional [n] ``mask`` (True = allowed; store-local row space,
    before ``base`` rebasing) rides the id-masking fence on every path —
    filtered rows cost nothing extra to skip, so stats are unchanged.

    Dispatch consults the installed TuneTable first (``repro.tune``):
    a matching entry decides fused-vs-scan and the tile/chunk shapes;
    on a miss, today's constants apply unchanged.  ``stats["tuned"]``
    records which happened.  The fused kernel's stats add
    ``merge_steps`` and ``merge_tiles`` (its top-k merge steps and the
    corpus tiles it visited, over all query tiles) as int32 device
    scalars, so reading them is the caller's choice of when to wait.
    """
    if isinstance(store, PQStore):
        if metric == "angular":
            raise ValueError(
                "PQ/ADC scoring supports ip and l2 only (see the dispatch "
                "table in this module's docstring)"
            )
        cfg = T.lookup("fused_adc", metric, store.bits,
                       jnp.shape(queries)[0], store.n, store.m)
        s, i = _topk_pq(queries, store, k, metric, chunk,
                        use_pallas=use_pallas, interpret=interpret, cfg=cfg,
                        mask=mask)
        if s.shape[1] < k:               # uniform [Q, k] contract: -1 pads
            s = jnp.pad(s, ((0, 0), (0, k - s.shape[1])), constant_values=NEG)
            i = jnp.pad(i, ((0, 0), (0, k - i.shape[1])), constant_values=-1)
        fused, tile, chunk_eff = _pq_fused(store, metric, chunk,
                                           use_pallas, interpret, cfg)
        if fused:
            n_chunks = -(-store.n // tile)
            # like the CodeStore kernel, the fused grid re-streams the
            # code matrix once per query tile (the LUT block is what
            # stays VMEM-resident, not the codes)
            bq = (cfg.bq if cfg is not None and cfg.bq is not None
                  else K.fused_adc_query_tile())
            passes = max(1, -(-jnp.shape(queries)[0] // bq))
        else:
            n_chunks = max(1, -(-store.n // chunk_eff))
            passes = 1
        stats = search_stats(store, candidates=store.n, chunks=n_chunks,
                             rows_read=store.n * passes)
        stats["tuned"] = cfg is not None
        return s, i, stats

    q = queries if prepared else store.encode_queries(queries)
    k_eff = min(k, store.n)

    kernel = "packed" if store.packed else "fused_topk"
    cfg = T.lookup(kernel if metric in ("ip", "l2") else "scan",
                   metric, store.bits, jnp.shape(q)[0], store.n,
                   jnp.shape(q)[1])
    tile = min(FUSED_TILE, max(8, chunk))
    chunk_eff = chunk
    bq = None
    if cfg is not None:
        if cfg.impl == "fused":
            tile = cfg.bn or tile
            bq = cfg.bq
        else:                            # measured crossover says scan
            chunk_eff = max(8, cfg.chunk or chunk)
    # The fused Pallas kernel is the TPU hot path (or forced via
    # interpret=True for CI wiring tests).  Off-TPU, interpret mode is a
    # parity tool, not a serving path — the XLA streaming scan is ~20x
    # faster there and keeps the same O(Q * (k + chunk)) working set.
    # Corpora that fit one tile (IVF centroids, graph seeds) also skip
    # the kernel: there is nothing to stream.
    fused = (
        metric in ("ip", "l2")
        and use_pallas
        and store.n > tile
        and (cfg is None or cfg.impl == "fused")
        and (bool(interpret) or jax.default_backend() == "tpu")
    )
    merge = {}
    if fused:
        s, i, (steps, tiles) = K.fused_topk(
            q, store.data, k_eff, metric, packed=store.packed,
            bq=bq, bn=tile, interpret=interpret, mask=mask, merge_counts=True,
        )
        # the kernel's merge counter, left on the device
        merge = {"merge_steps": steps, "merge_tiles": tiles}
        chunks = -(-store.n // tile)
        # the fused grid re-streams the corpus once per bq-row query tile
        # (queries are VMEM-resident within a tile, not across tiles)
        passes = max(1, -(-q.shape[0] // (bq or K.fused_query_tile())))
    else:
        s, i = _scan_topk(q, store, k_eff, metric, chunk_eff, mask)
        chunks = max(1, -(-store.n // chunk_eff))
        passes = 1                       # one scan, all queries resident

    if k_eff < k:                        # uniform [Q, k] contract: -1 pads
        s = jnp.pad(s, ((0, 0), (0, k - k_eff)), constant_values=NEG)
        i = jnp.pad(i, ((0, 0), (0, k - k_eff)), constant_values=-1)
    if store.base:
        i = jnp.where(i >= 0, i + store.base, -1)
    stats = search_stats(store, candidates=store.n, chunks=chunks,
                         rows_read=store.n * passes)
    stats["tuned"] = cfg is not None
    return s, i, {**stats, **merge}


# --------------------------------------------------------------------------
# candidate-set top-k (IVF fine scoring and friends)
# --------------------------------------------------------------------------

#: bound on one block of gathered candidate rows, [queries, L, d] at four
#: bytes an element: candidate scans run over query blocks that fit it, so
#: a large bucket against long IVF lists stays within device memory
GATHER_BYTES = 1 << 30


def by_query_block(fn, q: jax.Array, cand: jax.Array):
    """``fn(q, cand)`` over blocks of as many queries as fit GATHER_BYTES,
    so one block's gathered rows live at a time.

    ``fn`` must treat queries independently.  Pad queries are zeros with
    empty (-1) candidates and are cut from the result."""
    n_q, width = cand.shape
    block = max(1, min(n_q, GATHER_BYTES // max(1, 4 * width * q.shape[-1])))
    n_blocks = -(-n_q // block)
    pad = n_blocks * block - n_q
    qb = jnp.pad(q, ((0, pad), (0, 0))).reshape(n_blocks, block, -1)
    cb = jnp.pad(cand, ((0, pad), (0, 0)), constant_values=-1).reshape(
        n_blocks, block, width)
    out = jax.lax.map(lambda a: fn(*a), (qb, cb))
    return jax.tree.map(lambda o: o.reshape((-1,) + o.shape[2:])[:n_q], out)


@partial(jax.jit, static_argnames=("k", "metric"))
def topk_among(
    q_codes: jax.Array,
    store: CodeStore,
    cand_ids: jax.Array,
    k: int,
    metric: str,
    mask: jax.Array | None = None,
):
    """Top-k restricted to per-query candidate lists.

    q_codes [Q, d_eff] prepared queries; cand_ids [Q, L] (-1 = empty
    slot).  Gathers store rows (unpacking int4 only for what was
    gathered), scores, masks empties, returns ([Q, k], [Q, k]).
    An optional [n] predicate ``mask`` over store rows (True = allowed,
    same row space as ``cand_ids``) ANDs into the empty-slot fence.

    Scoring is the batched ``D.scores_among`` (einsum over the gathered
    [Q, L, d] block) rather than a vmapped per-query dot: the batched
    form lowers identically inside ``shard_map``, which is what lets a
    sharded IVF plan reproduce this function's scores bit-exactly
    (DESIGN.md §15).  Queries run in blocks (``by_query_block``), so
    the gathered rows stay within GATHER_BYTES.
    """
    L = cand_ids.shape[1]
    k_eff = min(k, L)

    def block(q, cand):
        ok = cand >= 0
        safe = jnp.where(ok, cand, 0)
        if mask is not None:
            ok = ok & mask.astype(bool)[safe]
        rows = store.take(safe)                          # [q, L, d]
        s = D.scores_among(q, rows, metric, quantized=store.quantized)
        s = jnp.where(ok, s.astype(jnp.float32), NEG)
        s, pos = jax.lax.top_k(s, k_eff)
        return s, jnp.where(
            s > NEG, jnp.take_along_axis(cand, pos, axis=1), -1
        ).astype(jnp.int32)

    s, i = by_query_block(block, q_codes, cand_ids)
    if k_eff < k:
        s = jnp.pad(s, ((0, 0), (0, k - k_eff)), constant_values=NEG)
        i = jnp.pad(i, ((0, 0), (0, k - k_eff)), constant_values=-1)
    if store.base:
        i = jnp.where(i >= 0, i + store.base, -1)
    return s, i


# --------------------------------------------------------------------------
# rerank tail (Searcher §3.4 recall recovery: quantized scan -> exact pass)
# --------------------------------------------------------------------------

def rerank_among(
    queries: jax.Array,
    store: CodeStore,
    cand_ids: jax.Array,
    k: int,
    metric: str,
    mask: jax.Array | None = None,
):
    """Re-score candidate ids against a higher-precision store.

    The Searcher's rerank tail: ``cand_ids`` [Q, depth] come from a
    quantized scan (-1 = empty slot); rows are gathered from the fp32 /
    int8 ``store`` and re-scored by exact distance, returning the best k.
    Runs inside the caller's jit (``topk_among`` is the compiled body), so
    scan → rerank → merge is one executable.  Returns (scores, ids, stats
    delta) — ``bytes_read`` counts the gathered rerank payload.
    """
    q = store.encode_queries(jnp.asarray(queries, jnp.float32))
    s, i = topk_among(q, store, cand_ids, k, metric, mask)
    depth = int(cand_ids.shape[1])
    stats = {
        "reranked": depth,
        "rerank_bits": int(store.bits),
        "rerank_bytes": int(cand_ids.shape[0]) * depth * store.row_bytes,
    }
    return s, i, stats


# --------------------------------------------------------------------------
# cascade stages (DESIGN.md §14): budgeted refinement + per-region lookup
# --------------------------------------------------------------------------

def refine_among(
    queries: jax.Array,
    store: CodeStore,
    cand_ids: jax.Array,
    out_k: int,
    metric: str,
    mask: jax.Array | None = None,
):
    """One cascade refinement stage: re-score the surviving candidates at
    this store's precision and keep the best ``out_k``.

    Same compiled body as the rerank tail (``topk_among``) — a cascade's
    final fp32 stage is therefore bit-identical to the ``+r32`` tail at
    the same depth — but reports the stage-stat names the cascade
    aggregates: its own fetch budget (``candidates`` = the incoming
    candidate-list width), gathered payload bytes, and code width.
    """
    q = store.encode_queries(jnp.asarray(queries, jnp.float32))
    s, i = topk_among(q, store, cand_ids, out_k, metric, mask)
    depth = int(cand_ids.shape[1])
    stats = {
        "candidates": depth,
        "bytes_read": int(cand_ids.shape[0]) * depth * store.row_bytes,
        "bits": int(store.bits),
    }
    return s, i, stats


@partial(jax.jit, static_argnames=("k", "metric"))
def topk_among_regional(
    queries: jax.Array,
    store: CodeStore,
    region_scale: jax.Array,
    region_zero: jax.Array,
    assign: jax.Array,
    cand_ids: jax.Array,
    k: int,
    metric: str,
    mask: jax.Array | None = None,
):
    """Candidate top-k with per-region Eq. 1 constant lookup.

    Codes quantized under different regions' constants are not comparable
    in integer space, so the regional path scores fp32 ``queries``
    against *dequantized* rows: each gathered candidate's region id
    (``assign [N]``) selects its own ``region_scale`` / ``region_zero``
    rows ([R, d]) and the code is mapped back to fp32 before the metric.
    Everything else (empty-slot masking, -1 pads, base rebasing, the
    optional row-space ``mask``, query blocks) matches ``topk_among``.
    """
    L = cand_ids.shape[1]
    k_eff = min(k, L)

    def block(q, cand):
        ok = cand >= 0
        safe = jnp.where(ok, cand, 0)
        if mask is not None:
            ok = ok & mask.astype(bool)[safe]
        codes = store.take(safe).astype(jnp.float32)     # [q, L, d]
        reg = assign[safe]                               # [q, L]
        x = codes * region_scale[reg] + region_zero[reg]
        s = D.scores_among(q, x, metric, quantized=False)
        s = jnp.where(ok, s.astype(jnp.float32), NEG)
        s, pos = jax.lax.top_k(s, k_eff)
        return s, jnp.where(
            s > NEG, jnp.take_along_axis(cand, pos, axis=1), -1
        ).astype(jnp.int32)

    s, i = by_query_block(block, queries, cand_ids)
    if k_eff < k:
        s = jnp.pad(s, ((0, 0), (0, k - k_eff)), constant_values=NEG)
        i = jnp.pad(i, ((0, 0), (0, k - k_eff)), constant_values=-1)
    if store.base:
        i = jnp.where(i >= 0, i + store.base, -1)
    return s, i


def regional_stats(store, cand_ids) -> dict[str, Any]:
    """Stats delta of one ``topk_among_regional`` call: the gathered code
    payload plus the per-row constant lookup (scale + zero, fp32 [d])."""
    depth = int(cand_ids.shape[1])
    const_bytes = 2 * 4 * int(store.d)
    return {
        "candidates": depth,
        "bytes_read": int(cand_ids.shape[0]) * depth * (store.row_bytes + const_bytes),
        "bits": int(store.bits),
        "packed": bool(store.packed),
        "regional": True,
    }


# --------------------------------------------------------------------------
# Distributed merge (corpus row-sharded over one or more mesh axes)
# --------------------------------------------------------------------------

def distributed_topk(
    local_scores: jax.Array,
    local_ids: jax.Array,
    k: int,
    axis_name: str | tuple[str, ...],
    shard_offset: jax.Array,
    *,
    tie_break: str = "order",
):
    """Merge per-shard top-k into a global top-k, inside ``shard_map``.

    Each shard holds [Q, k] candidates with *local* ids; ``shard_offset``
    (scalar, per shard) rebases them to global row ids.  One all_gather of
    k entries per query per shard — O(shards * Q * k) bytes, independent of
    corpus size N.  (A butterfly collective_permute halves wire bytes at
    log-depth; see EXPERIMENTS.md §Perf for why all_gather wins at k=100.)

    Shard-local stores built with ``CodeStore(base=offset)`` already
    return rebased ids from the engine — pass ``shard_offset=0`` there.

    ``tie_break`` decides which of several equal-score candidates wins —
    the thing that makes sharded results *bit-identical* to unsharded
    ones, not merely score-identical (quantized scores tie constantly):

      * ``"order"`` — ``lax.top_k``'s stable gather order: lower shard
        first, then local rank.  Correct when shard order matches global
        id order (contiguous row blocks: flat/pq/stream scans).
      * ``"id"`` — lexicographic (score desc, id asc) via a two-key
        sort.  Correct when shards interleave the id space (IVF list
        placement merges on candidate *positions*, reproducing
        ``topk_among``'s canonical per-query ``top_k`` order).
        Masked entries (NEG score) sort last regardless of id.
    """
    if tie_break not in ("order", "id"):
        raise ValueError(f"tie_break must be 'order' or 'id', got {tie_break!r}")
    gids = jnp.where(local_ids >= 0, local_ids + shard_offset, -1)
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    s, i = local_scores, gids
    for name in names:
        s = jax.lax.all_gather(s, name, axis=0)   # [S, Q, k]
        i = jax.lax.all_gather(i, name, axis=0)
        S, Q, kk = s.shape
        s = jnp.moveaxis(s, 0, 1).reshape(Q, S * kk)
        i = jnp.moveaxis(i, 0, 1).reshape(Q, S * kk)
        if tie_break == "id":
            # ascending lexicographic sort on (-score, id): score desc,
            # id asc among ties; NEG-masked rows (-NEG = fp32 max) last
            ns, i = jax.lax.sort((-s, i), num_keys=2)
            s, i = (-ns)[:, :k], i[:, :k]
        else:
            s, pos = jax.lax.top_k(s, k)
            i = jnp.take_along_axis(i, pos, axis=-1)
    return s, i


# --------------------------------------------------------------------------
# PQ: ADC — fused Pallas kernel or streaming LUT gather-sum scan
# --------------------------------------------------------------------------

def build_pq_lut(queries: jax.Array, store: PQStore, metric: str) -> jax.Array:
    """Per-query ADC lookup table [Q, M, K] f32 of query-to-codeword
    scores (K = ``store.n_codewords``).

    Both metrics are an elementwise product and a reduction over the
    subspace width, never a dot: a dot's accumulation order depends on
    the batch shape, so a query padded into a Searcher bucket would get
    a LUT an ulp away from its eager one.
    """
    q = jnp.asarray(queries, jnp.float32)
    Q, d = q.shape
    ds = d // store.m
    qs = q.reshape(Q, store.m, ds)
    if metric == "ip":
        return jnp.sum(qs[:, :, None, :] * store.codebooks[None], -1)
    diff = qs[:, :, None, :] - store.codebooks[None]    # l2 (negated)
    return -jnp.sum(diff * diff, -1)


def quantize_pq_lut(lut: jax.Array) -> jax.Array:
    """The paper's after-the-codebook composition (``lpq_tables``): Eq. 1
    abs-max quantization of the LUT entries to int8, one scale **per
    query** (over that query's [M, K] table).  Per-query scaling keeps
    the M subspace entries that sum into one score on a common scale —
    the only comparability ADC needs, since top-k ranks within a query —
    while making each query's quantized LUT independent of batch
    composition: a Searcher pad row (whose negated-L2 table against the
    codebooks is large) cannot perturb a real query's scale, so padded
    planned execution is bit-identical to the eager path."""
    amax = jnp.maximum(jnp.max(jnp.abs(lut), axis=(1, 2), keepdims=True),
                       1e-12)
    return jnp.clip(jnp.round(lut / amax * 127.0), -128, 127).astype(jnp.int8)


def _pq_fused(store: PQStore, metric: str, chunk: int,
              use_pallas: bool, interpret,
              cfg=None) -> tuple[bool, int, int]:
    """Fused-vs-reference dispatch for the ADC scan: (fused, fused tile,
    scan chunk).

    The fused Pallas kernel needs integer LUTs (``lpq_tables``: int8
    entries it holds VMEM-resident and accumulates in int32); fp32-LUT
    stores take the streaming gather-sum scan.  Backend gating matches
    the CodeStore path: TPU hot path, ``interpret=True`` for CI wiring,
    single-tile corpora skip the kernel.  A TuneTable entry (``cfg``)
    overrides the tile/chunk shapes and can force the measured
    crossover's scan choice; the gating conditions still apply.
    """
    tile = min(FUSED_TILE, max(8, chunk))
    chunk_eff = chunk
    if cfg is not None:
        if cfg.impl == "fused":
            tile = cfg.bn or tile
        else:
            chunk_eff = max(8, cfg.chunk or chunk)
    fused = (
        metric in ("ip", "l2")
        and store.lpq_tables
        and use_pallas
        and store.n > tile
        and (cfg is None or cfg.impl == "fused")
        and (bool(interpret) or jax.default_backend() == "tpu")
    )
    return fused, tile, chunk_eff


#: optional runtime LUT-block cache (repro.runtime.cache.LUTCache) — the
#: hook only fires on *concrete* query batches (eager / one-shot search);
#: inside a jitted Searcher bucket queries are tracers and the LUT is
#: already fused into the compiled executable, so there is nothing to cache
_LUT_CACHE = None


def set_lut_cache(cache) -> None:
    """Install (or, with None, remove) the process-wide PQ LUT cache."""
    global _LUT_CACHE
    _LUT_CACHE = cache


def get_lut_cache():
    return _LUT_CACHE


@partial(jax.jit, static_argnames=("metric",))
def _prepare_pq_lut(queries: jax.Array, store: PQStore, metric: str):
    """The per-batch ADC table build: ``build_pq_lut`` einsum plus — for
    ``lpq_tables`` stores — the paper's Eq. 1 int8 quantization.  This is
    exactly the work the runtime LUT cache elides for repeated batches."""
    lut = build_pq_lut(queries, store, metric)
    return quantize_pq_lut(lut) if store.lpq_tables else lut


def _topk_pq(
    queries: jax.Array,
    store: PQStore,
    k: int,
    metric: str,
    chunk: int,
    use_pallas: bool = True,
    interpret: bool | None = None,
    cfg=None,
    mask: jax.Array | None = None,
):
    """Asymmetric distance computation over the code matrix.

    Per-query LUT of query-to-codeword scores (served from the runtime
    LUT cache when one is installed and the batch is concrete), then
    either the **fused Pallas ADC kernel** (``kernels/adc.py``: int8 LUT
    VMEM-resident, 4-bit codes unpacked from their packed nibbles
    in-kernel, int32 accumulation, running top-k — the [Q, N] ADC matrix
    never exists) or the **reference streaming scan** (``_stream_topk``
    over code chunks with a gather-sum tile, unpacking 4-bit codes chunk
    by chunk).  Dispatch is ``_pq_fused``; both paths are bit-identical.
    """
    cache = _LUT_CACHE
    if cache is not None and not isinstance(queries, jax.core.Tracer):
        key = cache.key_for(queries, store.codebooks, metric,
                            store.lpq_tables)
        lut = cache.get_or_build(
            key, lambda: jax.block_until_ready(
                _prepare_pq_lut(queries, store, metric))
        )
        return _topk_pq_from_lut(lut, store, k, metric, chunk,
                                 use_pallas=use_pallas, interpret=interpret,
                                 cfg=cfg, mask=mask)
    return _topk_pq_built(queries, store, k, metric, chunk,
                          use_pallas=use_pallas, interpret=interpret,
                          cfg=cfg, mask=mask)


@partial(jax.jit, static_argnames=("k", "metric", "chunk", "use_pallas",
                                   "interpret", "cfg"))
def _topk_pq_built(queries, store, k, metric, chunk, use_pallas=True,
                   interpret=None, cfg=None, mask=None):
    """Table build and scan as one program: the eager call and a planned
    bucket (which inlines it) then compile the same fusion, so fp32
    tables score bit-identically on both."""
    lut = _prepare_pq_lut(queries, store, metric)
    return _topk_pq_from_lut(lut, store, k, metric, chunk,
                             use_pallas=use_pallas, interpret=interpret,
                             cfg=cfg, mask=mask)


@partial(jax.jit, static_argnames=("k", "metric", "chunk", "use_pallas",
                                   "interpret", "cfg"))
def _topk_pq_from_lut(
    lut: jax.Array,
    store: PQStore,
    k: int,
    metric: str,
    chunk: int,
    use_pallas: bool = True,
    interpret: bool | None = None,
    cfg=None,
    mask: jax.Array | None = None,
):
    n = store.n
    k_eff = min(k, n)

    fused, tile, chunk = _pq_fused(store, metric, chunk, use_pallas,
                                   interpret, cfg)
    if fused:
        return K.fused_adc_topk(lut, store.codes, k_eff,
                                packed=store.packed,
                                bq=(cfg.bq if cfg is not None else None),
                                bn=tile, interpret=interpret, mask=mask)

    ilut = lut.astype(jnp.int32) if store.lpq_tables else lut

    def tile_scores(lt, tile_codes):                    # [c, Mb] -> [Q, c]
        rows = (PK.unpack_uint4(tile_codes)[:, : store.m]
                if store.packed else tile_codes)
        idx = rows.T[None].astype(jnp.int32)            # [1, M, c]
        return jnp.sum(
            jnp.take_along_axis(lt, idx, axis=2), axis=1
        ).astype(jnp.float32)

    return _stream_topk(ilut, store.codes, k_eff, chunk, n, tile_scores,
                        mask=mask)
