"""The Searcher: compiled, sharded, rerank-capable search sessions
(DESIGN.md §9) — the query-plan API behind every index kind.

The paper's throughput claim is a *serving-time* claim, but an eager
``index.search()`` re-resolves dispatch and re-pads shapes on every
request.  ``index.searcher(k, params, ...)`` separates plan time from
query time, the way PR 1 separated build time:

  * **plan once** — kind/metric/bits/packed dispatch is resolved and
    ``SearchParams`` frozen into a pure runner (``index.plan(k, sp)``);
    invalid plans (k <= 0, k > n, chunk <= 0, nprobe <= 0) fail here with
    ``ValueError``s, not kernel-shape errors mid-trace.
  * **compile per bucket** — the runner is jitted once per padded
    batch-size bucket (default 1/8/32/256), so arbitrary request sizes
    hit a small, fixed set of compiled shapes; ``trace_counts`` exposes
    the compilation ledger the tests assert on.
  * **shard natively** — given a mesh, the flat scan row-shards its
    ``CodeStore`` over every mesh axis (``dist.sharding.corpus_shards``)
    and fuses shard-local top-k with one k-sized cross-shard merge
    *inside* the compiled function (O(shards·Q·k) wire, DESIGN.md §4).
  * **rerank** — an optional ``Rerank(depth, store)`` tail re-scores the
    quantized top-``depth`` candidates against an fp32/int8 store by
    gathered-row exact distance in the same jit (the paper's §3.4 recall
    recovery; ``"flat,lpq4+r32"`` builds the store at index time).
  * **account** — every result's stats carry the engine block plus
    ``{bucket, padded_q, shards, reranked}``.

``Index.search`` is a thin one-shot searcher (``one_shot``), so every
pre-plan call site keeps working unchanged.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import operator
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro import engine
from repro.knn import base as B
from repro.runtime.telemetry import span
from repro.tune import table as tunetable

__all__ = ["Searcher", "Rerank", "one_shot", "sharded_scan_plan",
           "multi_source_plan", "DEFAULT_BATCH_SIZES", "DEFAULT_RERANK_DEPTH"]

#: padded batch-size buckets a plan compiles for (smallest covering
#: bucket is picked per request; oversize requests run in max-bucket
#: slices)
DEFAULT_BATCH_SIZES = (1, 8, 32, 256)

NEG = float(jnp.finfo(jnp.float32).min)

#: stats a plan returns as device values, left unread by the call: the
#: fused kernel's merge counter (``engine.topk``).  Slices sum them on
#: the device, and the ``searcher.call`` span's fields carry them too,
#: for a reader to take to the host once the calls are done.
DEVICE_STATS = ("merge_steps", "merge_tiles")

PlanFn = Callable[[jax.Array], B.SearchResult]


def DEFAULT_RERANK_DEPTH(k: int, n: int) -> int:
    """Candidate depth when a rerank store exists but no depth is given:
    4k (clamped to [k, n]) — deep enough that the exact pass can repair
    low-bit scan inversions, shallow enough that the gather stays O(Q·k)."""
    return max(k, min(n, 4 * k))


@dataclasses.dataclass(frozen=True)
class Rerank:
    """Rerank stage config: re-score the quantized top-``depth`` against
    ``store`` (an fp32 or int8 ``engine.CodeStore``) by exact distance.

    ``store`` is None for indexes that own their rerank stage
    (``handles_rerank = True``, e.g. the stream kind, whose multi-segment
    merge re-scores against the manifest's raw payloads inside its own
    plan) — the Searcher then only resolves the depth and passes it down.
    """

    depth: int
    store: Optional[engine.CodeStore]


def _query_dim(index) -> Optional[int]:
    """Expected query width, for plan-time shape validation."""
    store = getattr(index, "store", None)
    if isinstance(store, engine.CodeStore):
        # the graph kind's MIP->L2 augmentation adds one internal column
        return store.d - 1 if getattr(index, "aug", False) else store.d
    if isinstance(store, engine.PQStore):
        return int(store.codebooks.shape[0] * store.codebooks.shape[2])
    d = getattr(index, "d", None)           # store-less kinds (stream)
    return int(d) if d is not None else None


def _resolve_rerank(index, k: int, n: int, rerank) -> Optional[Rerank]:
    """Normalize the ``rerank=`` argument against the index's own store.

    None  -> the index's ``+rN`` store at default depth (or no rerank)
    False -> explicitly off, even for a ``+rN`` index
    int   -> depth override over the index's ``+rN`` store
    Rerank -> fully explicit (store must cover the same id space)

    Indexes with ``handles_rerank = True`` resolve to a store-less
    ``Rerank(depth, None)``: the depth is passed to ``index.plan`` and the
    index's own runner re-scores (the Searcher runs no tail of its own).
    """
    if rerank is False:
        return None
    if getattr(index, "handles_rerank", False):
        if rerank is None:
            if getattr(index, "rerank_bits", None) is None:
                return None
            return Rerank(DEFAULT_RERANK_DEPTH(k, n), None)
        if rerank is True:
            return Rerank(DEFAULT_RERANK_DEPTH(k, n), None)
        if isinstance(rerank, bool) or not isinstance(rerank, int):
            raise TypeError(
                f"{index.kind!r} owns its rerank stage; pass None / False / "
                f"an int depth, not {type(rerank)!r}"
            )
        if rerank <= 0:
            raise ValueError(f"rerank depth must be positive, got {rerank}")
        return Rerank(max(k, min(int(rerank), max(n, k))), None)
    own = getattr(index, "rerank_store", None)
    if rerank is None or rerank is True:
        if own is None:
            if rerank is True:
                raise ValueError(
                    "rerank=True but the index holds no rerank store — "
                    "build with a '+r32'/'+r8' factory suffix or pass "
                    "Rerank(depth, store)"
                )
            return None
        return Rerank(DEFAULT_RERANK_DEPTH(k, n), own)
    if isinstance(rerank, int):
        if own is None:
            raise ValueError(
                f"rerank depth {rerank} given but the index holds no rerank "
                "store — build with a '+r32'/'+r8' factory suffix or pass "
                "Rerank(depth, store)"
            )
        rerank = Rerank(int(rerank), own)
    if not isinstance(rerank, Rerank):
        raise TypeError(
            f"rerank must be None/False/int depth/Rerank, got {type(rerank)!r}"
        )
    if not isinstance(rerank.store, engine.CodeStore):
        raise TypeError("Rerank.store must be an engine.CodeStore")
    if rerank.store.n != n:
        raise ValueError(
            f"rerank store covers {rerank.store.n} rows but the index holds "
            f"{n} — the stores must share one id space"
        )
    if rerank.depth <= 0:
        raise ValueError(f"rerank depth must be positive, got {rerank.depth}")
    # clamp to the useful band: >= k (the tail must be able to fill the
    # result) and <= n (deeper gathers than the corpus are pure waste)
    return dataclasses.replace(rerank, depth=max(k, min(rerank.depth, n)))


# --------------------------------------------------------------------------
# sharded flat scan: the row-sharded plan body (used by FlatIndex.plan)
# --------------------------------------------------------------------------

def sharded_scan_plan(
    store: engine.CodeStore, metric: str, k: int, mesh, chunk: int = 16384,
    placement=None, mask=None,
) -> PlanFn:
    """Row-shard a ``CodeStore`` scan over a mesh (DESIGN.md §4/§9/§15).

    Queries replicate; corpus rows shard over every mesh axis in the
    contiguous blocks a ``rows`` :class:`~repro.dist.placement.Placement`
    describes; each shard streams its slice in ``chunk``-row tiles
    (unpacking int4 tile by tile) with a running local top-k — the same
    O(Q·(k+chunk)) working set as the unsharded scan, never a [Q, N_loc]
    score matrix.  Pad rows are id-masked at the source with
    globally-unique sentinel gids (``dist.sharding.sentinel_gids`` — a
    tile-pad row's arithmetic gid lands in the NEXT shard's id range, so
    the sentinel is what makes a missed mask an impossible alias instead
    of a silent wrong neighbor), and ``distributed_topk`` merges the
    per-shard candidates with one k-sized all_gather; block order ==
    gid order, so the merge's stable shard-major tie-break reproduces
    the unsharded scan's canonical (score desc, gid asc) order exactly.
    The whole thing is a pure function of the query batch, so the
    Searcher compiles scan -> local top-k -> cross-shard merge
    (-> rerank) as one unit.

    ``mask`` (optional [n] bool, DESIGN.md §16) is a filter bitmap over
    the store's row ids: it shards alongside the data rows and ANDs into
    the *validity* handed to ``sentinel_gids`` — a filtered row gets a
    sentinel gid >= n and dies at the existing ``gid < n`` fence, so the
    filter rides the pad/tombstone masking path with zero extra scans
    and an unchanged merge.
    """
    from repro.core import distances as D
    from repro.core import pack as PK
    from repro.dist.placement import Placement
    from repro.dist.sharding import (
        P, corpus_shards, sentinel_gids, shard_map, shard_rows,
    )
    from repro.engine import distributed_topk

    if store.base:
        raise ValueError("sharded plans require a base-0 store (the plan "
                         "owns the global id space)")
    axes, n_shards = corpus_shards(mesh)
    n = store.n
    if placement is None:
        placement = Placement.rows(n, n_shards)
    if placement.n_shards != n_shards:
        raise ValueError(
            f"placement covers {placement.n_shards} shards but the mesh has "
            f"{n_shards}"
        )
    if placement.kind != "rows":
        raise ValueError(
            f"sharded_scan_plan shards contiguous row blocks; got a "
            f"{placement.kind!r} placement"
        )
    rows_per = -(-n // n_shards)
    pad = n_shards * rows_per - n
    k_merge = min(k, n)
    k_local = min(k_merge, rows_per)
    tile_rows = min(chunk, rows_per)
    n_tiles = -(-rows_per // tile_rows)
    padded_rows = n_tiles * tile_rows          # per-shard sentinel band width
    data = shard_rows(
        mesh, jnp.pad(store.data, ((0, pad), (0, 0))) if pad else store.data)
    shard_idx = shard_rows(mesh, jnp.arange(n_shards, dtype=jnp.int32))
    fmask = None
    if mask is not None:
        fm = jnp.asarray(mask).astype(jnp.int8)
        fmask = shard_rows(mesh, jnp.pad(fm, (0, pad)) if pad else fm)

    def local(q, shard, mshard, idx):
        gid0 = idx[0] * rows_per
        Q = q.shape[0]
        tile_pad = padded_rows - rows_per
        if tile_pad:
            shard = jnp.pad(shard, ((0, tile_pad), (0, 0)))
        tiles = shard.reshape(n_tiles, tile_rows, shard.shape[-1])
        if mshard is not None:
            if tile_pad:
                mshard = jnp.pad(mshard, (0, tile_pad))
            mtiles = mshard.reshape(n_tiles, tile_rows)
        else:
            mtiles = jnp.zeros((n_tiles, 0), jnp.int8)

        def step(carry, inp):
            tile, mrow, t = inp
            rows = PK.unpack_int4(tile) if store.packed else tile
            s = D.scores(q, rows, metric, quantized=store.quantized)
            s = s.astype(jnp.float32)
            lrow = t * tile_rows + jnp.arange(tile_rows, dtype=jnp.int32)
            # pad rows — the shard's own tile pad (lrow >= rows_per,
            # whose arithmetic gid aliases the NEXT shard) and the
            # global tail pad (gid >= n) — get unique >= n sentinels:
            # validity now travels in the gid itself.  A filtered-out
            # row is treated exactly like a pad row: its sentinel gid
            # dies at the same fence (DESIGN.md §16).
            valid = (lrow < rows_per) & (gid0 + lrow < n)
            if mshard is not None:
                valid = valid & (mrow != 0)
            gid = sentinel_gids(
                gid0 + lrow, valid,
                shard=idx[0], local_rows=lrow, n_total=n,
                padded_rows=padded_rows,
            )
            ok = gid < n
            s = jnp.where(ok[None, :], s, NEG)
            ids = jnp.where(ok[None, :], jnp.broadcast_to(gid[None], s.shape), -1)
            return engine.merge_topk(*carry, s, ids, k_local), None

        init = (jnp.full((Q, k_local), NEG, jnp.float32),
                jnp.full((Q, k_local), -1, jnp.int32))
        (ls, li), _ = jax.lax.scan(
            step, init, (tiles, mtiles, jnp.arange(n_tiles, dtype=jnp.int32))
        )
        return distributed_topk(ls, li, k_merge, axes, 0)

    merge_wire = n_shards * k_merge * 8        # per query: fp32 score + i32 id

    def run(queries: jax.Array) -> B.SearchResult:
        q = store.encode_queries(queries)
        if fmask is None:
            s, i = inner(q, data, shard_idx)
        else:
            s, i = inner(q, data, fmask, shard_idx)
        # belt under the sentinel braces: nothing >= n may leave the plan
        i = jnp.where(i >= n, -1, i)
        if k_merge < k:                  # uniform [Q, k] contract: -1 pads
            s = jnp.pad(s, ((0, 0), (0, k - k_merge)), constant_values=NEG)
            i = jnp.pad(i, ((0, 0), (0, k - k_merge)), constant_values=-1)
        stats = engine.search_stats(store, candidates=n,
                                    chunks=n_shards * n_tiles, rows_read=n)
        return B.SearchResult(s, i, {
            "kind": "flat", **stats, "placement": placement.kind,
            "merge_wire_bytes": int(queries.shape[0]) * merge_wire,
        })

    if fmask is None:
        # keep the unfiltered trace byte-identical to the pre-filter plan
        def local_plain(q, shard, idx):
            return local(q, shard, None, idx)

        inner = shard_map(
            local_plain,
            mesh=mesh,
            in_specs=(P(), P(axes, None), P(axes)),
            out_specs=(P(), P()),
            check_vma=False,
        )
    else:
        inner = shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(axes, None), P(axes), P(axes)),
            out_specs=(P(), P()),
            check_vma=False,
        )

    return run


# --------------------------------------------------------------------------
# multi-source plans: segments + memtable behind one runner (stream kind)
# --------------------------------------------------------------------------

def multi_source_plan(
    sources: Sequence[tuple[PlanFn, int, int]],
    *,
    k: int,
    metric: str,
    id_map: jax.Array,
    live: jax.Array,
    merge_store: Optional[engine.CodeStore],
    rescore: bool,
    stats_extra: Optional[dict] = None,
    mesh=None,
    placement=None,
) -> PlanFn:
    """Fuse per-source plans into one runner over a shared internal id
    space (DESIGN.md §10 — the stream kind's search path).

    ``sources`` is a list of ``(runner, base, width)``: each runner is a
    kind's ``plan`` output over one sealed segment (or the memtable's
    flat scan) returning *local* ids; ``base`` rebases them into the
    manifest's internal id space, ``width`` is the candidate count the
    runner returns.  The fused runner:

      1. runs every source, rebases ids, and **tombstone-masks** deleted
         rows through the manifest's ``live`` bitmap (masked at candidate
         level: a dead row can occupy a candidate slot but never a
         result slot — sources over-fetch by their masked count so k
         surviving rows always reach the merge on exact sources).  A
         search-time filter (DESIGN.md §16) composes here too: the
         caller hands ``live ∧ filter`` as one internal-space bitmap, so
         a filtered row dies exactly like a tombstoned one;
      2. merges: with ``rescore``, all candidates are re-scored in one
         common space via ``engine.topk_among`` against ``merge_store``
         (per-segment quantized scores are NOT comparable across
         differently-calibrated segments — the re-score is what makes
         the merge sound, and doubles as the ``+rN`` rerank tail); a
         single source with no re-score requested passes through its own
         score order (the exact-parity path a freshly-compacted stream
         index shares with its from-scratch equivalent);
      3. maps internal ids to external ids via ``engine.remap_ids``.

    Everything is a pure function of the query batch, so the Searcher
    compiles sources -> mask -> merge -> remap as one executable per
    bucket.  Like every plan, the runner snapshots the state it closed
    over — mutations after plan time need a new plan (LSM readers pin a
    manifest version; DESIGN.md §10).

    Under a ``mesh``, the per-source runners handed in are themselves
    sharded plans (each segment's inner kind shards its own rows/lists
    over the full mesh — see DESIGN.md §15) and the merge/rescore above
    them stays replicated inside the same jit; ``placement`` (a
    ``segments`` Placement) is the accounting view, stamped into the
    stats so serve telemetry can report per-shard residency.
    """
    if rescore and merge_store is None:
        raise ValueError("rescoring merge needs a merge_store")
    extra = dict(stats_extra or {})
    if placement is not None:
        extra["placement"] = placement.kind
        extra["placement_balance"] = placement.summary()["balance"]
    total_width = sum(w for _, _, w in sources)

    def run(queries: jax.Array) -> B.SearchResult:
        q = jnp.asarray(queries, jnp.float32)
        Q = q.shape[0]
        if not sources:                       # fully empty index
            return B.SearchResult(
                jnp.full((Q, k), NEG, jnp.float32),
                jnp.full((Q, k), -1, jnp.int32),
                {"kind": "stream", "candidates": 0, "reranked": 0, **extra},
            )

        parts_s, parts_i = [], []
        agg = {"candidates": 0, "bytes_read": 0, "chunks": 0,
               "merge_wire_bytes": 0}
        for runner, base, _w in sources:
            res = runner(q)
            gid = jnp.where(res.ids >= 0, res.ids + base, -1)
            parts_s.append(res.scores)
            parts_i.append(gid)
            for key in agg:
                agg[key] += int(res.stats.get(key, 0))
        s = jnp.concatenate(parts_s, axis=1)
        gids = jnp.concatenate(parts_i, axis=1)

        # tombstone mask: dead rows lose their candidate slot here, at
        # merge level, inside the compiled function
        ok = (gids >= 0) & live[jnp.clip(gids, 0, live.shape[0] - 1)]
        s = jnp.where(ok, s, NEG)
        gids = jnp.where(ok, gids, -1)

        stats = {"kind": "stream", **agg, **extra}
        if rescore:
            qm = merge_store.encode_queries(q)
            s, gids = engine.topk_among(qm, merge_store, gids, k, metric)
            stats.update(
                reranked=total_width,
                rerank_bits=int(merge_store.bits),
                rerank_bytes=int(Q) * total_width * merge_store.row_bytes,
            )
            stats["bytes_read"] += stats["rerank_bytes"]
        else:
            # single-source pass-through: keep the source's own score
            # order (lax.top_k is stable, so dropping dead slots cannot
            # reorder live ties)
            k_eff = min(k, s.shape[1])
            s, pos = jax.lax.top_k(s, k_eff)
            gids = jnp.take_along_axis(gids, pos, axis=-1)
            if k_eff < k:
                s = jnp.pad(s, ((0, 0), (0, k - k_eff)), constant_values=NEG)
                gids = jnp.pad(gids, ((0, 0), (0, k - k_eff)),
                               constant_values=-1)
            stats["reranked"] = 0
        ext = engine.remap_ids(gids, id_map)
        return B.SearchResult(s, ext, stats)

    return run


# --------------------------------------------------------------------------
# the Searcher handle
# --------------------------------------------------------------------------

class Searcher:
    """A planned search session: ``index.searcher(k, params)(queries)``.

    Construction *is* plan time: arguments are validated, the rerank
    stage resolved, the per-kind runner built (``index.plan``) and the
    jit wrapper created.  Calls execute: the request is sliced into
    batch-size buckets, padded, run through the compiled executable for
    that bucket, and stitched back with uniform accounting.

    ``batch_sizes=None`` is the one-shot mode ``Index.search`` uses: no
    padding, no extra jit wrapper — exactly the historical eager call.
    """

    def __init__(
        self,
        index,
        k: int,
        params: Optional[B.SearchParams] = None,
        *,
        batch_sizes: Optional[Sequence[int]] = DEFAULT_BATCH_SIZES,
        shards=None,
        rerank: Union[None, bool, int, Rerank] = None,
        strict: bool = True,
    ):
        if not isinstance(k, int) or isinstance(k, bool) or k <= 0:
            raise ValueError(f"k must be a positive int, got {k!r}")
        n = int(index.n)
        if strict and k > n:
            raise ValueError(
                f"k={k} exceeds the corpus size n={n}; a plan cannot return "
                "more neighbors than the index holds"
            )
        sp = (params or B.SearchParams()).validate()
        if batch_sizes is not None:
            batch_sizes = tuple(sorted(set(int(b) for b in batch_sizes)))
            if not batch_sizes or batch_sizes[0] <= 0:
                raise ValueError(
                    f"batch_sizes must be positive ints, got {batch_sizes!r}"
                )

        self.index = index
        self.k = k
        self.params = sp
        self.batch_sizes = batch_sizes
        self.mesh = shards
        self.rerank = _resolve_rerank(index, k, n, rerank)
        if self.rerank is not None and sp.filter is not None:
            # filter over-fetch (DESIGN.md §16): widen the candidate
            # depth by the filter's estimated selectivity so ~k allowed
            # rows survive to the settling stage; survivors < k still
            # pad with (-1, NEG) — the exact pad-sentinel contract
            from repro.filter import overfetch

            self.rerank = dataclasses.replace(
                self.rerank,
                depth=max(self.rerank.depth,
                          overfetch(k, sp.filter.selectivity, n)),
            )
        self._qdim = _query_dim(index)
        self._counts: collections.Counter = collections.Counter()

        n_shards = int(shards.devices.size) if shards is not None else 1
        # plan-time table resolution: the active TuneTable (if it matches
        # this backend's stamp) is snapshotted NOW and pinned around every
        # runner execution, so bucketed executables compile with the
        # tuned shapes this plan saw — a table installed later cannot
        # silently retile a compiled plan (DESIGN.md §13)
        self.tune_table = tunetable.snapshot_for_plan()
        # plan-time placement resolution mirrors the table: the unit ->
        # shard assignment is computed NOW from the index's sizes (list
        # sizes / segment rows / row count) and handed to the plan, so a
        # mutation after plan time cannot silently re-place a compiled
        # plan's shards (DESIGN.md §15)
        if shards is not None:
            from repro.dist import placement as dplacement

            self.placement = dplacement.for_index(index, n_shards)
        else:
            self.placement = None
        self._extras = {"shards": n_shards,
                        "tuned": self.tune_table is not None}
        if self.placement is not None:
            self._extras["placement"] = self.placement.kind
            self._extras["placement_balance"] = (
                self.placement.summary()["balance"])

        rr = self.rerank
        if rr is not None and rr.store is None:
            # index-owned rerank (stream): the plan runs scan -> merge ->
            # exact re-score itself; hand it k AND the candidate depth
            inner = index.plan(k, sp, mesh=shards, rerank_depth=rr.depth,
                               placement=self.placement)
            rr = None
        else:
            k_inner = rr.depth if rr is not None else k
            inner = index.plan(k_inner, sp, mesh=shards,
                               placement=self.placement)
        metric = index.metric

        def run(queries: jax.Array) -> B.SearchResult:
            self._counts[int(queries.shape[0])] += 1   # fires once per trace
            with tunetable.pinned(self.tune_table):    # plan-time snapshot
                res = inner(queries)
            stats = dict(res.stats)
            s, i = res.scores, res.ids
            if rr is not None:
                s, i, rstats = engine.rerank_among(
                    queries, rr.store, i, k, metric
                )
                stats.update(rstats)
                stats["bytes_read"] = (
                    stats.get("bytes_read", 0) + rstats["rerank_bytes"]
                )
            else:
                stats.setdefault("reranked", 0)
            return B.SearchResult(s, i, stats)

        self._run = run
        self._executables: dict = {}     # (shape, dtype) -> (jit fn, arrays)

    # -- accounting --------------------------------------------------------
    @property
    def trace_counts(self) -> dict[int, int]:
        """bucket size -> number of times the runner was (re)traced."""
        return dict(self._counts)

    @property
    def n_shards(self) -> int:
        return self._extras["shards"]

    def buckets_for(self, q_len: int) -> tuple[int, ...]:
        """The compile buckets a ``q_len``-query request will execute in
        (one per slice) — callers warm these before timing (serve.py)."""
        if self.batch_sizes is None:
            return (q_len,)
        out = []
        max_b = self.batch_sizes[-1]
        while q_len > 0:
            rows = min(q_len, max_b)
            out.append(next(b for b in self.batch_sizes if b >= rows))
            q_len -= rows
        return tuple(out)

    def lower(self, q_len: int):
        """Lower the executable of the bucket a ``q_len``-query request
        runs in (``.compile().as_text()`` shows which kernels it holds)."""
        q = jax.ShapeDtypeStruct((self.buckets_for(q_len)[0], self._qdim),
                                 jnp.float32)
        fn, arrays = self._executable(q)
        return fn.lower(arrays, q)

    def _executable(self, q) -> tuple[Callable, list]:
        """The bucket executable for ``q``'s shape, and the arrays it runs on.

        The plan closes over the index (codes, lists, graphs, placed
        shards).  jit would bake closed-over arrays into the program as
        constants: gigabytes of HLO per bucket at a deployment's size,
        minutes of compile and host memory beyond a chip host's.  So the
        runner is traced once to a jaxpr and every array it closed over
        is passed to the compiled program as an argument instead, in the
        placement the plan gave it.
        """
        key = (tuple(q.shape), jnp.dtype(q.dtype))
        if key not in self._executables:
            closed, out = jax.make_jaxpr(self._run, return_shape=True)(
                jax.ShapeDtypeStruct(q.shape, q.dtype))
            tree = jax.tree.structure(out)
            fn = jax.jit(lambda arrays, x: jax.tree.unflatten(
                tree, jax.core.eval_jaxpr(closed.jaxpr, arrays, x)))
            arrays = [c if isinstance(c, jax.Array) else jnp.asarray(c)
                      for c in closed.consts]
            self._executables[key] = (fn, arrays)
        return self._executables[key]

    # -- execution ---------------------------------------------------------
    def _validate_queries(self, queries) -> jax.Array:
        q = jnp.asarray(queries)
        if q.ndim != 2:
            raise ValueError(
                f"queries must be [Q, d], got shape {tuple(q.shape)}"
            )
        if q.shape[0] == 0:
            raise ValueError("empty query batch: queries.shape[0] == 0")
        if self._qdim is not None and int(q.shape[1]) != self._qdim:
            raise ValueError(
                f"query dim {int(q.shape[1])} != index dim {self._qdim}"
            )
        return q

    def __call__(self, queries) -> B.SearchResult:
        """Run ``queries`` [Q, d].  Host spans (``runtime.telemetry``)
        name the phases: ``searcher.call`` around the whole call, and
        inside it ``searcher.prepare`` (validation, upload, slicing and
        bucket pad), ``searcher.dispatch`` (executable lookup and launch;
        ``built=True`` where the lookup missed, so the launch compiles),
        ``searcher.wait`` (the read of a slice's stats, the one point in
        the call where the host may block on the device; stats computed
        from shapes alone are ready at once, and then the caller's copy
        of the answer is the first block) and ``searcher.assemble``.
        ``searcher.call``'s fields hold ``queries``, ``slices`` and, in
        bucketed calls, the call's ``DEVICE_STATS`` as device values."""
        with span("searcher.call") as call:
            with span("searcher.prepare"):
                q = self._validate_queries(queries)
                total = int(q.shape[0])
                slices = self._slices(q)
            call.update(queries=total, slices=len(slices))
            if self.batch_sizes is None:                   # one-shot mode
                with span("searcher.dispatch"):
                    res = self._run(q)
                return B.SearchResult(res.scores, res.ids, {
                    **res.stats, **self._extras,
                    "bucket": total, "padded_q": 0,
                })

            parts_s, parts_i = [], []
            padded_q = 0
            # batch-cumulative keys sum across slices; the remaining stats
            # (candidates/chunks/reranked: per-query by the engine
            # contract, identical in every slice) carry over from the last
            summed = {"bytes_read": 0, "rerank_bytes": 0}
            device_parts: dict[str, list] = {}
            stats: dict[str, Any] = {}
            for sl, rows in slices:
                with span("searcher.dispatch") as dispatch:
                    known = len(self._executables)
                    fn, arrays = self._executable(sl)
                    if len(self._executables) > known:
                        dispatch["built"] = True
                    res = fn(arrays, sl)
                with span("searcher.assemble"):
                    parts_s.append(res.scores[:rows])
                    parts_i.append(res.ids[:rows])
                    padded_q += int(sl.shape[0]) - rows
                with span("searcher.wait"):
                    for key in summed:
                        summed[key] += int(res.stats.get(key, 0))
                for key in DEVICE_STATS:
                    if key in res.stats:
                        device_parts.setdefault(key, []).append(
                            res.stats[key])
                stats = dict(res.stats)

            with span("searcher.assemble"):
                s, i = ((parts_s[0], parts_i[0]) if len(slices) == 1 else
                        (jnp.concatenate(parts_s), jnp.concatenate(parts_i)))
                stats.update(self._extras)
                stats.update(bucket=int(slices[-1][0].shape[0]),
                             padded_q=padded_q,
                             bytes_read=summed["bytes_read"])
                if summed["rerank_bytes"]:
                    stats["rerank_bytes"] = summed["rerank_bytes"]
                # summed on the device: an add per extra slice, no wait
                device = {key: functools.reduce(operator.add, parts)
                          for key, parts in device_parts.items()}
                stats.update(device)
                call.update(device)
            return B.SearchResult(s, i, stats)

    def _slices(self, q: jax.Array) -> list[tuple[jax.Array, int]]:
        """``q`` cut into slices of at most the largest bucket, each
        padded up to its bucket: [(padded slice, real rows), ...]."""
        if self.batch_sizes is None:
            return [(q, int(q.shape[0]))]
        total = int(q.shape[0])
        max_b = self.batch_sizes[-1]
        out = []
        for start in range(0, total, max_b):
            sl = q[start:start + max_b]
            rows = int(sl.shape[0])
            bucket = next(b for b in self.batch_sizes if b >= rows)
            if bucket > rows:
                sl = jnp.pad(sl, ((0, bucket - rows), (0, 0)))
            out.append((sl, rows))
        return out


def one_shot(index, queries, k: int, params: Optional[B.SearchParams]) -> B.SearchResult:
    """The eager path ``Index.search`` delegates to: a non-strict (k > n
    keeps the historical pad-with--1 contract), unbucketed, unsharded
    searcher built and called once."""
    return Searcher(index, k, params, batch_sizes=None, strict=False)(queries)
