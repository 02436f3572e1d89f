"""IVF (inverted-file) index — the TPU-native ANN structure.

HNSW's pointer-chasing traversal is hostile to a systolic machine; the
cluster-prune-then-scan pattern of IVF maps onto exactly two TPU-friendly
ops: a (small) dense matmul against the centroid table, and a gathered
batched matmul over the probed lists.  Both run through the engine layer:
the coarse probe is ``engine.topk`` over a dense centroid store, the fine
scan is ``engine.topk_among`` over the corpus store — fp32, int8 or
bit-packed int4 alike, so the paper's technique composes with IVF the
same way it composes with HNSW in §2 of the paper.

Lists are padded to a fixed length so every shape is static (jit/pjit
friendly); pad slots carry id -1 and are masked by the engine.

Registered as kind ``"ivf"``; factory strings: ``"ivf256"``,
``"ivf256,lpq8"``, ``"ivf256,lpq4"`` (packed int4).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro import engine
from repro.core import distances as D
from repro.core.blocks import fill_row_blocks
from repro.core import quant as Qz
from repro.knn import base as B
from repro.knn import registry
from repro.knn.spec import (
    IndexSpec,
    build_rerank_store,
    quant_spec_from_kwargs,
    resolve_build_spec,
)


# --------------------------------------------------------------------------
# k-means (Lloyd) — the coarse quantizer
# --------------------------------------------------------------------------

#: rows per k-means assignment block: bounds the [rows, C] score
#: temporaries, so a multi-million-row build fits one chip
ASSIGN_BLOCK = 65536


@jax.jit
def nearest_centroid(x: jax.Array, cents: jax.Array) -> jax.Array:
    """[N] int32 id of each row's L2-nearest centroid, in row blocks."""
    block = min(ASSIGN_BLOCK, x.shape[0])

    def assign(_b, start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, block)
        return jnp.argmax(D.l2_scores(xb, cents), axis=-1).astype(jnp.int32)

    return fill_row_blocks(jnp.zeros((x.shape[0],), jnp.int32), block, assign)


@partial(jax.jit, static_argnames=("n_clusters", "iters"))
def kmeans(
    x: jax.Array, n_clusters: int, key: jax.Array, iters: int = 10
) -> jax.Array:
    """Plain Lloyd k-means, random init, [N, d] -> [n_clusters, d]."""
    n = x.shape[0]
    x = x.astype(jnp.float32)
    init_ids = jax.random.choice(key, n, (n_clusters,), replace=False)
    cents = x[init_ids]

    def step(cents, _):
        assign = nearest_centroid(x, cents)
        counts = jnp.bincount(assign, length=n_clusters).astype(jnp.float32)
        sums = jax.ops.segment_sum(x, assign, num_segments=n_clusters)
        new = sums / jnp.maximum(counts[:, None], 1.0)
        # keep old centroid for empty clusters
        new = jnp.where(counts[:, None] > 0, new, cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    return cents


@registry.register("ivf")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class IVFIndex:
    metric: str = dataclasses.field(metadata=dict(static=True))
    nlist: int = dataclasses.field(metadata=dict(static=True))
    max_list: int = dataclasses.field(metadata=dict(static=True))
    centroids: jax.Array                 # [nlist, d] f32
    lists: jax.Array                     # [nlist, max_list] i32, -1 pad
    store: engine.CodeStore              # corpus payload at any precision
    rerank_store: Optional[engine.CodeStore] = None
    # per-list Eq. 1 constants ('ivf64,lpq8,regions' — DESIGN.md §14):
    # the store's codes are encoded under each row's own list constants
    # and fine scoring runs the regional dequant path; None = the global
    # single-constant path, bit-identical to pre-region builds
    regions: Optional["RegionQuant"] = None

    # -- legacy views ------------------------------------------------------
    @property
    def quantized(self) -> bool:
        return self.store.quantized

    @property
    def n(self) -> int:
        return self.store.n

    @property
    def data(self) -> jax.Array:
        return self.store.data

    @property
    def params(self) -> Optional[Qz.QuantParams]:
        return self.store.params

    @staticmethod
    def build(
        corpus: jax.Array,
        spec: IndexSpec | str | None = None,
        *,
        key: jax.Array | None = None,
        nlist: int = 64,
        metric: str = "ip",
        quantized: bool = False,
        bits: int = 8,
        scheme: str | Qz.Scheme = Qz.Scheme.GAUSSIAN,
        sigmas: float = 1.0,
        params: Optional[Qz.QuantParams] = None,
        kmeans_iters: int = 10,
    ) -> "IVFIndex":
        spec, p = resolve_build_spec(
            "ivf", spec, metric=metric,
            quant=quant_spec_from_kwargs(quantized, bits, scheme, sigmas, params),
            nlist=nlist, kmeans_iters=kmeans_iters,
        )
        nlist = int(p["nlist"])
        kmeans_iters = int(p["kmeans_iters"])

        if key is None:
            key = jax.random.PRNGKey(0)
        corpus = jnp.asarray(corpus, jnp.float32)
        cents = kmeans(corpus, nlist, key, iters=kmeans_iters)
        assign = nearest_centroid(corpus, cents)

        # bucket ids into fixed-width lists (host-side; build is offline)
        import numpy as np

        assign_np = np.asarray(assign)
        buckets = [np.where(assign_np == c)[0] for c in range(nlist)]
        max_list = max(1, max(len(b) for b in buckets))
        # round up for alignment
        max_list = ((max_list + 127) // 128) * 128
        lists = np.full((nlist, max_list), -1, np.int32)
        for c, b in enumerate(buckets):
            lists[c, : len(b)] = b

        regions = None
        if p.get("regions"):
            # density-aware per-list constants: each row encoded under its
            # own list's Eq. 1 fit (spec validation guarantees quant here)
            from repro.cascade import RegionQuant

            regions = RegionQuant.fit(
                corpus, assign_np, nlist,
                bits=spec.quant.bits, scheme=spec.quant.scheme,
                sigmas=spec.quant.sigmas,
            )
            # the store keeps nominal global constants for persistence /
            # compat, but its codes are regional — only the regional
            # dequant path in plan() may score them
            store = engine.CodeStore.from_codes(
                regions.encode(corpus), spec.quant.learn(corpus),
                pack=spec.quant.effective_packed,
            )
        else:
            store = (
                engine.CodeStore.dense(corpus)
                if spec.quant is None
                else spec.quant.build_store(corpus)
            )
        return IVFIndex(
            metric=spec.metric, nlist=nlist, max_list=max_list,
            centroids=cents, lists=jnp.asarray(lists), store=store,
            rerank_store=build_rerank_store(spec, corpus),
            regions=regions,
        )

    # ------------------------------------------------------------------
    def prepare_queries(self, queries: jax.Array) -> jax.Array:
        return self.store.encode_queries(queries)

    def list_sizes(self):
        """Per-list member counts (host ints) — what placement balances."""
        import numpy as np

        return tuple(int(x) for x in (np.asarray(self.lists) >= 0).sum(axis=1))

    def placement(self, n_shards: int):
        """Whole IVF lists, LPT-balanced by list size (DESIGN.md §15)."""
        from repro.dist.placement import Placement

        return Placement.lists(self.list_sizes(), n_shards)

    def plan(
        self,
        k: int,
        params: Optional[B.SearchParams] = None,
        *,
        mesh=None,
        placement=None,
    ):
        """Freeze (k, nprobe) into a pure probe-then-fine-score runner.

        With a mesh, lists are *placed*: each shard holds the code rows
        of the lists assigned to it (``Placement.lists``), the coarse
        probe and candidate gather stay replicated (routing metadata is
        tiny — the payload is what is placed), each shard fine-scores
        the candidates it owns, and one ``distributed_topk`` merge with
        id tie-breaking reproduces the unsharded ``topk_among``'s
        canonical (score desc, candidate-position asc) order bit-exactly
        (DESIGN.md §15).
        """
        if mesh is not None:
            return self._sharded_plan(k, params, mesh, placement)
        sp = params or B.SearchParams()
        nprobe = min(sp.nprobe, self.nlist)
        # filter (DESIGN.md §16): candidate-level mask over store rows,
        # plus a list-level skip — lists whose bitmap is empty are masked
        # out of the coarse probe itself, so their probe slots go to
        # lists that can still contribute
        fmask, lmask, fstats = self._filter_masks(sp)

        def run(queries: jax.Array) -> B.SearchResult:
            qf = jnp.asarray(queries, jnp.float32)
            qq = self.prepare_queries(queries)

            # 1) coarse: engine top-k over the (tiny, always-fp32)
            #    centroid store
            with jax.named_scope("ivf.coarse"):
                _cs, probe, _ = engine.topk(
                    qf, engine.CodeStore.dense(self.centroids), nprobe,
                    self.metric, mask=lmask,
                )

            # 2) gather candidate ids -> [Q, nprobe * max_list]; a fully
            #    masked-out probe slot (id -1 under the list skip) yields
            #    -1 candidates, dead at the fine-score fence
            with jax.named_scope("ivf.gather"):
                if lmask is None:
                    cand = self.lists[probe].reshape(qq.shape[0], -1)
                else:
                    probe_ok = probe >= 0
                    cand = jnp.where(
                        probe_ok[..., None],
                        self.lists[jnp.clip(probe, 0, self.nlist - 1)], -1,
                    ).reshape(qq.shape[0], -1)

            # 3) fine scoring + top-k through the engine (gather, unpack-
            #    as-needed, mask empties, select).  Regional builds must
            #    dequantize per row — codes from different lists live in
            #    different integer spaces, so raw-code scoring would
            #    silently compare across constant sets.
            with jax.named_scope("ivf.fine"):
                if self.regions is not None:
                    scores, ids = engine.topk_among_regional(
                        qf, self.store, self.regions.scale, self.regions.zero,
                        self.regions.assign, cand, k, self.metric, mask=fmask,
                    )
                    stats = {"kind": "ivf", "nprobe": nprobe, "chunks": nprobe,
                             **engine.regional_stats(self.store, cand)}
                else:
                    scores, ids = engine.topk_among(
                        qq, self.store, cand, k, self.metric, mask=fmask
                    )
                    stats = {"kind": "ivf", "nprobe": nprobe,
                             **engine.search_stats(
                                 self.store,
                                 candidates=nprobe * self.max_list,
                                 chunks=nprobe,
                                 rows_read=(qq.shape[0] * nprobe
                                            * self.max_list))}
            return B.SearchResult(scores, ids, {**stats, **fstats})

        return run

    def _filter_masks(self, sp):
        """(row mask [n] bool | None, probe mask [nlist] bool | None,
        filter stats) for ``sp.filter`` (DESIGN.md §16).  The probe mask
        marks lists with at least one allowed member; an all-dead list
        never earns a probe slot."""
        if sp.filter is None:
            return None, None, {}
        import numpy as np

        m = np.asarray(sp.filter.aligned(self.n))
        lists_np = np.asarray(self.lists)
        memb = lists_np >= 0
        allowed = np.zeros(lists_np.shape, bool)
        allowed[memb] = m[lists_np[memb]]
        lmask = allowed.any(axis=1)
        fstats = {"filter_selectivity": round(sp.filter.selectivity, 6),
                  "filter_lists_skipped": int((~lmask).sum())}
        return jnp.asarray(m), jnp.asarray(lmask), fstats

    def _sharded_plan(self, k, params, mesh, placement):
        """List-placed fine scoring under ``shard_map`` (DESIGN.md §15).

        Plan-time (host): group each shard's list members into a local
        row block ``codes [S, rows_max, width]`` (row permutation is safe
        — packing is per-row) plus replicated ``owner [N]`` / ``local_of
        [N]`` routing maps.  Query-time (one jit): replicated coarse
        probe -> replicated candidate vector ``cand [Q, W]`` -> each
        shard scores the candidate *slots* whose rows it owns (identical
        per-query gather/score shapes to ``topk_among``, so owned slots
        score bit-identically) -> local top-k over slot positions ->
        ``distributed_topk(tie_break="id")`` on (-score, position) ->
        positions map back to gids through the replicated ``cand``.
        Unowned/pad slots carry NEG scores and lose every comparison;
        ids never travel un-masked (positions >= 0 only for real rows).
        """
        import numpy as np

        from repro.dist.placement import Placement
        from repro.dist.sharding import P, corpus_shards, shard_map, shard_rows
        from repro.engine import by_query_block, distributed_topk
        from repro.engine.scorer import NEG
        from repro.core import pack as PK

        sp = params or B.SearchParams()
        nprobe = min(sp.nprobe, self.nlist)
        # filter: same row/list masks as the unsharded plan — the row
        # mask ANDs into each shard's slot-ownership test (a filtered
        # slot is as dead as an unowned one), the list mask skips empty
        # lists at the replicated coarse probe (DESIGN.md §16)
        fmask, lmask, fstats = self._filter_masks(sp)
        axes, n_shards = corpus_shards(mesh)
        if placement is None:
            placement = Placement.lists(self.list_sizes(), n_shards)
        if placement.kind != "lists" or placement.n_units != self.nlist:
            raise ValueError(
                f"ivf plans place whole lists; got a {placement.kind!r} "
                f"placement over {placement.n_units} units (nlist={self.nlist})"
            )
        if placement.n_shards != n_shards:
            raise ValueError(
                f"placement covers {placement.n_shards} shards but the mesh "
                f"has {n_shards}"
            )

        n = self.store.n
        lists_np = np.asarray(self.lists)
        owner = np.zeros(n, np.int32)
        local_of = np.zeros(n, np.int32)
        shard_gids = []
        for s in range(n_shards):
            mine = [lists_np[c][lists_np[c] >= 0]
                    for c in placement.shard_units(s)]
            gids = (np.concatenate(mine).astype(np.int64) if mine
                    else np.zeros(0, np.int64))
            owner[gids] = s
            local_of[gids] = np.arange(gids.size, dtype=np.int32)
            shard_gids.append(gids)
        rows_max = max(1, max(g.size for g in shard_gids))
        data_np = np.asarray(self.store.data)
        codes = np.zeros((n_shards, rows_max) + data_np.shape[1:],
                         data_np.dtype)
        for s, gids in enumerate(shard_gids):
            codes[s, : gids.size] = data_np[gids]
        codes = shard_rows(mesh, jnp.asarray(codes))
        owner = jnp.asarray(owner)
        local_of = jnp.asarray(local_of)
        shard_idx = shard_rows(mesh, jnp.arange(n_shards, dtype=jnp.int32))

        W = nprobe * self.max_list
        k_eff = min(k, W)
        regional = self.regions is not None
        store = self.store

        def local(q, cand, codes_s, idx):
            codes_s = codes_s[0]                    # [rows_max, width]
            shard = idx[0]

            def block(q, cand):     # the same query blocks as topk_among
                safe = jnp.clip(cand, 0, n - 1)
                ok = (cand >= 0) & (owner[safe] == shard)
                if fmask is not None:
                    ok = ok & fmask[safe]
                rows = codes_s[jnp.where(ok, local_of[safe], 0)]  # [q, W, w]
                if store.packed:
                    rows = PK.unpack_int4(rows)
                if regional:
                    reg = self.regions.assign[safe]               # [q, W]
                    x = (rows.astype(jnp.float32) * self.regions.scale[reg]
                         + self.regions.zero[reg])
                    s = D.scores_among(q, x, self.metric, quantized=False)
                else:
                    s = D.scores_among(q, rows, self.metric,
                                       quantized=store.quantized)
                s = jnp.where(ok, s.astype(jnp.float32), NEG)
                ls, pos = jax.lax.top_k(s, k_eff)
                # merge on candidate POSITIONS — the id space whose
                # ascending tie-break equals topk_among's stable top_k
                return ls, jnp.where(ls > NEG, pos, -1).astype(jnp.int32)

            ls, li = by_query_block(block, q, cand)
            return distributed_topk(ls, li, k_eff, axes, 0, tie_break="id")

        inner = shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(), P(axes, None, None), P(axes)),
            out_specs=(P(), P()),
            check_vma=False,
        )

        merge_wire = n_shards * k_eff * 8

        def run(queries: jax.Array) -> B.SearchResult:
            qf = jnp.asarray(queries, jnp.float32)
            qq = self.prepare_queries(queries)
            _cs, probe, _ = engine.topk(
                qf, engine.CodeStore.dense(self.centroids), nprobe,
                self.metric, mask=lmask,
            )
            if lmask is None:
                cand = self.lists[probe].reshape(qq.shape[0], -1)   # [Q, W]
            else:
                probe_ok = probe >= 0
                cand = jnp.where(
                    probe_ok[..., None],
                    self.lists[jnp.clip(probe, 0, self.nlist - 1)], -1,
                ).reshape(qq.shape[0], -1)
            s, pos = inner(qf if regional else qq, cand, codes, shard_idx)
            ids = jnp.where(
                pos >= 0,
                jnp.take_along_axis(cand, jnp.clip(pos, 0, W - 1), axis=1),
                -1,
            ).astype(jnp.int32)
            if store.base:
                ids = jnp.where(ids >= 0, ids + store.base, -1)
            if k_eff < k:
                s = jnp.pad(s, ((0, 0), (0, k - k_eff)), constant_values=NEG)
                ids = jnp.pad(ids, ((0, 0), (0, k - k_eff)),
                              constant_values=-1)
            if regional:
                stats = {"kind": "ivf", "nprobe": nprobe, "chunks": nprobe,
                         **engine.regional_stats(store, cand)}
            else:
                stats = {"kind": "ivf", "nprobe": nprobe,
                         **engine.search_stats(
                             store,
                             candidates=W,
                             chunks=nprobe,
                             rows_read=qq.shape[0] * W)}
            stats.update(placement="lists",
                         merge_wire_bytes=int(qq.shape[0]) * merge_wire,
                         **fstats)
            return B.SearchResult(s, ids, stats)

        return run

    def searcher(self, k: int, params: Optional[B.SearchParams] = None, **kw):
        from repro.knn.searcher import Searcher

        return Searcher(self, k, params, **kw)

    def search(
        self,
        queries: jax.Array,
        k: int,
        params: Optional[B.SearchParams] = None,
        *,
        nprobe: int | None = None,
    ) -> B.SearchResult:
        """One-shot plan-and-run: probe the nprobe best lists per query,
        exact-score the members.  Returns ``SearchResult`` [Q, k]."""
        from repro.knn import searcher as S

        sp = (params or B.SearchParams()).merged(nprobe=nprobe)
        return S.one_shot(self, queries, k, sp)

    def memory_bytes(self) -> int:
        base = self.store.memory_bytes()
        base += self.centroids.size * 4 + self.lists.size * 4
        if self.rerank_store is not None:
            base += self.rerank_store.memory_bytes()
        if self.regions is not None:
            base += self.regions.memory_bytes()
        return base

    def region_drift(self, live_corpus):
        """Per-list calibration drift of a live corpus against the fitted
        per-region constants ([nlist] floats; +inf marks stale/empty
        lists).  Live rows are assigned by the build centroids, so the
        report answers 'would this corpus still be well-served by the
        constants each list learned at build time?'."""
        if self.regions is None:
            raise ValueError(
                "region_drift needs a per-region build — construct the "
                "index with an '...,regions' factory (e.g. 'ivf64,lpq8,regions')"
            )
        live = jnp.asarray(live_corpus, jnp.float32)
        live_assign = nearest_centroid(live, self.centroids)
        return self.regions.drift_report(live, live_assign)

    # ------------------------------------------------------------------
    def save(self, path: str) -> None:
        arrays, meta = self.store.state()
        if self.rerank_store is not None:
            rr_a, rr_m = self.rerank_store.state(prefix="rr_")
            arrays.update(rr_a)
            meta.update(rr_m)
        if self.regions is not None:
            rg_a, rg_m = self.regions.state(prefix="rg_")
            arrays.update(rg_a)
            meta.update(rg_m)
        B.save_state(
            path,
            {"centroids": self.centroids, "lists": self.lists, **arrays},
            {"kind": "ivf", "metric": self.metric, "quantized": self.quantized,
             "n": self.n, "nlist": self.nlist, "max_list": self.max_list,
             **meta},
        )

    @staticmethod
    def load(path: str) -> "IVFIndex":
        arrays, meta = B.load_state(path)
        regions = None
        if "rg_regions" in meta:
            from repro.cascade import RegionQuant

            regions = RegionQuant.from_state(arrays, meta, prefix="rg_")
        return IVFIndex(
            metric=meta["metric"], nlist=meta["nlist"],
            max_list=meta["max_list"],
            centroids=jnp.asarray(arrays["centroids"]),
            lists=jnp.asarray(arrays["lists"]),
            store=engine.CodeStore.from_state(arrays, meta),
            rerank_store=(engine.CodeStore.from_state(arrays, meta, prefix="rr_")
                          if "rr_store" in meta else None),
            regions=regions,
        )
