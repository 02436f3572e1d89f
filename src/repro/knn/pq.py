"""Product quantization (Jégou et al., the paper's §2 seminal reference)
and its composition with the paper's low-precision scheme.

The paper positions LPQ as *complementary* to PQ: "one can either replace
the original dataset with low-precision quantized vectors or use it after
the codebook mapping step for calculating the distance computations at
query time."  Both modes are implemented:

  * :class:`PQIndex` — classic PQ: split d into M subspaces, k-means a
    2^bits-codeword codebook per subspace (``pq<M>`` = 256 codewords,
    ``pq<M>x4`` = 16 codewords with codes bit-packed two per byte —
    Bolt / Quick-ADC's layout, half the code bytes), store codes in an
    ``engine.PQStore``, score by ADC through ``engine.topk``.
  * ``lpq_tables=True`` — the paper's composition: the ADC lookup tables
    themselves are quantized to int8 with Eq. 1 constants learned over
    the table entries, so the scan accumulates integers (int32) instead
    of f32 — the same implementation-level substitution the paper makes
    inside HNSW, applied after the codebook mapping step.  Integer
    tables are also what the fused Pallas ADC kernel
    (``kernels/adc.py``) holds VMEM-resident: it unpacks the nibble
    codes in-kernel and runs the LUT gather as one int8 MXU
    contraction, streaming a running top-k so the [Q, N] ADC matrix
    never materializes (engine dispatch: ``scorer._pq_fused``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro import engine
from repro.knn import base as B
from repro.knn import registry
from repro.knn.ivf import kmeans
from repro.knn.spec import IndexSpec, build_rerank_store, resolve_build_spec


@registry.register("pq")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PQIndex:
    metric: str = dataclasses.field(metadata=dict(static=True))
    store: engine.PQStore
    rerank_store: Optional[engine.CodeStore] = None

    # -- legacy views ------------------------------------------------------
    @property
    def m(self) -> int:
        return self.store.m

    @property
    def bits(self) -> int:
        """Codeword index width (4 or 8)."""
        return self.store.bits

    @property
    def n(self) -> int:
        return self.store.n

    @property
    def codes(self) -> jax.Array:
        return self.store.codes

    @property
    def codebooks(self) -> jax.Array:
        return self.store.codebooks

    @property
    def lpq_tables(self) -> bool:
        return self.store.lpq_tables

    @staticmethod
    def build(
        corpus: jax.Array,
        spec: IndexSpec | str | None = None,
        *,
        m: int = 8,
        metric: str = "ip",
        bits: int = 8,
        lpq_tables: bool = False,
        key: jax.Array | None = None,
        kmeans_iters: int = 8,
    ) -> "PQIndex":
        spec, p = resolve_build_spec(
            "pq", spec, metric=metric,
            m=m, bits=bits, lpq_tables=lpq_tables, kmeans_iters=kmeans_iters,
        )
        if p.get("regions"):
            # spec parsing rejects this; guard direct-kwargs construction too
            raise ValueError(
                "per-region Eq. 1 constants need a partitioned kind (ivf / "
                "hnsw / graph) — PQ codebooks already adapt per subspace, "
                "and its codes carry no region assignment"
            )
        m = int(p["m"])
        # codeword-count knob: 2^bits codewords per subspace codebook
        # (``pq16x4`` = 16, ``pq16`` = 256); PQStore validates the width
        bits = int(p["bits"] or 8)
        # "pq64+lpq" / "pq64,lpq8" — the paper's after-the-codebook
        # composition: int8 ADC lookup tables (codes are already <= 1 byte)
        lpq_tables = bool(p["lpq_tables"]) or spec.quant is not None
        kmeans_iters = int(p["kmeans_iters"])
        metric = spec.metric
        if metric == "angular":
            raise ValueError(
                "pq supports ip and l2 only — the ADC lookup tables have "
                "no per-row norm to rescale by (engine dispatch table)"
            )
        if key is None:
            key = jax.random.PRNGKey(0)
        corpus = jnp.asarray(corpus, jnp.float32)
        n, d = corpus.shape
        assert d % m == 0, (d, m)
        ds = d // m
        sub = corpus.reshape(n, m, ds)
        if bits not in engine.PQ_CODE_BITS:
            raise ValueError(
                f"pq codeword width must be one of {engine.PQ_CODE_BITS} "
                f"bits (16- or 256-codeword codebooks), got {bits}"
            )
        n_codewords = 2 ** bits

        books, codes = [], []
        for j in range(m):
            cb = kmeans(sub[:, j], min(n_codewords, n),
                        jax.random.fold_in(key, j), iters=kmeans_iters)
            if cb.shape[0] < n_codewords:   # tiny corpora: pad codebook
                cb = jnp.pad(cb, ((0, n_codewords - cb.shape[0]), (0, 0)))
            d2 = jnp.sum((sub[:, j][:, None, :] - cb[None]) ** 2, -1)
            books.append(cb)
            codes.append(jnp.argmin(d2, -1).astype(jnp.uint8))

        code_mat = jnp.stack(codes, 1)
        if bits == 4:                        # honest width: two per byte
            from repro.core import pack as PK

            code_mat = PK.pack_uint4(code_mat)
        store = engine.PQStore(
            n=n, m=m, bits=bits, lpq_tables=lpq_tables,
            codes=code_mat, codebooks=jnp.stack(books),
        )
        return PQIndex(metric=metric, store=store,
                       rerank_store=build_rerank_store(spec, corpus))

    # ------------------------------------------------------------------
    def placement(self, n_shards: int):
        """Contiguous code-row blocks — ADC scans shard like flat scans."""
        from repro.dist.placement import Placement

        return Placement.rows(self.n, n_shards)

    def plan(
        self,
        k: int,
        params: "B.SearchParams | None" = None,
        *,
        mesh=None,
        placement=None,
    ):
        """Freeze (k, chunk) into a pure ADC-scan runner.  A rerank tail
        over a ``"pq16+lpq,r32"`` build is the classic PQ+refine pattern.

        With a mesh, code rows shard in contiguous blocks: the per-query
        LUT is built (and, for ``lpq_tables``, Eq. 1-quantized)
        replicated — it is O(Q·M·K), the thing ADC exists to keep small —
        and each shard runs the streaming gather-sum scan over its block
        with sentinel-masked pad rows, merged by one ``distributed_topk``
        (block order == gid order, so the stable merge reproduces the
        unsharded scan's canonical tie-break bit-exactly).
        """
        if mesh is not None:
            return self._sharded_plan(k, params, mesh, placement)
        sp = params or B.SearchParams()
        fmask = (None if sp.filter is None
                 else jnp.asarray(sp.filter.aligned(self.n)))
        fstats = ({} if sp.filter is None
                  else {"filter_selectivity": round(sp.filter.selectivity, 6)})

        def run(queries: jax.Array) -> B.SearchResult:
            s, i, stats = engine.topk(
                queries, self.store, k, self.metric, chunk=sp.chunk,
                mask=fmask,
            )
            return B.SearchResult(
                s, i, {"kind": "pq", "m": self.m,
                       "lpq_tables": self.lpq_tables, **stats, **fstats},
            )

        return run

    def _sharded_plan(self, k, params, mesh, placement):
        """Row-block ADC scan under ``shard_map`` (DESIGN.md §15)."""
        from repro.core import pack as PK
        from repro.dist.placement import Placement
        from repro.dist.sharding import (
            P, corpus_shards, sentinel_gids, shard_map, shard_rows,
        )
        from repro.engine import distributed_topk, merge_topk
        from repro.engine.scorer import NEG, _prepare_pq_lut

        sp = params or B.SearchParams()
        axes, n_shards = corpus_shards(mesh)
        store = self.store
        n = store.n
        if placement is None:
            placement = Placement.rows(n, n_shards)
        if placement.kind != "rows" or placement.n_shards != n_shards:
            raise ValueError(
                f"pq plans shard contiguous code-row blocks; got a "
                f"{placement.kind!r} placement over {placement.n_shards} "
                f"shards (mesh has {n_shards})"
            )
        rows_per = -(-n // n_shards)
        pad = n_shards * rows_per - n
        k_eff = min(k, n)
        k_local = min(k_eff, rows_per)
        tile_rows = min(sp.chunk, rows_per)
        n_tiles = -(-rows_per // tile_rows)
        padded_rows = n_tiles * tile_rows
        data = shard_rows(mesh, jnp.pad(store.codes, ((0, pad), (0, 0)))
                          if pad else store.codes)
        shard_idx = shard_rows(mesh, jnp.arange(n_shards, dtype=jnp.int32))

        def tile_scores(lt, tile_codes):     # same math as _topk_pq_from_lut
            rows = (PK.unpack_uint4(tile_codes)[:, : store.m]
                    if store.packed else tile_codes)
            idx = rows.T[None].astype(jnp.int32)            # [1, M, c]
            return jnp.sum(
                jnp.take_along_axis(lt, idx, axis=2), axis=1
            ).astype(jnp.float32)

        # filter bitmap sliced per shard alongside the code rows: a
        # filtered row's `valid` goes False, sentinel_gids hands it a
        # sentinel >= n, and the existing ok fence + merge kill it —
        # exactly the pad-row dataflow (DESIGN.md §16)
        fmask = None
        if sp.filter is not None:
            fm = jnp.asarray(sp.filter.aligned(n)).astype(jnp.int8)
            fmask = shard_rows(mesh, jnp.pad(fm, (0, pad)) if pad else fm)

        def local(lt, shard, mshard, idx):
            gid0 = idx[0] * rows_per
            Q = lt.shape[0]
            tile_pad = padded_rows - rows_per
            if tile_pad:
                shard = jnp.pad(shard, ((0, tile_pad), (0, 0)))
                if mshard is not None:
                    mshard = jnp.pad(mshard, (0, tile_pad))
            tiles = shard.reshape(n_tiles, tile_rows, shard.shape[-1])
            mtiles = (jnp.zeros((n_tiles, 0), jnp.int8) if mshard is None
                      else mshard.reshape(n_tiles, tile_rows))

            def step(carry, inp):
                tile, mrow, t = inp
                s = tile_scores(lt, tile)
                lrow = t * tile_rows + jnp.arange(tile_rows, dtype=jnp.int32)
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
                ids = jnp.where(ok[None, :],
                                jnp.broadcast_to(gid[None], s.shape), -1)
                return merge_topk(*carry, s, ids, k_local), None

            init = (jnp.full((Q, k_local), NEG, jnp.float32),
                    jnp.full((Q, k_local), -1, jnp.int32))
            (ls, li), _ = jax.lax.scan(
                step, init,
                (tiles, mtiles, jnp.arange(n_tiles, dtype=jnp.int32)),
            )
            return distributed_topk(ls, li, k_eff, axes, 0)

        def local_plain(lt, shard, idx):
            return local(lt, shard, None, idx)

        if fmask is None:
            inner_plain = shard_map(
                local_plain,
                mesh=mesh,
                in_specs=(P(), P(axes, None), P(axes)),
                out_specs=(P(), P()),
                check_vma=False,
            )
        else:
            inner_masked = shard_map(
                local,
                mesh=mesh,
                in_specs=(P(), P(axes, None), P(axes), P(axes)),
                out_specs=(P(), P()),
                check_vma=False,
            )

        merge_wire = n_shards * k_eff * 8
        fstats = ({} if sp.filter is None
                  else {"filter_selectivity": round(sp.filter.selectivity, 6)})

        def run(queries: jax.Array) -> B.SearchResult:
            lut = _prepare_pq_lut(queries, store, self.metric)
            ilut = lut.astype(jnp.int32) if store.lpq_tables else lut
            if fmask is None:
                s, i = inner_plain(ilut, data, shard_idx)
            else:
                s, i = inner_masked(ilut, data, fmask, shard_idx)
            i = jnp.where(i >= n, -1, i)     # sentinels never leave the plan
            if k_eff < k:
                s = jnp.pad(s, ((0, 0), (0, k - k_eff)), constant_values=NEG)
                i = jnp.pad(i, ((0, 0), (0, k - k_eff)), constant_values=-1)
            stats = engine.search_stats(store, candidates=n,
                                        chunks=n_shards * n_tiles,
                                        rows_read=n)
            return B.SearchResult(s, i, {
                "kind": "pq", "m": self.m, "lpq_tables": self.lpq_tables,
                **stats, **fstats, "placement": "rows",
                "merge_wire_bytes": int(queries.shape[0]) * merge_wire,
            })

        return run

    def searcher(self, k: int, params: "B.SearchParams | None" = None, **kw):
        from repro.knn.searcher import Searcher

        return Searcher(self, k, params, **kw)

    def search(
        self,
        queries: jax.Array,
        k: int,
        params: "B.SearchParams | None" = None,
    ) -> B.SearchResult:
        """One-shot plan-and-run ADC scan (streaming LUT gather-sum).

        ``SearchParams.chunk`` sizes the scan tiles; PQ has no other
        search-time knob.
        """
        from repro.knn import searcher as S

        return S.one_shot(self, queries, k, params)

    def memory_bytes(self) -> int:
        total = self.store.memory_bytes()
        if self.rerank_store is not None:
            total += self.rerank_store.memory_bytes()
        return total

    # ------------------------------------------------------------------
    def save(self, path: str) -> None:
        arrays, meta = self.store.state()
        if self.rerank_store is not None:
            rr_a, rr_m = self.rerank_store.state(prefix="rr_")
            arrays = {**arrays, **rr_a}
            meta = {**meta, **rr_m}
        B.save_state(
            path, arrays,
            {"kind": "pq", "metric": self.metric, "m": self.m, "n": self.n,
             "lpq_tables": self.lpq_tables, **meta},
        )

    @staticmethod
    def load(path: str) -> "PQIndex":
        arrays, meta = B.load_state(path)
        return PQIndex(
            metric=meta["metric"],
            store=engine.PQStore.from_state(arrays, meta),
            rerank_store=(engine.CodeStore.from_state(arrays, meta, prefix="rr_")
                          if "rr_store" in meta else None),
        )
