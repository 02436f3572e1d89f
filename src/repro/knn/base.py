"""The common ``Index`` protocol: one call shape for every index kind.

Every registered index implements

    build(corpus, spec, *, key=None)          -> Index
    search(queries, k, params=None)           -> SearchResult
    memory_bytes()                            -> int
    save(path) / load(path)                   -> disk round-trip

``SearchParams`` unifies the per-kind search knobs (``chunk`` for the
exhaustive scan, ``nprobe`` for IVF, ``ef_search`` for the graph walks);
each index reads the knobs it understands and ignores the rest, so one
``SearchParams`` drives any kind — the registry-driven serving loop and
benchmarks depend on exactly that property.

``SearchResult`` carries (scores, ids, stats).  It unpacks like the
historical ``(scores, ids)`` pair so pre-unification call sites keep
working: ``scores, ids = index.search(q, k)``.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Iterator, Optional, Protocol, runtime_checkable

import jax
import numpy as np

from repro.knn.spec import IndexSpec


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Union of every index kind's search-time knobs.

    chunk      exhaustive-scan working-set bound (flat, pq): scan-chunk
               rows on the unfused path, corpus-tile cap for the fused
               kernels
    nprobe     probed lists per query (ivf)
    ef_search  beam width of the graph walk (hnsw, graph)
    budgets    per-stage fetch depths of a cascade index (DESIGN.md §14):
               ``budgets[i]`` is how many candidates refinement stage
               ``i`` receives; must be non-increasing and each >= k
               (validated at plan time).  ``None`` = geometric defaults.
               A tuple (not a list) so SearchParams stays hashable — it
               rides inside compiled-plan and result-cache keys.
    filter     a ``repro.filter.Filter`` predicate bitmap over *external*
               row ids (DESIGN.md §16); every kind pushes it into the
               engine's id-masking path so only allowed rows can be
               returned.  Filters hash by bitmap digest, so SearchParams
               stays a valid compiled-plan / result-cache key member.
    """

    chunk: int = 16384
    nprobe: int = 8
    ef_search: int = 100
    budgets: Optional[tuple[int, ...]] = None
    filter: Optional[Any] = None

    def merged(self, **overrides) -> "SearchParams":
        live = {k: v for k, v in overrides.items() if v is not None}
        return dataclasses.replace(self, **live) if live else self

    def validate(self) -> "SearchParams":
        """Reject nonsense knobs at plan time (clear ``ValueError``s now
        instead of kernel-shape errors deep inside a trace)."""
        for name in ("chunk", "nprobe", "ef_search"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
                raise ValueError(
                    f"SearchParams.{name} must be a positive int, got {v!r}"
                )
        if self.budgets is not None:
            if not isinstance(self.budgets, tuple) or not self.budgets:
                raise ValueError(
                    f"SearchParams.budgets must be a non-empty tuple of "
                    f"positive ints (or None), got {self.budgets!r}"
                )
            for v in self.budgets:
                if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
                    raise ValueError(
                        f"SearchParams.budgets entries must be positive "
                        f"ints, got {v!r} in {self.budgets!r}"
                    )
        if self.filter is not None:
            from repro.filter import Filter

            if not isinstance(self.filter, Filter):
                raise ValueError(
                    f"SearchParams.filter must be a repro.filter.Filter "
                    f"(or None), got {type(self.filter).__name__}"
                )
        return self


@dataclasses.dataclass
class SearchResult:
    """scores [Q, k] f32 (larger-is-closer), ids [Q, k] i32 (-1 = no hit),
    stats: per-search accounting (kind, candidates scored, ...)."""

    scores: jax.Array
    ids: jax.Array
    stats: dict[str, Any] = dataclasses.field(default_factory=dict)

    # legacy pair protocol: ``scores, ids = index.search(...)`` and
    # ``index.search(...)[1]`` predate SearchResult and stay valid.
    def __iter__(self) -> Iterator[jax.Array]:
        return iter((self.scores, self.ids))

    def __getitem__(self, i):
        return (self.scores, self.ids)[i]

    def __len__(self) -> int:
        return 2


def _flatten_result(r: SearchResult):
    # an array's shape stands in for it where a trace returns shapes
    device = tuple(sorted(k for k, v in r.stats.items()
                          if isinstance(v, (jax.Array, jax.ShapeDtypeStruct))))
    static = tuple(sorted((k, v) for k, v in r.stats.items()
                          if k not in device))
    return (r.scores, r.ids, *(r.stats[k] for k in device)), (static, device)


def _unflatten_result(aux, kids) -> SearchResult:
    static, device = aux
    return SearchResult(kids[0], kids[1],
                        {**dict(static), **dict(zip(device, kids[2:]))})


# a jax pytree (scores, ids and device-valued stats such as the fused
# kernel's merge counter are leaves; the other stats are static aux data)
# so jitted callers can return it, as they could the old (scores, ids)
# tuple
jax.tree_util.register_pytree_node(SearchResult, _flatten_result,
                                   _unflatten_result)


@runtime_checkable
class Index(Protocol):
    """Structural protocol every registered index satisfies.

    The query side is plan-then-execute (DESIGN.md §9): ``plan`` freezes
    k + SearchParams into a pure runner, ``searcher`` wraps that runner in
    the compiled/bucketed/rerank-capable ``Searcher`` handle, and
    ``search`` is sugar — a one-shot searcher call — kept for every
    pre-plan call site.
    """

    kind: str

    @staticmethod
    def build(corpus, spec: IndexSpec | str | None = None, *, key=None) -> "Index":
        ...

    def search(self, queries, k: int, params: Optional[SearchParams] = None) -> SearchResult:
        ...

    def plan(self, k: int, params: Optional[SearchParams] = None, *, mesh=None,
             placement=None):
        ...

    def searcher(self, k: int, params: Optional[SearchParams] = None, **kwargs):
        ...

    def memory_bytes(self) -> int:
        ...

    def save(self, path: str) -> None:
        ...

    @staticmethod
    def load(path: str) -> "Index":
        ...


# --------------------------------------------------------------------------
# Disk round-trip: one .npz per index — arrays plus a JSON meta record.
# --------------------------------------------------------------------------

_META_KEY = "__meta__"


def save_state(path, arrays: dict[str, Any], meta: dict[str, Any]) -> None:
    """Write an index's arrays + static metadata as a single ``.npz``.

    ``meta`` must be JSON-serializable and include ``kind`` so
    ``registry.load_index`` can dispatch without knowing the class.
    ``path`` may be a filesystem path or a binary file-like object — the
    stream manifest embeds each sealed segment's inner-index npz as a
    byte blob inside its own npz, so index save/load must compose through
    in-memory buffers (DESIGN.md §10).

    When a TuneTable is installed (``repro.tune``), it rides along under
    ``meta["tune"]`` so a reloaded index serves with the configs it was
    tuned with (``registry.load_index`` adopts it, stamp-checked).
    """
    from repro.tune import table as tunetable

    active_table = tunetable.active()
    if active_table is not None and "tune" not in meta:
        meta = {**meta, "tune": active_table.to_dict()}
    out = {k: np.asarray(v) for k, v in arrays.items() if v is not None}
    out[_META_KEY] = np.frombuffer(
        json.dumps(meta).encode("utf-8"), dtype=np.uint8
    )
    if hasattr(path, "write"):
        np.savez(path, **out)
        return
    with open(path, "wb") as f:
        np.savez(f, **out)


def load_state(path) -> tuple[dict[str, np.ndarray], dict[str, Any]]:
    if hasattr(path, "seek"):
        path.seek(0)              # compose after load_meta on one buffer
    with np.load(path) as z:
        meta = json.loads(bytes(z[_META_KEY].tobytes()).decode("utf-8"))
        arrays = {k: z[k] for k in z.files if k != _META_KEY}
    return arrays, meta


def load_meta(path) -> dict[str, Any]:
    """Read only the metadata record — npz members load lazily, so this
    never materializes the (possibly huge) index arrays."""
    if hasattr(path, "seek"):
        path.seek(0)
    with np.load(path) as z:
        return json.loads(bytes(z[_META_KEY].tobytes()).decode("utf-8"))


# Quantization-constant (de)serialization lives with the storage layer:
# ``engine.CodeStore.state`` / ``from_state`` — index save/load merges the
# store's fragments into its own npz record.
