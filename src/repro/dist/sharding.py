"""Mesh/NamedSharding rules for the dry-run cells and the launcher.

One convention everywhere: the production mesh is ("data", "model") —
optionally prefixed by a "pod" axis on the multi-pod mesh — and every
rule here degrades gracefully: a dimension is only sharded when its size
divides the axis size, otherwise that dimension is replicated, so the
same rules drive the 512-chip dry-run meshes and the 1-device host mesh
the tests run on.

Layout summary (DESIGN.md §4 records the serving side):
  * LM params: megatron-style — embed table vocab-sharded over "model";
    attention/MLP in-projections column-sharded, out-projections
    row-sharded over "model"; norms replicated.
  * ZeRO: gradient/optimizer accumulators additionally take "data" on
    their first replicated dimension (``lm_zero_spec``).
  * KV caches: batch-sharded over the data axes.
  * Recsys: big embedding tables row-sharded over ("data", "model")
    (DLRM hybrid parallelism); towers replicated.
  * GNN: edge lists sharded over the whole mesh; SchNet params replicated.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "P",
    "shard_map",
    "named",
    "replicated",
    "dp_axes",
    "corpus_shards",
    "sentinel_gids",
    "shard_rows",
    "lm_params_sharding",
    "lm_opt_sharding",
    "lm_grad_specs",
    "lm_zero_spec",
    "lm_cache_spec",
    "recsys_params_sharding",
    "recsys_opt_sharding",
    "gnn_params_sharding",
    "gnn_edge_sharding",
]


# --------------------------------------------------------------------------
# Generic helpers
# --------------------------------------------------------------------------

def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh, tree: Any):
    """Fully-replicated NamedSharding for every leaf of ``tree``."""
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    """The mesh axes the global batch is sharded over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def corpus_shards(mesh: Mesh) -> tuple[tuple[str, ...], int]:
    """Row-sharding rule for serving corpora (DESIGN.md §4/§9).

    A corpus ``CodeStore`` shards its rows over *every* mesh axis —
    queries are replicated, so there is no reason to leave devices idle —
    and the Searcher's compiled plan merges shard-local top-k with one
    k-sized cross-shard pass.  Returns (axes, n_shards).
    """
    axes = tuple(mesh.axis_names)
    return axes, int(mesh.devices.size)


def shard_rows(mesh: Mesh, x: jax.Array) -> jax.Array:
    """Place ``x`` with its leading axis split over every mesh axis (the
    ``corpus_shards`` rule).  Plans place their corpus once, at plan
    time, so no call reshards it from the device the build left it on."""
    axes, _ = corpus_shards(mesh)
    return jax.device_put(
        x, NamedSharding(mesh, P(axes, *([None] * (x.ndim - 1)))))


def sentinel_gids(gids, valid, *, shard, local_rows, n_total: int,
                  padded_rows: int):
    """Replace invalid slots' gids with globally-unique pad sentinels.

    A shard's tile-pad rows used to keep their arithmetic gid
    ``shard*rows_per + lrow`` — for ``lrow >= rows_per`` that value lands
    inside the NEXT shard's id range, so the only thing standing between
    a pad row and a real neighbor was the score mask.  Here every invalid
    slot instead gets

        ``n_total + shard * padded_rows + local_row``

    which is (a) ``>= n_total``, so it can never name a real row, and
    (b) unique across shards (each shard owns a disjoint
    ``padded_rows``-wide sentinel band), so even a dropped mask cannot
    alias two shards' pads onto one id.  Callers still NEG-mask the
    scores and map sentinels to ``-1`` at the plan boundary; the
    sentinel is the belt under that braces.

    ``shard`` and ``local_rows`` broadcast against ``gids`` (int32).
    """
    sent = (jnp.int32(n_total) + jnp.asarray(shard, jnp.int32) * padded_rows
            + jnp.asarray(local_rows, jnp.int32))
    return jnp.where(valid, jnp.asarray(gids, jnp.int32), sent)


def _axes_size(mesh: Mesh, axes: str | tuple[str, ...]) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


def _divisible(shape: tuple[int, ...], dim: int, mesh: Mesh, axes) -> bool:
    return dim < len(shape) and shape[dim] % max(_axes_size(mesh, axes), 1) == 0


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return "/".join(out)


def _spec_tree(mesh: Mesh, tree: Any, rule) -> Any:
    """tree of NamedSharding from rule(path_str, shape) -> P."""

    def leaf(path, x):
        shape = tuple(getattr(x, "shape", ()))
        spec = rule(_path_str(path), shape)
        # drop axes that do not divide — replicate those dims instead
        fixed = []
        for dim, entry in enumerate(spec):
            if entry is None:
                fixed.append(None)
            elif _divisible(shape, dim, mesh, entry):
                fixed.append(entry)
            else:
                fixed.append(None)
        return NamedSharding(mesh, P(*fixed))

    return jax.tree_util.tree_map_with_path(leaf, tree)


# --------------------------------------------------------------------------
# LM rules (megatron-style tensor parallelism over "model")
# --------------------------------------------------------------------------

# param-name suffixes whose *last* dim is column-sharded ("model")
_COL_KEYS = ("gate", "up", "wq", "wk", "wv", "w_gate", "router")
# suffixes whose *first matrix* dim is row-sharded (outputs get reduced)
_ROW_KEYS = ("down", "wo", "w_down")


def _lm_rule(path: str, shape: tuple[int, ...]) -> P:
    nd = len(shape)
    if nd <= 1:
        return P()                                     # norms, biases, scalars
    pad = [None] * (nd - 2)                            # leading vmapped block dims
    last2 = P(*pad, None, None)
    if "embed" in path and "table" in path:
        return P(*([None] * (nd - 2)), "model", None)  # vocab-sharded
    for key in _ROW_KEYS:
        if f"/{key}/" in path or path.endswith(f"/{key}/w"):
            return P(*pad, "model", None)
    for key in _COL_KEYS:
        if f"/{key}/" in path:
            return P(*pad, None, "model")
    return last2


def lm_params_sharding(mesh: Mesh, aparams: Any):
    """NamedSharding tree mirroring an LM abstract-params tree."""
    return _spec_tree(mesh, aparams, _lm_rule)


def lm_opt_sharding(mesh: Mesh, aopt: Any):
    """Optimizer state: mu/nu mirror the param layout; counters replicate."""
    return _spec_tree(mesh, aopt, _lm_rule)


def lm_zero_spec(path: str, leaf) -> P:
    """ZeRO accumulator spec: the param's "model" layout plus "data" on the
    first still-replicated dimension, so grad/optimizer accumulators live
    as 1/(data*model) slices instead of data-replicated copies."""
    shape = tuple(getattr(leaf, "shape", (1,) * getattr(leaf, "ndim", 0)))
    base = list(_lm_rule(path, shape))
    base += [None] * (len(shape) - len(base))
    for dim, entry in enumerate(base):
        if entry is None:
            base[dim] = "data"
            break
    return P(*base)


def lm_grad_specs(aparams: Any):
    """P-spec tree (not NamedSharding — used inside jit under a mesh
    context) for gradient accumulators, ZeRO layout."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: lm_zero_spec(_path_str(path), x), aparams
    )


def lm_cache_spec(mesh: Mesh, batch: int) -> NamedSharding:
    """KV cache [n_blocks, block_layers, B, S, Hkv, hd]: batch-sharded over
    the data axes when divisible, replicated otherwise (tiny decode B)."""
    dp = dp_axes(mesh)
    if dp and batch % _axes_size(mesh, dp) == 0:
        return NamedSharding(mesh, P(None, None, dp, None, None, None))
    return NamedSharding(mesh, P())


# --------------------------------------------------------------------------
# Recsys rules (DLRM hybrid parallelism)
# --------------------------------------------------------------------------

_TABLE_MIN_ROWS = 4096  # below this, tables replicate (the dry-run's pad rule)


def _recsys_rule_for(mesh: Mesh):
    shards = _axes_size(mesh, dp_axes(mesh) + ("model",)) if "model" in mesh.axis_names else 1

    def rule(path: str, shape: tuple[int, ...]) -> P:
        table_axes = tuple(a for a in ("data", "model") if a in mesh.axis_names)
        if (
            ("tables" in path or "codes" in path)
            and len(shape) == 2
            and shape[0] >= max(shards, _TABLE_MIN_ROWS)
        ):
            return P(table_axes, None)   # row-sharded embedding table
        return P(*([None] * len(shape)))  # towers/interactions replicate

    return rule


def recsys_params_sharding(mesh: Mesh, aparams: Any):
    return _spec_tree(mesh, aparams, _recsys_rule_for(mesh))


def recsys_opt_sharding(mesh: Mesh, aopt: Any):
    return _spec_tree(mesh, aopt, _recsys_rule_for(mesh))


# --------------------------------------------------------------------------
# GNN rules
# --------------------------------------------------------------------------

def gnn_params_sharding(mesh: Mesh, aparams: Any):
    """SchNet is tiny — replicate everything."""
    return replicated(mesh, aparams)


def gnn_edge_sharding(mesh: Mesh) -> NamedSharding:
    """Edge lists are padded to the full mesh size and sharded over it."""
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))
