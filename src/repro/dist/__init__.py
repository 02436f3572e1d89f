# Distribution utilities: mesh-sharding rules for every model family,
# placement plans assigning rows/lists/segments to mesh shards, and
# replica-group query fan-out.
from repro.dist import placement
from repro.dist.placement import Placement
from repro.dist.replica import ReplicaSet, replicated_query_plan, submeshes
from repro.dist.sharding import (
    P,
    corpus_shards,
    dp_axes,
    named,
    replicated,
    sentinel_gids,
    shard_map,
)

__all__ = [
    "P",
    "Placement",
    "ReplicaSet",
    "corpus_shards",
    "dp_axes",
    "named",
    "placement",
    "replicated",
    "replicated_query_plan",
    "sentinel_gids",
    "shard_map",
    "submeshes",
]
