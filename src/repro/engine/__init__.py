# The scoring engine (DESIGN.md §8): CodeStore/PQStore own corpus storage
# at any precision (fp32 / int8 / bit-packed int4 / PQ codewords) with
# honest memory accounting; the Scorer owns the whole query hot path —
# metric x bits kernel dispatch, chunking, padding, invalid-id masking and
# streaming top-k — so index classes hold structure and call
# ``engine.topk`` / ``topk_among`` / ``make_score_set`` and nothing else.
# Every top-k implementation lives here: the fused Pallas kernels, the
# streaming scan core, the generic score-fn ``chunked_topk``, the
# cross-shard ``distributed_topk`` merge, and the ``remap_ids`` gather the
# stream layer uses to map internal rows back to external ids.
from repro.engine.scorer import (
    build_pq_lut,
    by_query_block,
    chunked_topk,
    distributed_topk,
    get_lut_cache,
    make_score_set,
    merge_topk,
    pad_rows,
    quantize_pq_lut,
    refine_among,
    regional_stats,
    remap_ids,
    rerank_among,
    search_stats,
    set_lut_cache,
    topk,
    topk_among,
    topk_among_regional,
)
from repro.engine.store import PQ_CODE_BITS, CodeStore, PQStore

__all__ = [
    "CodeStore",
    "PQStore",
    "PQ_CODE_BITS",
    "build_pq_lut",
    "by_query_block",
    "quantize_pq_lut",
    "topk",
    "topk_among",
    "topk_among_regional",
    "refine_among",
    "regional_stats",
    "rerank_among",
    "make_score_set",
    "search_stats",
    "merge_topk",
    "pad_rows",
    "chunked_topk",
    "distributed_topk",
    "remap_ids",
    "set_lut_cache",
    "get_lut_cache",
]
