"""Tensorization layer: structs <-> dense arrays (north-star marshalling)."""
from .pack import (  # noqa: F401
    NodeMatrix, SpreadInfo, UsageState, bucket_size, fold_usage_base,
    invalidate_pack_caches, pack_affinities, pack_affinities_cached,
    pack_cache_stats, pack_feasibility, pack_feasibility_cached,
    pack_nodes, pack_spreads, pack_spreads_cached, pack_usage, PORT_WORDS,
)
