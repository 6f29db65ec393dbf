"""tpudml.serve — multi-tenant prefill–decode LM serving.

Layers: ``cache`` (dense preallocated per-layer KV caches,
f32/bf16/int8), ``paged`` (page-pool cache + slot→page table + prefix
sharing), ``spec`` (speculative decoding with exact greedy
acceptance-rejection), ``sched`` (SLO-aware admission priced on the
static cost model), ``engine`` (ONE jitted decode step + chunked
prefill + slot scheduler composing all of the above), ``load`` (seeded
Poisson request streams), ``tp`` (the dense steps under shard_map on a
tensor-parallel mesh; TP × {paged, spec, weight_quant} raises
ServeCompositionError), ``fleet`` (scale-OUT: multi-replica router with
drain/re-admit membership, disaggregated prefill/decode handoff, int8
weight quantization — imported lazily, see ``tpudml.serve.fleet``).
See docs/API.md §Serving.
"""

from tpudml.serve.cache import KVCache, LatentCache, RecurrentState, cache_bytes, init_cache
from tpudml.serve.engine import (
    SERVE_DECODE_MARKER,
    RequestStats,
    ServeCompositionError,
    ServeConfig,
    ServeReport,
    ServingEngine,
    make_decode_step,
    make_paged_decode_step,
)
from tpudml.serve.load import Request, poisson_workload
from tpudml.serve.paged import (
    PAGED_DECODE_MARKER,
    PagedKVCache,
    PagePool,
    init_pool,
    pool_bytes,
)
from tpudml.serve.sched import DecodeCostModel, SLOConfig
from tpudml.serve.spec import (
    SPEC_DECODE_MARKER,
    draft_from_trunk,
    make_spec_decode_step,
)

_FLEET_EXPORTS = (
    "FleetConfig", "FleetReport", "FleetRequestStats", "FleetRouter",
    "replay_fleet_fixture",
)


def __getattr__(name):
    # Lazy: the fleet tier pulls in the checkpoint store (disagg handoff)
    # and, for the drill, the elastic controller stack — none of which a
    # plain single-engine import should pay for.
    if name in _FLEET_EXPORTS:
        import tpudml.serve.fleet as fleet

        return getattr(fleet, name)
    raise AttributeError(name)


__all__ = [
    "RecurrentState",
    "FleetConfig",
    "FleetReport",
    "FleetRequestStats",
    "FleetRouter",
    "KVCache",
    "LatentCache",
    "PAGED_DECODE_MARKER",
    "PagePool",
    "PagedKVCache",
    "Request",
    "RequestStats",
    "SERVE_DECODE_MARKER",
    "SPEC_DECODE_MARKER",
    "DecodeCostModel",
    "SLOConfig",
    "ServeCompositionError",
    "ServeConfig",
    "ServeReport",
    "ServingEngine",
    "cache_bytes",
    "draft_from_trunk",
    "init_cache",
    "init_pool",
    "make_decode_step",
    "make_paged_decode_step",
    "make_spec_decode_step",
    "poisson_workload",
    "pool_bytes",
    "replay_fleet_fixture",
]
