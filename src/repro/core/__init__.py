# Coconut — sortable data-series summarizations + compact/contiguous indexes.
# The paper's primary contribution lives here; `distributed` maps it onto a
# TPU pod mesh (sample-sort build, broadcast-prune-reduce queries).
from .summarization import SummarizationConfig, breakpoints, paa, sax, sax_from_paa
from .sortable import (
    interleave, deinterleave, sort_by_keys, searchsorted_keys,
    searchsorted_keys_batch,
)
from .lower_bounds import ed2, mindist_paa_sax2, mindist_region2, topk_ed2
from .io_model import DiskModel, IOStats, coalesce_ranges, render_heatmap
from .external_sort import external_sort_order
from .plan import (
    BlockSource, DenseSource, GroupSource, QueryPlan, QueryStats, RangeSource,
    SourceOps,
)
from .execute import (
    execute, empty_topk_state, heap_to_sorted, merge_topk_state, recall_at_k,
    state_to_list,
)
from .ctree import CTree, CTreeConfig, RawStore, SortedRun
from .run_registry import BufferChunk, RunRegistry, RunSet
from .clsm import CLSM, CLSMConfig
from .ingest import IngestPipeline
from .storage import (
    FileStore, SimulatedCrash, StorageEngine, WriteAheadLog, resolve_backend,
)
from .streaming import StreamConfig, StreamingIndex
from .adsplus import ADSConfig, ADSIndex
from .recommender import (
    RationaleEntry, Scenario, Recommendation, TierDecision, recommend,
    serving_tier,
)
from .autotune import (
    AutoTuner, AutoTunerConfig, DecisionRecord, Knobs, WorkloadKey,
    knob_grid, workload_key,
)

# the gateway serves through the device engine and so loads jax: it is
# imported on first use, so importing the package keeps the numpy host
# path jax-free
_GATEWAY = ("Gateway", "GatewayConfig", "GatewayStats", "Response", "Ticket")


def __getattr__(name):
    if name in _GATEWAY:
        from . import gateway

        return getattr(gateway, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "SummarizationConfig", "breakpoints", "paa", "sax", "sax_from_paa",
    "interleave", "deinterleave", "sort_by_keys", "searchsorted_keys",
    "searchsorted_keys_batch",
    "ed2", "mindist_paa_sax2", "mindist_region2", "topk_ed2",
    "DiskModel", "IOStats", "coalesce_ranges", "render_heatmap",
    "external_sort_order",
    "BlockSource", "DenseSource", "GroupSource", "QueryPlan", "QueryStats",
    "RangeSource", "SourceOps", "execute", "state_to_list",
    "CTree", "CTreeConfig", "RawStore", "SortedRun", "heap_to_sorted",
    "empty_topk_state", "merge_topk_state", "recall_at_k",
    "CLSM", "CLSMConfig", "StreamConfig", "StreamingIndex",
    "BufferChunk", "RunRegistry", "RunSet", "IngestPipeline",
    "FileStore", "SimulatedCrash", "StorageEngine", "WriteAheadLog",
    "resolve_backend",
    "ADSConfig", "ADSIndex", "Scenario", "Recommendation", "TierDecision",
    "RationaleEntry", "recommend", "serving_tier",
    "AutoTuner", "AutoTunerConfig", "DecisionRecord", "Knobs",
    "WorkloadKey", "knob_grid", "workload_key",
    "Gateway", "GatewayConfig", "GatewayStats", "Response", "Ticket",
]

# Runtime sanitizer (lock-order assertions + snapshot seals): opt-in via
# env var so the slow-tier stress tests can run with invariants armed
# while production imports stay untouched. See repro.analysis.sanitize.
import os as _os

if _os.environ.get("REPRO_SANITIZE") == "1":
    from ..analysis.sanitize import install as _sanitize_install

    _sanitize_install()
