"""BaM core — the paper's contribution as a composable JAX library.

Layers (bottom-up):
  ssd.py        device cost models + Little's-law math   (paper §II-C, Tab. III)
  storage.py    block-store backends (host sim / in-graph)
  queues.py     high-throughput SQ/CQ rings              (paper §III-C)
  coalescer.py  wavefront dedup ("warp coalescing")      (paper §III-D)
  cache.py      set-assoc clock software cache           (paper §III-D)
  bam_array.py  BamArray / BamKVStore abstractions       (paper §III-E)
  prefetch.py   stride readahead + prefetch hints        (beyond paper)
  pipeline.py   fetch/compute overlap (latency hiding)   (paper §III-B)
  metrics.py    I/O amplification & throughput counters  (paper §II-B)
"""
from repro.core.bam_array import (
    BamArray, BamKVStore, BamRuntime, BamState, IORequest, IOToken,
    KVState, KVToken, RuntimeState, TenantCtx, TenantSpec,
)
from repro.core.cache import CacheState, make_cache
from repro.core.coalescer import CoalesceResult, coalesce
from repro.core.metrics import (
    IOMetrics, metrics_accumulate, metrics_delta, metrics_sum,
)
from repro.core.pipeline import pipelined_bam_map, software_pipeline
from repro.core.prefetch import PrefetchConfig, modal_stride, readahead_keys
from repro.core.queues import (
    QueueState, enqueue, in_flight, in_flight_per_device,
    in_flight_per_tenant, make_queues, service_all,
)
from repro.core.ssd import (
    ArrayOfSSDs, SSDSpec, SSD_PRESETS, DRAM_DIMM, INTEL_OPTANE_P5800X,
    SAMSUNG_980PRO, SAMSUNG_ZNAND_P1735, device_histogram, device_of_block,
    required_queue_depth, sustained_rate, target_iops_for_link,
)
from repro.core.storage import HBMStorage, SimStorage

__all__ = [
    "BamArray", "BamKVStore", "BamRuntime", "BamState", "IORequest",
    "IOToken", "KVState", "KVToken", "RuntimeState",
    "TenantCtx", "TenantSpec", "CacheState", "make_cache",
    "CoalesceResult", "coalesce", "IOMetrics", "metrics_accumulate",
    "metrics_delta", "metrics_sum", "pipelined_bam_map",
    "software_pipeline", "PrefetchConfig", "modal_stride", "readahead_keys",
    "QueueState", "enqueue", "in_flight", "in_flight_per_device",
    "in_flight_per_tenant", "make_queues", "service_all",
    "ArrayOfSSDs", "SSDSpec", "SSD_PRESETS", "DRAM_DIMM",
    "INTEL_OPTANE_P5800X", "SAMSUNG_980PRO", "SAMSUNG_ZNAND_P1735",
    "device_histogram", "device_of_block",
    "required_queue_depth", "sustained_rate", "target_iops_for_link",
    "HBMStorage", "SimStorage",
]
