"""Allocation serving layer: micro-batched scenario service over `solve_batch`.

The pipeline is  request -> `pad_params` into a `ShapeBucket` -> per-bucket
admission queue (`MicroBatcher`) -> one AOT-compiled `solve_batch` executable
per (bucket, batch-slots, AllocatorConfig) -> hardened exact-shape
`Allocation` back to the caller (scored through the batched
`kernels/fedsem_objective` evaluator, `Completion.objective`), with p50/p95
latency, queue-depth and batch-occupancy metrics along the way.

Two drivers sit on top of the sans-IO core: the virtual-clock load generator
(`loadgen.run_load`, reproducible DES for tests/benchmarks) and the
real-clock threaded `driver.RealClockDriver` (bounded admission queue,
solver thread, deadline timer, graceful drain). `ladder.LadderLearner`
learns an autoscaling `ShapeBucket` ladder from the observed shape mix.
`warmstart.WarmStartCache` closes the recurring-user loop: completed
hardened solutions are recorded under a quantized channel/accuracy signature
and re-enter later solves as an extra multi-start candidate — never-worse by
the multi-start dominance argument, bit-identical to the cold path when
disabled or missing.

Layer-wide equivalence contract: padding (shape buckets), co-batching
(micro-batches), sharding (`shard_batch`), the kernel objective path and the
real-clock driver are all *transparent* — each request's hardened allocation
and objective match a solo exact-shape `solve` to float32 round-off,
asserted respectively in `tests/test_serve_alloc.py`,
`tests/test_distribute.py`, `tests/test_kernels.py` and
`tests/test_serve_driver.py`.
"""
from .aio import AsyncAllocDriver
from .batching import BatchPolicy, MicroBatcher, PendingRequest
from .driver import (
    AdmissionQueueFull, DriverClosed, DriverConfig, RealClockDriver,
    pace_stream, same_hardened_assignments,
)
from .ladder import (
    LadderLearner, LadderSnapshot, learn_buckets, padded_area_waste,
)
from .loadgen import LoadResult, poisson_arrivals, run_load, scenario_stream
from .metrics import Reservoir, ServiceMetrics, percentile, span
from .service import AllocService, Completion, FlushTiming, ServeConfig
from .warmstart import (
    CacheEntry, WarmStartCache, WarmStartConfig, batch_starts,
    entry_from_alloc, iters_to_converge, pad_start, request_signature,
)

__all__ = [
    "AllocService", "Completion", "FlushTiming", "ServeConfig",
    "WarmStartCache", "WarmStartConfig", "CacheEntry", "request_signature",
    "entry_from_alloc", "pad_start", "batch_starts", "iters_to_converge",
    "BatchPolicy", "MicroBatcher", "PendingRequest",
    "ServiceMetrics", "Reservoir", "percentile", "span",
    "LoadResult", "poisson_arrivals", "run_load", "scenario_stream",
    "AsyncAllocDriver",
    "RealClockDriver", "DriverConfig", "AdmissionQueueFull", "DriverClosed",
    "pace_stream", "same_hardened_assignments",
    "LadderLearner", "LadderSnapshot", "learn_buckets", "padded_area_waste",
]
