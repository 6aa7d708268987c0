"""Streaming dataset→plan pipeline of one node — DV-DVFS at the
million-block regime.

The paper's pipeline (Fig. 3/4) is sample → estimate → plan.  The object
path builds one ``BlockStats``/``BlockEstimate``/``BlockInfo``/``BlockPlan``
per block per stage; at 10⁶ blocks the Python object churn dwarfs the actual
math.  This package is the same pipeline as chunked structure-of-arrays
flow:

    chunk source ──> sample_blocks_soa / block_stats_batched_pallas
                 ──> EstimateArrays (SoA)
                 ──> BlockArrays ──> plan_dvfs_arrays ──> PlanArrays

Chunks are bounded (``PipelineConfig.chunk_size``, default 64k blocks) so
peak memory is bounded by chunk size plus the per-block SoA accumulators,
not the dataset; no per-block Python object is created anywhere on the
path (``to_blocks()`` materializes them on demand only).

The package imports ``repro.core`` and the span recorder
``repro.obs.tracer``, and nothing above them.  A cluster plan or a
simulated run is composed by the caller from the layers above: the cluster
package's ``plan_cluster_arrays`` over ``est.to_block_arrays()``, then the
runtime's ``run_cluster``.

Equivalence contract (``tests/test_pipeline.py``): the streamed plans are
IDENTICAL — same frequency per block, same energies — to the object path
run on the same estimates, for any chunk size, including chunk boundaries
that split a node's block set.

Throughput numbers: ``python -m benchmarks.run --section pipeline`` (CPU
host timings).
"""
from repro.pipeline.arrivals import (ArrivalSpec, JobArrival, TenantSpec,
                                     generate_arrivals)
from repro.pipeline.sources import synthetic_cost_chunks
from repro.pipeline.stream import (PipelineConfig, plan_estimates,
                                   stream_estimates, stream_estimates_tokens,
                                   token_chunk_estimates)

__all__ = [
    "ArrivalSpec",
    "JobArrival",
    "PipelineConfig",
    "TenantSpec",
    "generate_arrivals",
    "plan_estimates",
    "stream_estimates",
    "stream_estimates_tokens",
    "synthetic_cost_chunks",
    "token_chunk_estimates",
]
