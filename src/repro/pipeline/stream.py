"""The chunked SoA dataset→plan path of one node (see package docstring).

``stream_estimates`` drives the sampling stage chunk by chunk and
accumulates ``EstimateArrays``; ``plan_estimates`` hands the accumulated SoA
straight to the vectorized single-node planner.  ``stream_estimates_tokens``
is the token-blocks front: it picks each block's sample rows by stateless
hash, reduces them with ONE ``block_stats_batched_pallas`` dispatch per
chunk (the kernel's ragged-row masking handles per-block sample sizes), and
prices records with a linear model over the kernel's [nonpad, matches,
mass] features.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Iterator

import numpy as np

from repro.core.energy import DEFAULT_LADDER, FrequencyLadder, PowerModel, TPU_V5E_POWER
from repro.core.sampling import (_DOMAIN_SAMPLER, _hash_uniform,
                                 _z_for_confidence, sample_blocks_soa)
from repro.core.scheduler import plan_dvfs_arrays
from repro.core.soa import EstimateArrays, PlanArrays
from repro.obs.tracer import count, span

__all__ = ["PipelineConfig", "stream_estimates", "stream_estimates_tokens",
           "sample_token_rows", "token_chunk_estimates", "plan_estimates"]

# default linear record-cost model over the kernel's per-row features:
# seconds ≈ w·[nonpad, matches, mass].  Values are arbitrary but fixed —
# benchmarks and tests care about the variety STRUCTURE, not the unit.
DEFAULT_TOKEN_COST_WEIGHTS = (2e-6, 5e-5, 1e-9)


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Everything the dataset→plan path needs, in one place."""

    chunk_size: int = 65536
    # sampling stage
    fraction: float = 0.05
    min_samples: int = 16
    confidence: float = 0.95
    seed: int = 0
    # planning stage
    planner: str = "global"
    ladder: FrequencyLadder = DEFAULT_LADDER
    power: PowerModel = TPU_V5E_POWER
    error_margin: float = 0.05


def _iter_chunks(source, chunk_size: int) -> Iterator[dict]:
    """Normalize a source into chunk dicts (see ``repro.pipeline.sources``)."""
    if isinstance(source, np.ndarray):
        if source.ndim != 2:
            raise ValueError("array sources must be 2D (n_blocks, n_records)")
        for start in range(0, len(source), chunk_size):
            yield {"costs": source[start:start + chunk_size]}
        return
    for chunk in source:
        yield chunk


_END = object()


def _pulls(chunks: Iterable, n_blocks) -> Iterator:
    """The chunks of ``chunks``, each pulled inside an ``estimate.source``
    span: a lazy source does its work there, not in the sampler's spans."""
    it = iter(chunks)
    while True:
        with span("estimate.source"):
            chunk = next(it, _END)
            if chunk is not _END:
                count(blocks=n_blocks(chunk))
        if chunk is _END:
            return
        yield chunk


def stream_estimates(source, config: PipelineConfig = PipelineConfig()
                     ) -> EstimateArrays:
    """Sampling stage: chunked per-record costs -> per-block ``EstimateArrays``.

    Each chunk is one ``sample_blocks_soa`` call (global block indices keep
    the draws chunk-invariant); accumulation is a list of SoA parts
    concatenated once — no per-block Python objects anywhere.
    """
    parts = []
    offset = 0
    # the request's root span; "pipeline." keeps it apart from a caller's
    # own span around the call, such as a job's "estimate" step
    with span("pipeline.estimate"):
        for chunk in _pulls(_iter_chunks(source, config.chunk_size),
                            lambda c: len(c["costs"])):
            costs = np.asarray(chunk["costs"], dtype=np.float64)
            est = sample_blocks_soa(
                costs, chunk.get("lengths"), fraction=config.fraction,
                min_samples=config.min_samples, confidence=config.confidence,
                seed=config.seed, start_index=offset)
            parts.append(est)
            offset += len(est)
            count(blocks=len(est), records=int(est.n_records.sum()))
        return EstimateArrays.concat(parts)


def sample_token_rows(tokens: np.ndarray, *, start_index: int,
                      config: PipelineConfig = PipelineConfig()) -> tuple:
    """The rows ``token_chunk_estimates`` reads from one (B, R, L) chunk.

    Returns ``(sampled (B, kmax, L), k (B,))``: each block's ``k`` rows
    picked by the sampler's stateless hash keyed by global block index, in
    hash order (every block samples the same ``k`` here).
    """
    tokens = np.asarray(tokens)
    b, r, length = tokens.shape
    index = start_index + np.arange(b, dtype=np.int64)
    k = np.minimum(r, np.maximum(max(int(config.min_samples), 1),
                                 int(np.ceil(config.fraction * r))))
    k = np.full(b, k, dtype=np.int64)
    kmax = int(k.max()) if b else 0
    if kmax == 0:
        return np.zeros((b, 0, length), tokens.dtype), k
    with span("sample.keys", keys=b * r):
        keys = _hash_uniform(config.seed, index[:, None],
                             np.arange(r, dtype=np.int64)[None, :],
                             domain=_DOMAIN_SAMPLER)
    with span("sample.select", rows=int(k.sum())):
        part = np.argpartition(keys, kmax - 1, axis=1)[:, :kmax]
        order = np.argsort(np.take_along_axis(keys, part, axis=1), axis=1,
                           kind="stable")
        sel = np.take_along_axis(part, order, axis=1)
        sampled = np.take_along_axis(tokens, sel[:, :, None], axis=1)
        count(bytes=sampled.nbytes)
    return sampled, k


def token_chunk_estimates(
    tokens: np.ndarray,
    *,
    start_index: int,
    config: PipelineConfig = PipelineConfig(),
    pattern: tuple = (17, 23, 5),
    weights: tuple = DEFAULT_TOKEN_COST_WEIGHTS,
    interpret: bool | None = None,
) -> EstimateArrays:
    """Estimate one (B, R, L) token chunk: hash-sampled rows through ONE
    batched stats kernel dispatch, linear cost model, analytic CI.

    Row selection reuses the sampler's stateless hash keyed by global block
    index, so estimates are chunk-size-invariant.  The kernel reduces all
    sampled rows in a single ``pallas_call`` (its per-block valid-row
    masking absorbs the varying sample sizes); the per-row feature
    decomposition — cheap NumPy over just the sampled rows — prices the CI.
    """
    from repro.kernels import ops

    tokens = np.asarray(tokens)
    b, r, length = tokens.shape
    index = start_index + np.arange(b, dtype=np.int64)
    sampled, k = sample_token_rows(tokens, start_index=start_index,
                                   config=config)
    kmax = sampled.shape[1]
    if kmax == 0:
        z0 = np.zeros(b)
        return EstimateArrays(index, z0, z0.copy(), z0.copy(), k,
                              np.full(b, r, dtype=np.int64))

    # block-level sampled features: ONE fused kernel dispatch for the chunk;
    # the span ends once the features are back on the host
    with span("estimate.kernel", bytes=4 * (sampled.size + b)):
        stats = np.asarray(ops.block_stats_batched(
            sampled.astype(np.int32), k.astype(np.int32), tuple(pattern),
            interpret=interpret), dtype=np.float64)
    with span("sample.stats", rows=int(k.sum())):
        w = np.asarray(weights, dtype=np.float64)
        mean_cost = (stats @ w) / k

        # per-row decomposition of the same features -> sample variance -> CI
        nonpad_r = (sampled != 0).sum(axis=2)
        mass_r = sampled.astype(np.float64).sum(axis=2)
        p = len(pattern)
        if length >= p:
            hits = np.ones((b, kmax, length - p + 1), dtype=bool)
            for j, pj in enumerate(pattern):
                hits &= sampled[:, :, j:length - p + 1 + j] == pj
            match_r = hits.sum(axis=2)
        else:
            match_r = np.zeros((b, kmax), dtype=np.int64)
        cost_r = w[0] * nonpad_r + w[1] * match_r + w[2] * mass_r
        valid = np.arange(kmax)[None, :] < k[:, None]
        row_mean = np.where(valid, cost_r, 0.0).sum(axis=1) / k
        var = (np.where(valid, cost_r - row_mean[:, None], 0.0) ** 2) \
            .sum(axis=1) / np.maximum(k - 1, 1)
        se = np.sqrt(var / k)
        hw = _z_for_confidence(config.confidence) * se * r
        total = mean_cost * r
    return EstimateArrays(index, total, total - hw, total + hw, k,
                          np.full(b, r, dtype=np.int64))


def stream_estimates_tokens(
    token_chunks: Iterable,
    config: PipelineConfig = PipelineConfig(),
    *,
    pattern: tuple = (17, 23, 5),
    weights: tuple = DEFAULT_TOKEN_COST_WEIGHTS,
    interpret: bool | None = None,
) -> EstimateArrays:
    """Sampling stage over ``(start, tokens)`` chunks (e.g.
    ``BlockDataset.iter_token_chunks``)."""
    parts = []
    with span("pipeline.estimate"):
        for start, toks in _pulls(token_chunks, lambda c: len(c[1])):
            est = token_chunk_estimates(toks, start_index=start,
                                        config=config, pattern=pattern,
                                        weights=weights, interpret=interpret)
            parts.append(est)
            count(blocks=len(est), records=int(est.n_records.sum()))
        return EstimateArrays.concat(parts)


def plan_estimates(
    est: EstimateArrays,
    deadline_s: float,
    config: PipelineConfig = PipelineConfig(),
) -> PlanArrays:
    """Planning stage: SoA estimates straight into the vectorized planner.

    A cluster plan belongs to the layer above: the cluster package's
    ``plan_cluster_arrays`` over the same ``est.to_block_arrays()``.
    """
    # the record holds what a missed deadline is read from: the deadline,
    # the summed estimate and the plan's own predicted time
    with span("pipeline.plan", blocks=len(est), deadline_s=float(deadline_s),
              est_s=float(est.total.sum())):
        plan = plan_dvfs_arrays(est.to_block_arrays(), deadline_s,
                                planner=config.planner, ladder=config.ladder,
                                power=config.power,
                                error_margin=config.error_margin)
        count(planned_s=plan.pred_total_time)
        return plan
