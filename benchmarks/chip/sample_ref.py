"""The rows the program's sampler reads, worked out independently.

A plain copy of the sampling rule the pipeline states: each block keeps
k = min(R, max(min_samples, ceil(fraction * R))) records, those with the k
smallest stateless keys, where a key is the splitmix64 finalizer over
(seed, domain, block index, record slot) scaled to [0, 1).  Nothing here
imports the program.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["FRACTION", "MIN_SAMPLES", "sample_size", "sampled_rows"]

FRACTION = 0.05      # PipelineConfig() defaults: the configuration as run
MIN_SAMPLES = 16
_DOMAIN_SAMPLER = 3
_MASK = 0xFFFFFFFFFFFFFFFF


def sample_size(records: int) -> int:
    return min(records, max(MIN_SAMPLES, math.ceil(FRACTION * records)))


def _keys(seed: int, block: int, records: int) -> np.ndarray:
    mix = ((int(seed) * 0x9E3779B97F4A7C15)
           ^ (_DOMAIN_SAMPLER * 0xD1B54A32D192ED03 + 0x632BE59BD9B4E019)) & _MASK
    with np.errstate(over="ignore"):
        z = (np.uint64(block) << np.uint64(24)) \
            ^ np.arange(records, dtype=np.uint64)
        z = z + np.uint64(mix)
        z ^= z >> np.uint64(30)
        z *= np.uint64(0xBF58476D1CE4E5B9)
        z ^= z >> np.uint64(27)
        z *= np.uint64(0x94D049BB133111EB)
        z ^= z >> np.uint64(31)
    return (z >> np.uint64(11)).astype(np.float64) / float(1 << 53)


def sampled_rows(seed: int, block: int, records: int) -> np.ndarray:
    """Indices of the records the sampler keeps in one block, ascending."""
    k = sample_size(records)
    keys = _keys(seed, block, records)
    if k >= records:
        return np.arange(records)
    return np.sort(np.argpartition(keys, k - 1)[:k])
