"""Pieces of the device-side data generator shared by the dataset kinds.

Copies of the distributions in ``repro.data.synth`` and
``repro.data.BlockDataset``, written with ``jax.random`` so that a dataset of
gigabytes is drawn on the chip in seconds.  Same distributions, not the same
bits as the NumPy generator.
"""
from __future__ import annotations

import numpy as np

__all__ = ["seed_streams", "zipf_densities", "zipf_alias_table"]


def seed_streams(seed: int) -> tuple:
    """(jax key, NumPy Generator) from any non-negative integer seed."""
    import jax

    ss = np.random.SeedSequence(int(seed))
    word = int(ss.generate_state(1, np.uint32)[0])
    return jax.random.key(word), np.random.default_rng(ss)


def zipf_densities(n_blocks: int, z: float, base: float, top: float,
                   rng: np.random.Generator) -> np.ndarray:
    """Per-block predicate densities, Zipf(z)-ranked and shuffled to
    aggregation order (``BlockDataset.match_densities``)."""
    w = np.arange(1, n_blocks + 1, dtype=np.float64) ** (-float(z))
    w /= w.sum()
    d = base + (top - base) * w / w.max()
    return d[rng.permutation(n_blocks)]


def zipf_alias_table(vocab: int, z: float) -> tuple:
    """Walker/Vose alias table of Zipf(z) over ids 1..vocab-1.

    Draw ``j`` uniform in [0, vocab-1) and ``u`` uniform in [0, 1); the id is
    ``j + 1`` if ``u < prob[j]`` else ``alias[j] + 1``: exactly the
    distribution ``SourceSpec.sample_records`` draws by inverse CDF.
    """
    ranks = np.arange(1, vocab, dtype=np.float64)
    p = ranks ** (-float(z))
    n = len(p)
    q = p / p.sum() * n
    prob = np.ones(n)
    alias = np.arange(n)
    small = list(np.nonzero(q < 1.0)[0])
    large = list(np.nonzero(q >= 1.0)[0])
    while small and large:
        s, g = small.pop(), large.pop()
        prob[s], alias[s] = q[s], g
        q[g] -= 1.0 - q[s]
        (small if q[g] < 1.0 else large).append(g)
    return prob.astype(np.float32), alias.astype(np.int32)
