"""Plain reference of WordCount: every non-pad token id counted exactly."""
from __future__ import annotations

import numpy as np

NUMBER = "wordcount_mismatch"   # counts that differ, over every map task
AGG = "sum"
LIMIT = 0                       # exact: int32 counts, no rounding anywhere


def expected(block: dict, config: dict) -> np.ndarray:
    toks = block["tokens"]
    counts = np.bincount(toks[toks != 0], minlength=config["vocab"])
    counts[0] = 0
    return counts


def compare(out, want) -> float:
    out = np.asarray(out)
    if out.shape != want.shape:
        return float(want.size)
    return float(np.count_nonzero(out != want))


def control(block: dict, config: dict):
    """The reference in the program's place, counting in bfloat16."""
    import jax.numpy as jnp

    toks = jnp.asarray(block["tokens"]).reshape(-1)
    ones = (toks != 0).astype(jnp.bfloat16)
    counts = jnp.zeros((config["vocab"],), jnp.bfloat16).at[toks].add(ones)
    return counts.astype(jnp.int32).at[0].set(0)
