"""Plain reference of AVG: per-group mean of the selected rows, in float64."""
from __future__ import annotations

import numpy as np

NUMBER = "avg_rel_err"   # largest |mean - reference| / |reference|, any group
AGG = "max"
# The configuration states it: TPC-H's query validation holds an AVG
# aggregate within 1 % of the validation output (clause 2.1.3.5).
LIMIT = 1e-2


def expected(block: dict, config: dict) -> np.ndarray:
    sel = block["select"]
    g = block["group"][sel]
    sums = np.bincount(g, weights=block["values"][sel].astype(np.float64),
                       minlength=config["n_groups"])
    counts = np.bincount(g, minlength=config["n_groups"])
    return sums / np.maximum(counts, 1)


def compare(out, want) -> float:
    out = np.asarray(out, np.float64)
    if out.shape != want.shape:
        return float("inf")
    return float(np.max(np.abs(out - want) / np.maximum(np.abs(want), 1e-30)))


def control(block: dict, config: dict):
    """The reference in the program's place, computed in bfloat16."""
    import jax.numpy as jnp

    v = jnp.asarray(block["values"]).astype(jnp.bfloat16)
    g = jnp.asarray(block["group"])
    m = jnp.asarray(block["select"]).astype(jnp.bfloat16)
    n = config["n_groups"]
    sums = jnp.zeros((n,), jnp.bfloat16).at[g].add(v * m)
    counts = jnp.zeros((n,), jnp.bfloat16).at[g].add(m)
    return (sums / jnp.maximum(counts, 1)).astype(jnp.float32)
