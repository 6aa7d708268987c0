"""AVG (TPC-H analogue) and SUM (Amazon-reviews analogue) aggregations.

Blocks carry numeric columns next to the tokens:
  * ``values``  (N,) float32 — e.g. l_extendedprice / review rating,
  * ``group``   (N,) int32   — e.g. shipmode bucket / product bucket,
  * ``select``  (N,) bool    — predicate (the Zipf-varied quantity).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
from jax import lax

__all__ = ["Average", "Sum"]


def _group_sums(block, n_groups: int):
    """Per-group sums of the selected ``values`` (in their own dtype) and
    exact int32 counts of the selected rows, each of shape ``(n_groups,)``.

    One dense masked reduction, with no indexed write: every row is compared
    against all ``n_groups`` ids and added to each accumulator under a
    select, so the cost is O(rows x n_groups). Laid out as ``(n_groups,
    rows)``, XLA reads the three columns once, in one fusion, with the
    groups on sublanes and the rows on lanes (a scatter into a few buckets
    serialises on the colliding indices). Rows whose group id lies outside
    ``[0, n_groups)`` are dropped.
    """
    v = block["values"]
    ids = jnp.arange(n_groups, dtype=block["group"].dtype)[:, None]
    hit = block["select"] & (block["group"] == ids)
    sums_cnts = lax.reduce(
        (jnp.where(hit, v, 0), hit.astype(jnp.int32)),
        (jnp.zeros((), v.dtype), jnp.zeros((), jnp.int32)),
        lambda a, b: (a[0] + b[0], a[1] + b[1]), (1,))
    # Keeps the reduction two-output where a caller reads only the sums:
    # XLA's CPU backend fuses the two-output reduction, but rewrites a lone
    # float one into reduce-windows over the materialised (n_groups, rows)
    # array, ~10x slower.
    return lax.optimization_barrier(sums_cnts)


@dataclasses.dataclass(frozen=True)
class Average:
    n_groups: int = 8
    name: str = "avg"

    def run(self, block):
        sums, cnts = _group_sums(block, self.n_groups)
        return sums / jnp.maximum(cnts, 1).astype(sums.dtype)

    def flops(self, stats: dict) -> float:
        return 6.0 * stats["records"] + 32.0 * stats.get("selected", 0.0)

    def cost_features(self, stats: dict) -> dict:
        return {"records": float(stats["records"]),
                "selected": float(stats.get("selected", 0.0)), "const": 1.0}


@dataclasses.dataclass(frozen=True)
class Sum:
    n_groups: int = 8
    name: str = "sum"

    def run(self, block):
        return _group_sums(block, self.n_groups)[0]

    def flops(self, stats: dict) -> float:
        return 4.0 * stats["records"] + 16.0 * stats.get("selected", 0.0)

    def cost_features(self, stats: dict) -> dict:
        return {"records": float(stats["records"]),
                "selected": float(stats.get("selected", 0.0)), "const": 1.0}
