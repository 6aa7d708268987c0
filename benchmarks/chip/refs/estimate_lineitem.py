"""Plain reference of the lineitem estimate front.

For a sample of blocks drawn from the seed: every record's cost units from
the mix's stated per-record cost (``record_cost``: units per row and per
selected row), the mean over the sampled records (``sample_ref``) in
float64, scaled to the block: units = mean * R.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.sample_ref import sampled_rows

NUMBER = "estimate_rel_err"   # largest |units - reference| / reference
# Provisional: set from readings on the CPU at the rehearsal size, not yet
# read on the chip at the cell's size (PERF.md, "correct").
LIMIT = 1e-10
N_CHECKED = 4                 # blocks checked a run: each hashes all its slots


def check_blocks(n_blocks: int, seed: int) -> list:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    n = min(N_CHECKED, n_blocks)
    return sorted(int(b) for b in rng.choice(n_blocks, size=n, replace=False))


def _units(select: np.ndarray, rows: np.ndarray, mix: dict, dtype):
    per_row, per_selected = (dtype(c) for c in mix["record_cost"])
    costs = per_row + per_selected * select[rows].astype(dtype)
    return costs.mean(dtype=dtype) * dtype(len(select))


def expected_units(ds: dict, config: dict, mix: dict, seed: int,
                   blocks) -> np.ndarray:
    sel = ds["select"]
    return np.asarray([_units(sel[b], sampled_rows(seed, b, sel.shape[1]), mix,
                              np.float64) for b in blocks])


def control_units(ds: dict, config: dict, mix: dict, seed: int,
                  blocks) -> np.ndarray:
    """The reference in the program's place, computed in float32."""
    sel = ds["select"]
    return np.asarray([float(_units(sel[b], sampled_rows(seed, b, sel.shape[1]),
                                    mix, np.float32)) for b in blocks])
