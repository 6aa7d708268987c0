"""Plain reference of the text estimate front.

For each block: the sampled records (``sample_ref``), their non-pad token
count, pattern matches and token-id mass counted exactly in integers, priced
by the pipeline's linear token cost and scaled from the k sampled records to
the block: units = (w . [nonpad, matches, mass]) / k * R.  This checks the
``block_stats`` kernel on the window's own sampled rows.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.sample_ref import sample_size, sampled_rows

NUMBER = "estimate_rel_err"   # largest |units - reference| / reference
# Provisional: set from readings on the CPU at the rehearsal size, not yet
# read on the chip at the cell's size (PERF.md, "correct").
LIMIT = 1e-4
# repro.pipeline.stream.DEFAULT_TOKEN_COST_WEIGHTS, frozen
WEIGHTS = (2e-6, 5e-5, 1e-9)


def check_blocks(n_blocks: int, seed: int) -> list:
    return list(range(n_blocks))


def _sampled(tokens: np.ndarray, seed: int, b: int) -> np.ndarray:
    return tokens[b][sampled_rows(seed, b, tokens.shape[1])]


def _matches(rows: np.ndarray, pattern) -> int:
    p = len(pattern)
    n_win = rows.shape[1] - p + 1
    win = np.ones((rows.shape[0], n_win), bool)
    for j, pj in enumerate(pattern):
        win &= rows[:, j:n_win + j] == pj
    return int(win.sum())


def expected_units(ds: dict, config: dict, mix: dict, seed: int,
                   blocks) -> np.ndarray:
    toks = ds["tokens"]
    r = toks.shape[1]
    k = sample_size(r)
    out = []
    for b in blocks:
        rows = _sampled(toks, seed, b)
        stats = (int(np.count_nonzero(rows)),
                 _matches(rows, config["grep_pattern"]),
                 int(rows.sum(dtype=np.int64)))
        out.append(sum(w * s for w, s in zip(WEIGHTS, stats)) / k * r)
    return np.asarray(out)


def control_units(ds: dict, config: dict, mix: dict, seed: int,
                  blocks) -> np.ndarray:
    """The reference in the program's place, its statistics in bfloat16."""
    import jax.numpy as jnp

    toks = ds["tokens"]
    r = toks.shape[1]
    k = sample_size(r)
    pattern = config["grep_pattern"]
    p = len(pattern)
    out = []
    for b in blocks:
        rows = jnp.asarray(_sampled(toks, seed, b))
        n_win = rows.shape[1] - p + 1
        hits = jnp.ones((rows.shape[0], n_win), bool)
        for j, pj in enumerate(pattern):
            hits = hits & (rows[:, j:n_win + j] == pj)
        stats = ((rows != 0).astype(jnp.bfloat16).sum(),
                 hits.astype(jnp.bfloat16).sum(),
                 rows.astype(jnp.bfloat16).sum())
        out.append(sum(w * float(s) for w, s in zip(WEIGHTS, stats)) / k * r)
    return np.asarray(out)
