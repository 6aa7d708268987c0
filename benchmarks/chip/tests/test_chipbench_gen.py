"""The device generator draws the distributions of ``repro.data``.

Small sizes on the CPU: record lengths and token ids per source against
``SourceSpec.sample_records``, the planted match densities against
``BlockDataset.match_densities``, the lineitem columns against TPC-H's
published answer of Q1.
"""
import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip.cells import BENCH_DIR, load_module, repo_root
from repro.data import BlockDataset
from repro.data.synth import SOURCES

ROOT = repo_root()


def _config(name: str, **changes) -> dict:
    cfg = json.loads((ROOT / BENCH_DIR / "configs" / f"{name}.json").read_text())
    cfg.update(changes)
    return cfg


@pytest.mark.parametrize("source", range(len(SOURCES)))
def test_text_lengths_and_ids_per_source(source):
    spec = SOURCES[source]
    cfg = _config("hibench-text-large", blocks=2, records_per_block=4096,
                  sources=[_config("hibench-text-large")["sources"][source]],
                  base_match_density=0.0, max_match_density=0.0)
    toks = load_module(ROOT, "kinds", "text").generate(cfg, seed=2**31 + 5)[
        "tokens"].reshape(-1, cfg["max_len"])
    want = spec.sample_records(len(toks), cfg["max_len"], cfg["vocab"],
                               np.random.default_rng(0))
    got_len, want_len = (toks != 0).sum(1), (want != 0).sum(1)
    assert got_len.mean() == pytest.approx(want_len.mean(), rel=0.03)
    assert got_len.min() >= 1 and got_len.max() <= cfg["max_len"]
    # a record is its first `length` positions, all non-pad
    assert np.all((toks != 0).sum(1) == np.argmin(
        np.concatenate([toks, np.zeros((len(toks), 1), toks.dtype)], 1) != 0,
        axis=1))
    for tok in (1, 2, 10):
        got = np.mean(toks[toks != 0] == tok)
        ref = np.mean(want[want != 0] == tok)
        assert got == pytest.approx(ref, rel=0.08), tok
    assert toks.max() < cfg["vocab"]


def test_text_match_densities_and_planting():
    cfg = _config("hibench-text-large", blocks=8, records_per_block=2048)
    ds = load_module(ROOT, "kinds", "text").generate(cfg, seed=7)
    ref = BlockDataset(n_blocks=8, records_per_block=2048, seed=7)
    np.testing.assert_allclose(np.sort(ds["densities"]),
                               np.sort(ref.match_densities()), rtol=1e-12)
    p = cfg["grep_pattern"]
    for b in range(8):
        toks = ds["tokens"][b]
        win = np.ones((len(toks), toks.shape[1] - len(p) + 1), bool)
        for j, pj in enumerate(p):
            win &= toks[:, j:toks.shape[1] - len(p) + 1 + j] == pj
        planted = int(round(ds["densities"][b] * len(toks)))
        rows = int(win.any(axis=1).sum())
        # every planted record holds the pattern; chance matches are rare
        assert planted <= rows <= planted + 3


# TPC-H's published answer of Q1 at SF1 (DELTA 90): rows and average
# l_extendedprice of each (l_returnflag, l_linestatus) group
Q1_SF1 = {("A", "F"): (1478493, 38273.13), ("N", "F"): (38854, 38284.47),
          ("N", "O"): (2920374, 38249.12), ("R", "F"): (1478870, 38250.85)}
LINEITEM_SF1_ROWS = 6001215


def test_lineitem_columns_and_select_densities():
    cfg = _config("tpch-lineitem-sf30", blocks=6, records_per_block=200_000)
    kind = load_module(ROOT, "kinds", "lineitem")
    ds = kind.generate(cfg, seed=11)
    sel = ds["select"]
    n_q1 = sum(n for n, _ in Q1_SF1.values())
    assert sel.mean() == pytest.approx(n_q1 / LINEITEM_SF1_ROWS, abs=0.002)
    # the predicate is spread evenly over the table: no block stands out
    assert np.ptp(sel.mean(axis=1)) < 0.003
    groups = ds["group"][sel]
    values = ds["values"][sel].astype(np.float64)
    assert set(np.unique(groups)) == {kind.group_key(*g) for g in Q1_SF1}
    for g, (n, avg) in Q1_SF1.items():
        mine = groups == kind.group_key(*g)
        assert mine.mean() == pytest.approx(n / n_q1, abs=0.002), g
        se = values[mine].std() / np.sqrt(mine.sum())
        assert values[mine].mean() == pytest.approx(avg, abs=4 * se), g
    assert ds["values"].min() >= 901.0 and ds["values"].max() <= 50 * 2098.99
    assert ds["values"].dtype == np.float32 and ds["group"].dtype == np.int32


def test_same_seed_same_data():
    gen = load_module(ROOT, "kinds", "lineitem").generate
    cfg = _config("tpch-lineitem-sf30", blocks=2, records_per_block=1024)
    a, b, c = gen(cfg, 3), gen(cfg, 3), gen(cfg, 4)
    assert all(np.array_equal(a[k], b[k]) for k in ("values", "group",
                                                     "select"))
    assert not np.array_equal(a["values"], c["values"])
