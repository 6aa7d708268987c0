"""Sampling estimator: CI coverage, overhead contract, cost-model calibration."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (CostModel, RooflineTimeModel, required_sample_size,
                        sample_block_cost)


def test_estimate_close_to_truth():
    rng = np.random.default_rng(0)
    costs = rng.lognormal(0.0, 0.5, 20000)
    est = sample_block_cost(costs, fraction=0.05, seed=1)
    assert abs(est.total - costs.sum()) / costs.sum() < 0.05
    assert est.ci_low <= est.total <= est.ci_high
    assert est.n_sampled <= max(16, int(np.ceil(0.05 * len(costs))))


def test_ci_coverage_over_many_blocks():
    """~95% of bootstrap CIs should contain the truth (allow slack: >=80%)."""
    rng = np.random.default_rng(42)
    hits = 0
    trials = 60
    for t in range(trials):
        costs = rng.lognormal(0.0, 0.6, 4000)
        est = sample_block_cost(costs, fraction=0.08, seed=t, n_boot=200)
        hits += est.ci_low <= costs.sum() <= est.ci_high
    assert hits / trials >= 0.8


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 10_000))
def test_sampling_never_exceeds_block(n):
    costs = np.ones(n)
    est = sample_block_cost(costs, fraction=0.05)
    assert est.n_sampled <= n
    assert est.n_records == n
    assert est.total == pytest.approx(n)


def test_required_sample_size_matches_paper_contract():
    """CoV=1, 5% error, 95% conf -> n ≈ (1.96/0.05)^2 ≈ 1537 records; for a
    100k-record block that is ~1.5% — same order as the paper's <1% overhead."""
    n = required_sample_size(cov=1.0, rel_err=0.05, confidence=0.95)
    assert 1400 < n < 1700


def test_cost_model_recovers_linear_costs():
    rng = np.random.default_rng(3)
    feats = [{"tokens": float(t), "const": 1.0}
             for t in rng.integers(1000, 100000, 50)]
    secs = [2e-6 * f["tokens"] + 0.3 for f in feats]
    m = CostModel(("tokens", "const")).fit(feats, secs)
    pred = m.predict({"tokens": 50000.0, "const": 1.0})
    assert pred == pytest.approx(2e-6 * 50000 + 0.3, rel=1e-6)


def test_roofline_time_model_terms():
    rt = RooflineTimeModel.from_counts(flops=197e12, hbm_bytes=819e9,
                                       coll_bytes=0, chips=1)
    # exactly 1 second of compute and 1 second of memory
    assert rt.terms.t_comp == pytest.approx(1.0)
    assert rt.terms.t_mem == pytest.approx(1.0)
    assert rt.time_at(1.0) == pytest.approx(1.0)
    assert rt.time_at(0.5) == pytest.approx(2.0)   # compute-bound below f*
    assert rt.zero_cost_freq() == pytest.approx(1.0)


# --- degenerate-input guards (the streamed pipeline feeds these raw) --------

def test_zero_variance_block_has_exact_zero_width_ci():
    costs = np.full(500, 3.25)
    est = sample_block_cost(costs, fraction=0.05, seed=0)
    assert est.total == pytest.approx(costs.sum())
    assert est.ci_low == est.total == est.ci_high
    assert est.rel_halfwidth == 0.0


def test_single_record_block_never_nan():
    est = sample_block_cost(np.asarray([7.5]), fraction=0.05, seed=0)
    assert est.n_sampled == 1 and est.n_records == 1
    assert est.total == 7.5
    assert np.isfinite([est.ci_low, est.ci_high]).all()
    assert est.rel_halfwidth == 0.0


def test_min_samples_zero_still_samples_at_least_one_record():
    """min_samples=0 with a tiny fraction used to produce an empty sample
    (NaN mean); the k >= 1 guard keeps the estimate finite."""
    est = sample_block_cost(np.ones(10), fraction=1e-9, min_samples=0, seed=0)
    assert est.n_sampled == 1
    assert np.isfinite(est.total)


def test_n_boot_must_be_positive():
    with pytest.raises(ValueError):
        sample_block_cost(np.ones(10), n_boot=0)


def test_required_sample_size_degenerate_inputs():
    assert required_sample_size(cov=0.0) == 1  # zero variance: one record
    with pytest.raises(ValueError):
        required_sample_size(cov=-0.5)
    with pytest.raises(ValueError):
        required_sample_size(cov=float("nan"))
    with pytest.raises(ValueError):
        required_sample_size(cov=1.0, rel_err=0.0)
    with pytest.raises(ValueError):
        required_sample_size(cov=1.0, confidence=1.0)


def test_sample_blocks_soa_degenerate_blocks():
    from repro.core import sample_blocks_soa
    # zero-variance, single-record, and empty blocks packed in one ragged
    # chunk: no NaN anywhere, zero-width CI where variance is zero
    costs = np.zeros((3, 400))
    costs[0] = 2.0          # zero variance
    costs[1, 0] = 9.0       # single record
    lengths = np.asarray([400, 1, 0])
    est = sample_blocks_soa(costs, lengths, seed=1)
    assert np.isfinite(est.total).all()
    assert np.isfinite(est.ci_low).all() and np.isfinite(est.ci_high).all()
    assert est.total[0] == pytest.approx(800.0)
    assert est.ci_low[0] == est.total[0] == est.ci_high[0]
    assert est.total[1] == 9.0 and est.n_sampled[1] == 1
    assert est.total[2] == 0.0 and est.n_sampled[2] == 0
    assert np.all(est.rel_halfwidth >= 0.0)


def _whole_array_sample(seed, index, n, k, r):
    """Each block's k smallest-key slots, ascending, as the sampler picks
    them from the whole (b, r) key array: ``_hash_uniform``, then
    ``argpartition``."""
    from repro.core.sampling import _DOMAIN_SAMPLER, _hash_uniform

    keys = _hash_uniform(seed, index[:, None], np.arange(r)[None, :],
                         domain=_DOMAIN_SAMPLER)
    keys = np.where(np.arange(r)[None, :] < n[:, None], keys, np.inf)
    return [np.sort(np.argpartition(keys[j], k[j] - 1)[:k[j]]) if k[j]
            else np.zeros(0, dtype=np.int64) for j in range(len(n))]


# (b, r, lengths, fraction, min_samples, _TAU_SIGMAS, refills a block)
_SAMPLE_SET_CASES = {
    "uniform blocks": (3, 1 << 17, None, 0.05, 16, 6.0, 0),
    "ragged, empty and one-record blocks":
        (5, 1 << 17, [1 << 17, 70_000, 0, 1, 100_001], 0.05, 16, 6.0, 0),
    "k == n": (3, 5000, [5000, 17, 1], 1.0, 16, 6.0, 0),
    "min_samples above fraction n": (2, 1 << 17, None, 0.001, 2000, 6.0, 0),
    "r below one tile": (4, 1000, None, 0.05, 16, 6.0, 0),
    "bound too low: a refill": (2, 1 << 17, None, 0.05, 16, -6.0, 1),
}


@pytest.mark.parametrize("case", list(_SAMPLE_SET_CASES))
def test_streamed_selection_is_the_whole_array_sample(case, monkeypatch):
    """The tiled, key-bound selection picks each block's slots as hashing
    and partitioning the whole (b, r) key array does, and a bound that
    keeps too few is refilled.  ``sample_blocks_soa`` then matches the
    whole-array arithmetic within 1e-12, however the blocks are chunked."""
    from repro.core import sampling
    from repro.core.soa import EstimateArrays

    b, r, lengths, fraction, min_samples, sigmas, refills = \
        _SAMPLE_SET_CASES[case]
    monkeypatch.setattr(sampling, "_TAU_SIGMAS", sigmas)
    seed, start = 2**31 + 19, 7
    index = start + np.arange(b)
    n = np.full(b, r) if lengths is None else np.asarray(lengths)
    k = np.minimum(n, np.maximum(min_samples,
                                 np.ceil(fraction * n).astype(np.int64)))
    want = _whole_array_sample(seed, index, n, k, r)

    found, refilled = sampling._bounded_candidates(seed, index, n, k)
    assert refilled == refills * int(np.count_nonzero(k))
    for j, (slots, h) in enumerate(found):
        assert np.all(slots < n[j]) and len(slots) >= k[j]
    sel = sampling._smallest(found, k)
    for j in range(b):
        assert np.array_equal(sel[j, :k[j]], want[j])

    costs = np.random.default_rng(5).lognormal(0.0, 0.7, (b, r))
    est = sampling.sample_blocks_soa(costs, lengths, fraction=fraction,
                                     min_samples=min_samples, seed=seed,
                                     start_index=start)
    assert np.array_equal(est.n_sampled, k)
    one_a_chunk = [sampling.sample_blocks_soa(
        costs[j:j + 1], None if lengths is None else n[j:j + 1],
        fraction=fraction, min_samples=min_samples, seed=seed,
        start_index=start + j) for j in range(b)]
    with monkeypatch.context() as whole:   # every block below one tile
        whole.setattr(sampling, "_TILE", 1 << 30)
        ref = sampling.sample_blocks_soa(costs, lengths, fraction=fraction,
                                         min_samples=min_samples, seed=seed,
                                         start_index=start)
    # a ragged chunk's masked sums run over its widest sample, so only the
    # order of summation depends on the chunk
    for name in ("total", "ci_low", "ci_high"):
        got = getattr(est, name)
        for other in (ref, EstimateArrays.concat(one_a_chunk)):
            np.testing.assert_allclose(getattr(other, name), got, rtol=1e-12,
                                       atol=0)
