"""Serving engine: generation correctness + DV-DVFS window accounting."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.core import RooflineTimeModel
from repro.models import transformer as T
from repro.serve import ServeConfig, ServingEngine


def _engine(planner="global", window=8, mem_bound=True, **sc_kw):
    cfg = smoke_config("olmo-1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rt = RooflineTimeModel.from_counts(
        flops=1e9, hbm_bytes=8e9 if mem_bound else 1e6, coll_bytes=0)
    sc_kw.setdefault("slack", 1.15)
    eng = ServingEngine(cfg, params,
                        ServeConfig(batch=2, max_len=128, window=window,
                                    planner=planner, **sc_kw),
                        roofline=rt)
    prompts = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab, (2, 16)), jnp.int32)}
    return eng, prompts


def test_generate_shapes_and_determinism():
    eng, prompts = _engine()
    out = eng.generate(prompts, n_tokens=24)
    assert out["tokens"].shape[0] == 2
    assert out["n_generated"] >= 24
    # greedy decoding from the same params/prompts is deterministic
    eng2, prompts2 = _engine()
    out2 = eng2.generate(prompts2, n_tokens=24)
    np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                  np.asarray(out2["tokens"]))


def test_memory_bound_decode_gets_free_downclock():
    """Global planner on a memory-bound decode roofline: energy drops,
    clocks < 1."""
    eng, prompts = _engine(mem_bound=True)
    out = eng.generate(prompts, n_tokens=32)
    assert out["energy"]["busy_j"] < out["energy_dvo"]["busy_j"]
    assert any(f < 1.0 for f in eng.actuator.history)


def test_compute_bound_decode_stays_fast():
    """Compute-bound roofline + tight slack: little room to down-clock."""
    eng, prompts = _engine(mem_bound=False)
    out = eng.generate(prompts, n_tokens=32)
    # still never worse than DVO
    assert out["energy"]["busy_j"] <= out["energy_dvo"]["busy_j"] * 1.01


def test_short_generation_no_windows():
    """All tokens inside the calibration window: ledgers match DVO exactly."""
    eng, prompts = _engine(window=16)
    out = eng.generate(prompts, n_tokens=8)
    assert out["energy"]["busy_j"] == out["energy_dvo"]["busy_j"]


def test_multi_replica_decode_windows():
    """3 heterogeneous replicas under a shared SLO: the cluster planner pins
    windows to their replica, slow hosts clock higher than fast ones, and
    the aggregate still beats DVO.  Tokens are unchanged vs single-replica
    (replica 0 decodes physically either way)."""
    eng, prompts = _engine(replicas=3, replica_speeds=(1.0, 0.8, 1.25),
                           slack=1.4)
    out = eng.generate(prompts, n_tokens=32)
    single, prompts1 = _engine(slack=1.4)
    out1 = single.generate(prompts1, n_tokens=32)
    np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                  np.asarray(out1["tokens"]))

    cp = eng.cluster_plan
    assert cp is not None and cp.feasible
    # every window is pinned to its own replica
    n_windows = len(cp.node_plans[0].blocks)
    for r, np_ in enumerate(cp.node_plans):
        assert len(np_.blocks) == n_windows
        assert all(r * n_windows <= bp.index < (r + 1) * n_windows
                   for bp in np_.blocks)
    # slowest host needs the highest clocks (same work, same deadline)
    mean_freq = [np.mean([bp.rel_freq for bp in p.blocks])
                 for p in cp.node_plans]
    assert mean_freq[1] >= mean_freq[2]
    # aggregate across replicas still saves energy vs all-f_max
    assert out["energy"]["busy_j"] <= out["energy_dvo"]["busy_j"] * 1.01
    assert out["energy"]["steps"] > out1["energy"]["steps"]


def test_launch_serve_smoke_preset(monkeypatch, capsys):
    """The serving entry point runs its smoke preset's request shape; only
    batch and token count may be overridden."""
    from repro.launch import serve
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "off")
    serve.main(["--arch", "olmo-1b", "--batch", "1", "--tokens", "9"])
    out = capsys.readouterr().out
    assert "preset=smoke generated=" in out
    assert int(out.split("generated=")[1].split()[0]) >= 9
