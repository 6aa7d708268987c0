"""Bring-up check: the DV-DVFS main path, once, on one TPU chip.

  python chip_smoke.py                   # needs a TPU; fails on anything else
  python chip_smoke.py --cpu-rehearsal   # tiny sizes, Pallas interpreter, CPU

Everything runs in this one process, which holds the chip throughout; no
child process is started.  Phases:

  0  device and JAX's persistent compile cache (``repro.launch.compile_cache``).
  1  the paper's pipeline over HDFS-default 128 MiB blocks (131,072 records
     × 256 int32 tokens: the unit Hadoop MapReduce gives one map task):
     the estimate kernel through ``BlockDataset.stats_soa`` and
     ``stream_estimates_tokens`` (Mosaic-compiled, checked against
     ``block_stats_batched_ref``), the five apps measured per block and
     checked against NumPy oracles, a DV-DVFS plan against DVO simulated on
     the measured times, and the streamed plan run over a 4-node fleet.
  2  the LM path at olmo-1b's published widths: a few ``Trainer`` steps at a
     2048-token context with the depth cut to fit one chip, then
     ``ServingEngine.generate`` at full depth with the Pallas flash-attention
     prefill (the request shape of ``repro.launch.serve --preset full``),
     checked against a plain decode loop and ``T.forward``.

Every check raises on a mismatch, so a failed phase ends the run with a
non-zero exit.  Energies are from the power model (*modelled*), not
measured.  All data and weights come from ``--seed``.  The last stdout line
is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch, smoke_config  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (PRESETS, ServePreset, build_engine,  # noqa: E402
                                make_prompts)

GIB = 2 ** 30


@dataclasses.dataclass(frozen=True)
class Sizes:
    records: int          # records per token block
    max_len: int          # tokens per record (int32)
    stat_blocks: int      # blocks in the full-block stats chunk
    app_blocks: int       # blocks per app for the measured plan
    agg_records: int      # records per AVG/SUM block
    nodes: int            # simulated fleet of the streamed run
    arch: ArchConfig
    train_layers: int
    train_batch: int
    seq_len: int
    train_steps: int
    serve: ServePreset    # the serving request shape


CHIP = Sizes(records=131072, max_len=256, stat_blocks=8, app_blocks=4,
             agg_records=1 << 24, nodes=4, arch=get_arch("olmo-1b"),
             train_layers=8, train_batch=1, seq_len=2048, train_steps=5,
             serve=PRESETS["full"])
REHEARSAL = Sizes(records=256, max_len=64, stat_blocks=3, app_blocks=3,
                  agg_records=4096, nodes=4, arch=smoke_config("olmo-1b"),
                  train_layers=1, train_batch=2, seq_len=64, train_steps=5,
                  serve=ServePreset(batch=2, prompt_len=16, tokens=13,
                                    window=4, attn_impl="pallas"))

# Tolerances, with why:
# - token mass is a float32 sum of ~3.4e7 ids per block, accumulated in a
#   different order from the reference's: relative differences of a few
#   float32 ulps times log(n) are expected;
MASS_RTOL = 1e-5
# - AVG/SUM scatter ~2^21 float32 values into each group bucket; against a
#   float64 oracle the accumulation error is a random walk of that length;
AGG_RTOL = 1e-3
# - the prefill logits come from the Pallas kernel and the reference from
#   XLA's chunked attention; both round f32 matmul operands to bf16 on the
#   TPU (8-bit mantissa) at different points, through 16 layers.
LOGIT_TOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported by this backend"
    return f"{stats['peak_bytes_in_use']} B ({stats['peak_bytes_in_use'] / GIB:.3f} GiB)"


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def has_mosaic_kernel(jitted, *args, **kwargs) -> bool:
    return "tpu_custom_call" in jitted.lower(*args, **kwargs).compile().as_text()


# ----------------------------------------------------------------- phase 1 --

def phase_estimate(ds, toks, sz: Sizes, seed: int, on_tpu: bool):
    """The estimate kernel through ``stats_soa`` and the streamed sampler."""
    from repro.kernels import ops, ref
    from repro.pipeline import PipelineConfig, stream_estimates_tokens
    from repro.pipeline.stream import (DEFAULT_TOKEN_COST_WEIGHTS,
                                       sample_token_rows)

    pattern = ds.grep_pattern
    shape = jax.ShapeDtypeStruct(toks.shape, jnp.int32)
    if on_tpu:
        check(has_mosaic_kernel(ops.block_stats_batched, shape,
                                pattern=pattern),
              "block_stats_batched did not compile to a Mosaic kernel")
        log("[phase1] block_stats_batched compiled as Mosaic "
            "(tpu_custom_call in the compiled text)")

    t0 = time.perf_counter()
    soa = ds.stats_soa(chunk_size=sz.stat_blocks)
    soa_s = time.perf_counter() - t0
    dev_toks = jax.device_put(toks)
    jax.block_until_ready(ops.block_stats_batched(dev_toks, pattern=pattern))
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(ops.block_stats_batched(dev_toks,
                                                      pattern=pattern))
        walls.append(time.perf_counter() - t0)
    kernel_s = float(np.median(walls))
    del dev_toks
    log(f"[phase1] stats_soa over {sz.stat_blocks} blocks "
        f"({toks.nbytes / GIB:.3f} GiB in one chunk): {soa_s:.3f} s with "
        f"block generation; kernel alone {kernel_s * 1e3:.3f} ms median of 3 "
        f"(host clock to block_until_ready) = "
        f"{toks.nbytes / kernel_s / 1e9:.1f} GB/s")

    # the kernel against the plain-jnp reference on two full blocks
    want = np.asarray(ref.block_stats_batched_ref(jnp.asarray(toks[:2]), None,
                                                  pattern))
    exact = np.array([(toks[b] != 0).sum() for b in range(2)])
    for b in range(2):
        check(soa["tokens"][b] == int(want[b, 0])
              and soa["matches"][b] == int(want[b, 1]),
              f"block {b}: kernel counts {soa['tokens'][b]}, "
              f"{soa['matches'][b]} != reference {want[b, :2]}")
        check(soa["tokens"][b] == int(np.float32(exact[b])),
              f"block {b}: nonpad {soa['tokens'][b]} is not float32 of the "
              f"exact count {exact[b]}")
    mass_err = float(np.max(np.abs(soa["mass"][:2] - want[:, 2])
                            / want[:, 2]))
    check(mass_err <= MASS_RTOL, f"token mass rel err {mass_err:.3e}")
    log(f"[phase1] stats vs block_stats_batched_ref on blocks 0-1: nonpad "
        f"{soa['tokens'][:2].tolist()} (exact int {exact.tolist()}), matches "
        f"{soa['matches'][:2].tolist()} equal; mass rel err {mass_err:.3e} "
        f"(bound {MASS_RTOL:g})")

    cfg = PipelineConfig(chunk_size=sz.stat_blocks, seed=seed)
    sampled, k = sample_token_rows(toks, start_index=0, config=cfg)
    if on_tpu:
        check(has_mosaic_kernel(
            ops.block_stats_batched,
            jax.ShapeDtypeStruct(sampled.shape, jnp.int32),
            jax.ShapeDtypeStruct(k.shape, jnp.int32), pattern=pattern),
            "sampled-shape block_stats_batched is not a Mosaic kernel")
    t0 = time.perf_counter()
    est = stream_estimates_tokens([(0, toks)], cfg, pattern=pattern)
    est_s = time.perf_counter() - t0
    w = np.asarray(DEFAULT_TOKEN_COST_WEIGHTS)
    want_s = np.asarray(ref.block_stats_batched_ref(
        jnp.asarray(sampled[:2]), k[:2], pattern), np.float64)
    want_total = want_s @ w / k[:2] * sz.records
    est_err = float(np.max(np.abs(est.total[:2] - want_total) / want_total))
    check(est_err <= MASS_RTOL and np.all(np.isfinite(est.total))
          and np.all(est.ci_low <= est.total)
          and np.all(est.total <= est.ci_high),
          f"stream_estimates_tokens disagrees: rel err {est_err:.3e}")
    log(f"[phase1] stream_estimates_tokens: {sampled.shape[1]} sampled rows "
        f"per block, {est_s:.3f} s; estimate vs reference on blocks 0-1 "
        f"rel err {est_err:.3e}")
    return est


def _oracle_checks(name: str, block: dict, out) -> str:
    """One block of ``name`` against the NumPy oracles of the app tests."""
    if name == "wordcount":
        toks = block["tokens"]
        want = np.bincount(toks[toks != 0], minlength=len(out))
        check(np.array_equal(np.asarray(out)[1:], want[1:len(out)]),
              "wordcount counts differ")
        return f"{int(want[1:].sum())} tokens counted exactly"
    if name == "grep":
        from repro.apps import Grep
        toks, p = block["tokens"], Grep().pattern
        n_win = toks.shape[1] - len(p) + 1
        win = np.ones((toks.shape[0], n_win), bool)
        for j, pj in enumerate(p):
            win &= toks[:, j:n_win + j] == pj
        check(int(out["total"]) == int(win.sum())
              and np.array_equal(np.asarray(out["per_record"]),
                                 win.sum(axis=1)),
              "grep matches differ")
        return f"{int(win.sum())} matches exact"
    if name == "inverted_index":
        toks = block["tokens"]
        offsets = np.asarray(out["offsets"])
        counts = np.bincount(toks.ravel(), minlength=len(offsets) - 1)
        check(np.array_equal(np.diff(offsets)[1:], counts[1:len(offsets) - 1])
              and int(out["n_valid"]) == int((toks != 0).sum()),
              "inverted index offsets differ")
        sorted_tok = np.asarray(out["tokens_sorted"])
        rec, pos = np.asarray(out["record"]), np.asarray(out["position"])
        for t in np.unique(toks[toks != 0])[:10]:
            lo, hi = offsets[t], offsets[t + 1]
            r, c = np.nonzero(toks == t)
            check(np.all(sorted_tok[lo:hi] == t)
                  and np.array_equal(rec[lo:hi], r)
                  and np.array_equal(pos[lo:hi], c),
                  f"postings of token {t} differ")
        return "offsets of every token and postings of 10 tokens exact"
    v, g, s = block["values"], block["group"], block["select"]
    sums = np.array([v[(g == gi) & s].astype(np.float64).sum()
                     for gi in range(8)])
    want = sums if name == "sum" else sums / np.maximum(
        [((g == gi) & s).sum() for gi in range(8)], 1)
    err = float(np.max(np.abs(np.asarray(out, np.float64) - want)
                       / np.abs(want)))
    check(err <= AGG_RTOL, f"{name} rel err {err:.3e}")
    return f"per-group rel err {err:.3e} vs float64 (bound {AGG_RTOL:g})"


def phase_apps(toks, sz: Sizes, seed: int):
    """The five apps on full blocks, checked, then planned as the paper."""
    from benchmarks.paper_figs import compare_on_measured, measure_blocks
    from repro.apps import ALL_APPS
    from repro.core import TPU_V5E_POWER
    from repro.data import BlockDataset

    token_blocks = [{"tokens": toks[i]} for i in range(sz.app_blocks)]
    nds = BlockDataset(n_blocks=sz.app_blocks, records_per_block=sz.agg_records,
                       max_len=8, seed=seed)
    agg_blocks = [nds.block(i, with_tokens=False)
                  for i in range(sz.app_blocks)]
    for name, app_cls in ALL_APPS.items():
        blocks = agg_blocks if name in ("avg", "sum") else token_blocks
        b0 = blocks[0]
        out = jax.jit(app_cls().run)({k: jnp.asarray(v) for k, v in b0.items()})
        note = _oracle_checks(name, b0, jax.device_get(out))
        t0 = time.perf_counter()
        times, t_sub = measure_blocks(name, blocks, sample_fraction=0.05,
                                      seed=seed)
        meas_s = time.perf_counter() - t0
        res = compare_on_measured(times, t_sub, slack=1.20, planner="paper",
                                  power=TPU_V5E_POWER)
        check(res["deadline_met"] and np.all(np.isfinite(times)),
              f"{name}: planned run missed its deadline")
        log(f"[phase1] {name}: oracle ok ({note}); f_max block times "
            f"{[f'{t * 1e3:.3f} ms' for t in times]} (5% sample "
            f"{[f'{t * 1e3:.3f} ms' for t in t_sub]}), measured in "
            f"{meas_s:.1f} s; DV-DVFS deadline met={res['deadline_met']}, "
            f"est MAPE {res['est_mape']:.3f}; modelled energy "
            f"{res['dvfs_energy_j']:.6g} J vs DVO {res['dvo_energy_j']:.6g} J "
            f"({res['energy_improvement']:+.2%})")


def phase_stream_run(est, sz: Sizes):
    """The streamed estimate -> cluster plan -> event-driven run."""
    from repro.cluster import NodeSpec, plan_cluster_arrays
    from repro.runtime import RuntimeConfig, run_cluster

    speeds = (1.0, 1.0, 0.8, 1.25, 1.0, 0.9, 1.1, 1.0)[:sz.nodes]
    nodes = [NodeSpec(f"node{i}", speed=s) for i, s in enumerate(speeds)]
    deadline = float(est.total.sum()) / sum(speeds) * 1.5
    ba = est.to_block_arrays()
    rep = run_cluster(plan_cluster_arrays(ba, nodes, deadline), ba,
                      config=RuntimeConfig(log_events=False))
    check(rep.deadline_met and not rep.missed_blocks,
          "streamed cluster run missed its deadline")
    log(f"[phase1] stream_run over {len(nodes)} simulated nodes: "
        f"{len(est)} blocks, makespan {rep.makespan_s:.6g} s of deadline "
        f"{deadline:.6g} s (model seconds), deadline met; modelled busy "
        f"energy {rep.total_energy_j:.6g} J")


def phase_pipeline(sz: Sizes, seed: int, on_tpu: bool):
    from repro.data import BlockDataset

    t0 = time.perf_counter()
    ds = BlockDataset(n_blocks=sz.stat_blocks, records_per_block=sz.records,
                      max_len=sz.max_len, seed=seed)
    (_, toks), = ds.iter_token_chunks(sz.stat_blocks)
    log(f"[phase1] data: {sz.stat_blocks} blocks x {sz.records} records x "
        f"{sz.max_len} int32 = {toks[0].nbytes / 2 ** 20:.1f} MiB a block, "
        f"generated in {time.perf_counter() - t0:.1f} s")
    est = phase_estimate(ds, toks, sz, seed, on_tpu)
    phase_apps(toks, sz, seed)
    phase_stream_run(est, sz)


# ----------------------------------------------------------------- phase 2 --

def phase_train(sz: Sizes, seed: int, ckpt_dir: Path):
    from repro.data import BlockDataset
    from repro.train import TrainConfig, Trainer

    cfg = sz.arch.replace(n_layers=sz.train_layers)
    n = cfg.param_count()
    log(f"[phase2] train {cfg.name}: depth cut {sz.arch.n_layers} -> "
        f"{sz.train_layers} layers at d_model {cfg.d_model}, {cfg.n_heads} x "
        f"{cfg.d_head} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab}: "
        f"{n / 1e9:.3f} B params, {16 * n / 1e9:.2f} GB of f32 params, grads "
        f"and Adam moments; batch {sz.train_batch} x {sz.seq_len} tokens")
    tc = TrainConfig(batch=sz.train_batch, seq_len=sz.seq_len,
                     total_steps=sz.train_steps, warmup=2,
                     ckpt_every=sz.train_steps, ckpt_dir=str(ckpt_dir),
                     seed=seed)
    ds = BlockDataset(n_blocks=sz.train_steps, records_per_block=128,
                      max_len=256, vocab=cfg.vocab, seed=seed)
    t0 = time.perf_counter()
    res = Trainer(cfg, tc, dataset=ds).run(resume=False)
    wall = time.perf_counter() - t0
    hist = res["history"]
    losses = [h["loss"] for h in hist]
    check(len(losses) == sz.train_steps and np.all(np.isfinite(losses)),
          f"train losses not finite: {losses}")
    steps_s = sum(h["wall_s"] for h in hist)
    log(f"[phase2] train: losses {[f'{x:.4f}' for x in losses]} all finite; "
        f"step walls {[f'{h['wall_s'] * 1e3:.1f} ms' for h in hist]}, rel "
        f"freq {[h['rel_freq'] for h in hist]}; run() {wall:.1f} s of which "
        f"steps {steps_s:.1f} s (the rest: compile, data, final checkpoint)")
    del res
    gc.collect()
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    log(f"[phase2] peak_bytes_in_use after train: {peak_bytes()}")


def phase_serve(sz: Sizes, seed: int):
    from repro.models import transformer as T
    from repro.models.common import apply_norm

    sv = sz.serve
    cfg = sz.arch.replace(attn_impl_train=sv.attn_impl)
    n_tokens = sv.tokens
    max_len = sv.prompt_len + n_tokens
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = make_prompts(cfg, sv.batch, sv.prompt_len, seed)
    log(f"[phase2] serve {cfg.name}: {cfg.n_layers} layers, "
        f"{cfg.param_count() * 4 / 1e9:.2f} GB f32 weights; batch "
        f"{sv.batch} x {sv.prompt_len}-token prompts, {n_tokens} new "
        f"tokens in {sv.window}-token windows; prefill attention: "
        f"{sv.attn_impl}")
    eng = build_engine(cfg, params, batch=sv.batch, max_len=max_len,
                       window=sv.window)
    t0 = time.perf_counter()
    out = eng.generate(prompts, n_tokens=n_tokens)
    wall = time.perf_counter() - t0
    tokens = np.asarray(out["tokens"])
    check(tokens.shape == (sv.batch, 1 + n_tokens),
          f"generated shape {tokens.shape}")

    # first window's greedy tokens vs a plain loop of decode_step
    prefill = jax.jit(T.prefill, static_argnums=(1, 3))
    step = jax.jit(T.decode_step, static_argnums=1)
    logits, cache = prefill(params, cfg, prompts, max_len)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    plain = [tok]
    for _ in range(1 + sv.window):
        step_logits, cache = step(params, cfg, tok, cache)
        tok = jnp.argmax(step_logits, -1).astype(jnp.int32)[:, None]
        plain.append(tok)
    plain = np.asarray(jnp.concatenate(plain, axis=1))
    n_cmp = plain.shape[1]
    check(np.array_equal(tokens[:, :n_cmp], plain),
          "engine tokens differ from the plain decode loop")
    del cache

    # prefill logits (Pallas flash attention) vs T.forward (chunked)
    ref_cfg = cfg.replace(attn_impl_train="chunked")

    def last_logits(p, batch):
        hidden, _ = T.forward(p, ref_cfg, batch)
        return apply_norm(cfg.norm, p["final_norm"], hidden[:, -1]) \
            @ p["lm_head"]

    want = jax.jit(last_logits)(params, prompts)
    err = float(jnp.max(jnp.abs(logits - want)) / jnp.max(jnp.abs(want)))
    check(err <= LOGIT_TOL, f"prefill logits err {err:.3e}")
    log(f"[phase2] serve: generate() {wall:.1f} s with compiles; first "
        f"{n_cmp} tokens of all {sv.batch} sequences equal the plain "
        f"decode loop; prefill logits vs T.forward(chunked) max abs err / "
        f"max |logit| = {err:.3e} (bound {LOGIT_TOL:g}); modelled energy "
        f"{out['energy']['busy_j']:.6g} J vs DVO "
        f"{out['energy_dvo']['busy_j']:.6g} J over "
        f"{out['energy']['time_s'] * 1e3:.3f} ms of timed windows")
    log(f"[phase2] peak_bytes_in_use after serve: {peak_bytes()}")


# -------------------------------------------------------------------- main --

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU, Pallas kernels interpreted")
    ap.add_argument("--ckpt-dir", type=Path, default=ROOT / ".smoke_ckpt",
                    help="trainer checkpoints (removed after the phase)")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if args.cpu_rehearsal:
        if dev.platform != "cpu":
            print(f"--cpu-rehearsal needs the CPU backend, found "
                  f"{dev.platform!r}", file=sys.stderr)
            return 2
    elif dev.platform != "tpu":
        print(f"no TPU: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}); pass --cpu-rehearsal for the tiny CPU "
              "rehearsal", file=sys.stderr)
        return 1
    on_tpu = dev.platform == "tpu"
    sz = CHIP if on_tpu else REHEARSAL
    cache = enable_compile_cache()
    log(f"[phase0] device {dev.platform} {dev.device_kind} x "
        f"{len(jax.devices())}; compile cache {cache}")

    t0 = time.perf_counter()
    phase_pipeline(sz, args.seed, on_tpu)
    log(f"[phase1] wall {time.perf_counter() - t0:.1f} s; peak_bytes_in_use "
        f"{peak_bytes()}")
    gc.collect()

    t0 = time.perf_counter()
    phase_train(sz, args.seed, args.ckpt_dir)
    phase_serve(sz, args.seed)
    log(f"[phase2] wall {time.perf_counter() - t0:.1f} s")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
