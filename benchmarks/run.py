"""Benchmark harness — one section per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract).  Sections:
  table1   — motivation: per-block time variety (mean/var/CoV) per app
  fig6-10  — energy & time, DV-DVFS vs DVO, 5 apps (paper-faithful CPU power
             model AND the TPU-adapted model), firm deadline
  fig11-12 — Zipf sensitivity z ∈ {0,1,2}
  fig13    — tight vs firm deadline
  planners — paper vs global planner on the same workload
  planner_scale — vectorized planning/sampling hot path at 100 .. 100k
             blocks: blocks/sec per planner, speedup vs the loop reference
             at 10k, plan-equivalence asserts at small n, batched sampler
             and batched block-stats kernel throughput
  pipeline — streamed SoA dataset→plan path (repro.pipeline): end-to-end
             blocks/sec and peak RSS at 10k → 1M blocks (quick: → 100k),
             per-stage timing breakdown, tight-vs-ample planner ratio,
             equivalence asserts vs the object path, token-kernel and
             cluster SoA rows
  cluster  — multi-node planner vs per-node independent Algorithm 1 on
             heterogeneous nodes, plus online re-planning under a mid-run
             slowdown (datasets × apps × node counts × deadline tightness)
  runtime  — event-driven cluster runtime (repro.runtime) scenario grid:
             faults × migration on/off × power-cap levels × deadline
             tightness, with a 10k-block fault+migration+cap smoke row;
             asserts migration recovers a deadline f_max alone misses and
             the cap trades deadline slack for lower peak power
  calibrate — telemetry-driven calibration (repro.calibrate): fit
             round-trip across trace noise × length (asserted tolerances),
             calibrated-vs-default planning across ground-truth model
             perturbation (asserts dominance at ≥10% deviation), online
             recalibration determinism, 10k-block loop smoke
  failures — failure-tolerant runtime (repro.runtime.failures/recovery):
             seeded chaos campaign (zero conservation violations, scalar=
             vector), recovery grid (crash time × MTTR × slack; recovery
             meets deadlines the migration-only baseline misses and never
             strands a block), zero-failure identity row
  engine   — vectorized vs scalar event engine on the everything-on fleet
             scenario: identical-report assert + blocks/sec per engine
  serving  — open-loop serving fabric (repro.serving): admission/shedding
             campaign grid, miss-rate bound, conservation asserts
  obs      — observability layer (repro.obs): inline streaming-metrics
             overhead, span-build and Chrome-export throughput
  obs_cf   — counterfactual layer: per-mechanism ablation replays on BOTH
             engines with bitwise Δ-ledger reconciliation, the DVFS-off
             paper-headline assert, watchdog alert-stream identity
             (scalar vs vector and run-to-run), run-diff self-check
  roofline — summary of results/roofline_sp.json (built from the dry-run)
  train    — tiny end-to-end LM training with the DV-DVFS controller
  serve    — batched decode with roofline-planned windows

Usage: PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# bumped whenever row shapes / section semantics change incompatibly;
# benchmarks.compare refuses to diff blobs whose schemas differ
SCHEMA_VERSION = 6


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def _row(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")
    sys.stdout.flush()


def bench_table1():
    from benchmarks.paper_figs import motivation_table
    tab = motivation_table()
    for app, row in tab.items():
        _row(f"table1_{app}", row["mean_ms"] * 1e3,
             f"cov={row['cov']:.3f};var={row['variance']:.3f}")
    return tab


def bench_fig6_10():
    from repro.core import CPU_PAPER_POWER, TPU_V5E_POWER

    from benchmarks.paper_figs import fig6_10
    out = {}
    for tag, power in (("paper_cpu", CPU_PAPER_POWER), ("tpu", TPU_V5E_POWER)):
        rows = fig6_10(power=power)
        out[tag] = rows
        for r in rows:
            _row(f"fig6_10_{tag}_{r['app']}", r["dvo_time_s"] * 1e6 / 12,
                 f"energy=-{r['energy_improvement']:.1%};"
                 f"time=+{r['time_increase']:.1%};met={r['deadline_met']};"
                 f"est_mape={r['est_mape']:.3f}")
    return out


def bench_fig11_12():
    from benchmarks.paper_figs import run_app_comparison
    rows = []
    for z in (0.0, 1.0, 2.0):
        for app in ("wordcount", "avg"):
            r = run_app_comparison(app, z=z)
            rows.append({"z": z, **r})
            _row(f"fig11_12_z{z:g}_{app}", r["dvo_time_s"] * 1e6 / 12,
                 f"norm_energy={1 - r['energy_improvement']:.3f};"
                 f"norm_time={1 + r['time_increase']:.3f};met={r['deadline_met']}")
    return rows


def bench_fig13():
    from benchmarks.paper_figs import SLACK, run_app_comparison
    rows = []
    for name, slack in SLACK.items():
        for app in ("wordcount", "grep", "inverted_index", "avg", "sum"):
            r = run_app_comparison(app, slack=slack)
            rows.append({"deadline": name, **r})
            _row(f"fig13_{name}_{app}", r["dvo_time_s"] * 1e6 / 12,
                 f"energy=-{r['energy_improvement']:.1%};"
                 f"time=+{r['time_increase']:.1%};met={r['deadline_met']}")
    return rows


def bench_planners():
    """Beyond-paper planners vs the paper planner on one workload."""
    from benchmarks.paper_figs import run_app_comparison
    rows = []
    for planner in ("paper", "global"):
        r = run_app_comparison("wordcount", planner=planner)
        rows.append(r)
        _row(f"planner_{planner}_wordcount", r["dvo_time_s"] * 1e6 / 12,
             f"energy=-{r['energy_improvement']:.1%};met={r['deadline_met']}")
    return rows


def bench_planner_scale(quick: bool = False):
    """Vectorized planning & sampling hot path at scale.

    Rows report planning throughput (blocks/sec; best of 3 — planning is
    deterministic, so min is the honest machine-noise-free figure) for the
    paper and global planners at n_blocks ∈ {100, 1k, 10k, 100k} (quick: up
    to 10k), under ample (1.8x), firm (1.5x) and tight (1.2x) deadlines —
    the three planner regimes (vectorized fast path / sorted scan / heap
    tail).  At n <= 1000 every plan is asserted identical to the loop
    reference (same frequencies, energies within 1e-9); at n = 10k the
    reference is timed on the ample and firm workloads for the speedup
    figures (quick mode skips reference timing and instead guards the
    vectorized wall time).  A sampler row compares ``sample_blocks``
    against the bootstrap-loop reference, and a kernel row compares one
    batched ``block_stats`` dispatch against per-block dispatches.
    """
    import numpy as np

    from repro.core import BlockInfo, plan_dvfs, sample_blocks, zipf_block_sizes
    from repro.core._reference import (plan_dvfs_reference,
                                       sample_blocks_reference)

    def _assert_equivalent(p, q, tag):
        assert p.feasible == q.feasible, tag
        assert len(p.blocks) == len(q.blocks), tag
        for a, b in zip(p.blocks, q.blocks):
            assert a.index == b.index and a.rel_freq == b.rel_freq, (tag, a, b)
            assert abs(a.pred_energy_j - b.pred_energy_j) <= 1e-9, (tag, a, b)

    rows = []
    sizes_n = (100, 1000, 10000) if quick else (100, 1000, 10000, 100000)
    for n in sizes_n:
        sizes = zipf_block_sizes(n, max(10 * n, 10000), z=1.0, seed=0)
        costs = sizes / sizes.mean() * 5.0
        blocks = [BlockInfo(i, float(c)) for i, c in enumerate(costs)]
        total = float(costs.sum())
        for tag, slack in (("ample", 1.8), ("firm", 1.5), ("tight", 1.2)):
            deadline = total * slack
            for planner in ("paper", "global"):
                walls = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    plan = plan_dvfs(blocks, deadline, planner=planner)
                    walls.append(time.perf_counter() - t0)
                wall = min(walls)
                row = {"n": n, "deadline": tag, "planner": planner,
                       "wall_s": wall, "blocks_per_s": n / wall,
                       "feasible": plan.feasible}
                if n <= 1000:
                    ref = plan_dvfs_reference(blocks, deadline,
                                              planner=planner)
                    _assert_equivalent(plan, ref,
                                       (n, tag, planner))
                    row["equivalent"] = True
                if n == 10000 and tag in ("ample", "firm") and not quick:
                    t0 = time.perf_counter()
                    ref = plan_dvfs_reference(blocks, deadline,
                                              planner=planner)
                    ref_wall = time.perf_counter() - t0
                    _assert_equivalent(plan, ref, (n, tag, planner))
                    row["ref_wall_s"] = ref_wall
                    row["speedup"] = ref_wall / wall
                rows.append(row)
                derived = f"blocks_per_s={n / wall:,.0f};feasible={plan.feasible}"
                if "speedup" in row:
                    derived += f";ref_speedup={row['speedup']:.1f}x"
                if "equivalent" in row:
                    derived += ";equiv=ref"
                _row(f"planner_scale_{planner}_{tag}_n{n}", wall * 1e6 / n,
                     derived)

    # batched sampling: vectorized bootstrap vs the 200-iteration loop
    rng = np.random.default_rng(0)
    n_blk = 200 if quick else 1000
    data = [rng.lognormal(0.0, 0.6, 2000) for _ in range(n_blk)]
    t0 = time.perf_counter()
    ests = sample_blocks(data, seed=0)
    vec_wall = time.perf_counter() - t0
    n_ref = min(n_blk, 50)
    t0 = time.perf_counter()
    ref = sample_blocks_reference(data[:n_ref], seed=0)
    ref_wall = (time.perf_counter() - t0) * (n_blk / n_ref)
    assert ests[:n_ref] == ref, "sampler diverged from bootstrap-loop reference"
    rows.append({"sampler_blocks": n_blk, "wall_s": vec_wall,
                 "blocks_per_s": n_blk / vec_wall,
                 "ref_wall_s_extrapolated": ref_wall,
                 "speedup": ref_wall / vec_wall})
    _row("planner_scale_sampler", vec_wall * 1e6 / n_blk,
         f"blocks_per_s={n_blk / vec_wall:,.0f};"
         f"ref_speedup={ref_wall / vec_wall:.1f}x;equiv=ref")

    # batched kernel: one (n_blocks, row_tiles) dispatch vs one per block
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    # nb stays modest: interpret mode re-slices the whole input per grid
    # step (cost grows ~quadratically with n_blocks), which is an artifact
    # of the python interpreter, not the kernel — on TPU the comparison is
    # purely 1 Mosaic dispatch vs nb of them
    nb, r, length = 32, 128, 64
    toks = jnp.asarray(rng.integers(0, 50, (nb, r, length)), jnp.int32)
    # correctness on a ragged dataset (per-block valid-row counts)
    lens = jnp.asarray(rng.integers(1, r + 1, nb), jnp.int32)
    ragged = ops.block_stats_batched(toks, lens)
    per_ragged = jnp.stack([ops.block_stats(toks[b, :int(lens[b])])
                            for b in range(nb)])
    assert bool(jnp.allclose(ragged, per_ragged)), "batched kernel diverged"
    # throughput on uniform blocks (both paths warmed: the comparison is
    # pure dispatch count — 1 pallas_call vs nb of them — not retracing)
    jax.block_until_ready(ops.block_stats_batched(toks))
    jax.block_until_ready(ops.block_stats(toks[0]))
    t0 = time.perf_counter()
    jax.block_until_ready(ops.block_stats_batched(toks))
    bat_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(jnp.stack([ops.block_stats(toks[b])
                                     for b in range(nb)]))
    per_wall = time.perf_counter() - t0
    rows.append({"kernel_blocks": nb, "batched_wall_s": bat_wall,
                 "per_block_wall_s": per_wall,
                 "speedup": per_wall / bat_wall})
    _row("planner_scale_kernel_batched", bat_wall * 1e6 / nb,
         f"dispatches=1_vs_{nb};speedup={per_wall / bat_wall:.1f}x;equiv=ref")
    return rows


def bench_pipeline(quick: bool = False):
    """Streamed SoA dataset→plan pipeline at 10k → 1M blocks.

    Rows report END-TO-END throughput (synthetic per-record costs → chunked
    batched sampling → SoA estimates → vectorized planner) with a per-stage
    breakdown (``est_wall_s`` / ``plan_wall_s``) and the process peak RSS
    after each scale — the path never materializes per-block Python
    objects, so memory is bounded by the chunk size plus the SoA
    accumulators, not the block count.  At 10k blocks every streamed plan
    is asserted identical to the object-based path on the same estimates
    (frequencies exact, energies within 1e-9) — the row fails loudly rather
    than reporting a fast-but-wrong pipeline.  A ratio row compares the
    tight-deadline planner regime (budget-binding kills: sorted-scan with
    the lazily-sorted window, no python tail) against the ample regime's
    pure-array fast path.  A token row streams a real ``BlockDataset``
    through the batched block-stats pallas kernel (one dispatch per chunk;
    CPU runs it in interpret mode, so treat its absolute wall as a
    correctness demo, not kernel speed), and a cluster row feeds the same
    SoA estimates to ``plan_cluster`` directly.
    """
    import resource

    import numpy as np

    from repro.core import plan_dvfs
    from repro.pipeline import (PipelineConfig, plan_estimates,
                                stream_estimates, stream_estimates_tokens,
                                synthetic_cost_chunks)

    def rss_mb() -> float:
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0

    rows = []
    cfg = PipelineConfig()
    sizes = (10_000, 100_000) if quick else (10_000, 100_000, 1_000_000)
    plan_bps = {}
    for n in sizes:
        t0 = time.perf_counter()
        est = stream_estimates(
            synthetic_cost_chunks(n, 64, z=1.0, seed=0,
                                  chunk_size=cfg.chunk_size), cfg)
        est_wall = time.perf_counter() - t0
        total = float(est.total.sum())
        if n == 10_000:  # equivalence oracle at the smallest scale
            blocks = est.to_block_arrays().to_blocks()
            for planner in ("paper", "global"):
                pcfg = PipelineConfig(planner=planner)
                for slack in (1.8, 1.2):
                    pa = plan_estimates(est, total * slack, pcfg)
                    obj = plan_dvfs(blocks, total * slack, planner=planner)
                    assert pa.feasible == obj.feasible
                    for i, b in enumerate(obj.blocks):
                        assert pa.rel_freq[i] == b.rel_freq and \
                            abs(pa.pred_energy_j[i] - b.pred_energy_j) \
                            <= 1e-9, (planner, slack, i)
        for tag, slack in (("ample", 1.8), ("tight", 1.2)):
            walls = []
            for _ in range(2):
                t0 = time.perf_counter()
                pa = plan_estimates(est, total * slack, cfg)
                walls.append(time.perf_counter() - t0)
            plan_wall = min(walls)
            e2e = est_wall + plan_wall
            plan_bps[(n, tag)] = n / plan_wall
            row = {"n": n, "deadline": tag, "est_wall_s": est_wall,
                   "plan_wall_s": plan_wall, "e2e_wall_s": e2e,
                   "blocks_per_s": n / e2e,
                   "plan_blocks_per_s": n / plan_wall,
                   "feasible": pa.feasible, "peak_rss_mb": rss_mb()}
            if n == 10_000:
                row["equivalent"] = True
            rows.append(row)
            derived = (f"blocks_per_s={n / e2e:,.0f};"
                       f"plan_bps={n / plan_wall:,.0f};"
                       f"est_s={est_wall:.2f};rss_mb={rss_mb():.0f};"
                       f"feasible={pa.feasible}")
            if n == 10_000:
                derived += ";equiv=object_path"
            _row(f"pipeline_n{n}_{tag}", e2e * 1e6 / n, derived)
        del est

    n_big = sizes[-1]
    ratio = plan_bps[(n_big, "ample")] / plan_bps[(n_big, "tight")]
    rows.append({"scenario": "tight_vs_ample", "n": n_big,
                 "ample_plan_bps": plan_bps[(n_big, "ample")],
                 "tight_plan_bps": plan_bps[(n_big, "tight")],
                 "ample_over_tight": ratio})
    _row("pipeline_tight_vs_ample", 0.0,
         f"n={n_big};ample_over_tight={ratio:.2f}x")

    # token path: BlockDataset -> batched stats kernel -> plan (one pallas
    # dispatch per chunk; interpret mode on CPU)
    from repro.data import BlockDataset
    nb = 48 if quick else 96
    ds = BlockDataset(n_blocks=nb, records_per_block=128, max_len=48, seed=0)
    t0 = time.perf_counter()
    te = stream_estimates_tokens(ds.iter_token_chunks(32), cfg)
    tok_wall = time.perf_counter() - t0
    pa = plan_estimates(te, float(te.total.sum()) * 1.3, cfg)
    rows.append({"token_blocks": nb, "est_wall_s": tok_wall,
                 "blocks_per_s": nb / tok_wall, "feasible": pa.feasible})
    _row("pipeline_tokens_kernel", tok_wall * 1e6 / nb,
         f"blocks_per_s={nb / tok_wall:,.0f};feasible={pa.feasible};"
         f"dispatches=1_per_chunk")

    # cluster SoA: the same streamed estimates straight into plan_cluster
    from repro.cluster import NodeSpec, plan_cluster
    nodes = [NodeSpec(f"n{k}", speed=s)
             for k, s in enumerate((1.0, 0.8, 1.25))]
    n_c = 2000
    est_c = stream_estimates(synthetic_cost_chunks(n_c, 32, seed=1), cfg)
    deadline = float(est_c.total.sum()) / (0.8 * len(nodes)) * 1.4
    ba = est_c.to_block_arrays()
    t0 = time.perf_counter()
    cpa = plan_cluster(ba, nodes, deadline, assignment="round_robin")
    clu_wall = time.perf_counter() - t0
    obj = plan_cluster(ba.to_blocks(), nodes, deadline,
                       assignment="round_robin")
    assert abs(cpa.pred_total_energy - obj.pred_total_energy) <= 1e-6, \
        "cluster SoA diverged from object path"
    rows.append({"cluster_blocks": n_c, "plan_wall_s": clu_wall,
                 "blocks_per_s": n_c / clu_wall,
                 "feasible": cpa.feasible, "equivalent": True})
    _row("pipeline_cluster_soa", clu_wall * 1e6 / n_c,
         f"blocks_per_s={n_c / clu_wall:,.0f};feasible={cpa.feasible};"
         f"equiv=object_path")
    return rows


def bench_cluster():
    """Cluster scenario sweep: datasets (Zipf z) × apps × node counts ×
    deadline tightness.  Every row compares the multi-node planner (LPT +
    cross-node greedy) against per-node independent Algorithm 1 on a
    round-robin split — same blocks, same heterogeneous nodes, same deadline.
    A final row injects a mid-run 2× slowdown and shows online re-planning
    recovering the deadline that the static plan misses."""
    import numpy as np

    from repro.cluster import (NodeSpec, SlowdownEvent, assign_blocks,
                               plan_cluster, plan_independent,
                               simulate_cluster)
    from repro.core import BlockInfo, FrequencyLadder, zipf_block_sizes

    SPEEDS = (1.0, 0.7, 1.3, 0.85, 1.2)
    APPS = {"wordcount": (5.0, 24), "grep": (3.0, 32), "avg": (8.0, 18)}
    rows = []
    for app, (mean_cost, n_blocks) in APPS.items():
        for z in (1.0, 2.0):
            sizes = zipf_block_sizes(n_blocks, 10000, z=z, seed=0)
            costs = sizes / sizes.mean() * mean_cost
            blocks = [BlockInfo(i, float(c)) for i, c in enumerate(costs)]
            for n_nodes in (3, 5):
                nodes = [NodeSpec(f"n{k}", speed=SPEEDS[k % len(SPEEDS)])
                         for k in range(n_nodes)]
                rr = assign_blocks(blocks, nodes, strategy="round_robin")
                mk_rr = max(sum(b.est_time_fmax for b in g) / n.speed
                            for g, n in zip(rr, nodes))
                for tag, slack in (("tight", 1.15), ("firm", 1.5)):
                    deadline = mk_rr * slack
                    r_ind = simulate_cluster(
                        plan_independent(blocks, nodes, deadline), blocks)
                    r_clu = simulate_cluster(
                        plan_cluster(blocks, nodes, deadline), blocks)
                    imp = r_clu.improvement_vs(r_ind)
                    rows.append({"app": app, "z": z, "nodes": n_nodes,
                                 "deadline": tag, "improvement": imp,
                                 "ind_energy_j": r_ind.total_energy_j,
                                 "clu_energy_j": r_clu.total_energy_j,
                                 "ind_met": r_ind.deadline_met,
                                 "clu_met": r_clu.deadline_met})
                    _row(f"cluster_{app}_z{z:g}_n{n_nodes}_{tag}",
                         r_clu.makespan_s * 1e6 / n_blocks,
                         f"energy=-{imp:.1%};ind_met={r_ind.deadline_met};"
                         f"clu_met={r_clu.deadline_met}")

    # online recovery: uniform blocks, deep ladder, 2x slowdown on one node
    deep = FrequencyLadder(
        states=tuple(round(f, 2) for f in np.arange(0.35, 1.001, 0.05)))
    blocks = [BlockInfo(i, 5.0) for i in range(24)]
    nodes = [NodeSpec("n0", speed=1.0, ladder=deep),
             NodeSpec("n1", speed=0.8, ladder=deep),
             NodeSpec("n2", speed=1.25, ladder=deep)]
    mk = max(sum(b.est_time_fmax for b in g) / n.speed
             for g, n in zip(assign_blocks(blocks, nodes), nodes))
    deadline = mk * 2.2
    # balanced spread (not the auto assignment search): the scenario shows
    # the feedback loop recovering a deadline, so every node must hold work
    plan = plan_cluster(blocks, nodes, deadline, assignment="lpt")
    n0_blocks = len(plan.node_plans[0].blocks)
    events = [SlowdownEvent("n0", after_block=n0_blocks // 2 - 1, factor=2.0)]
    r_static = simulate_cluster(plan, blocks, events=events)
    r_online = simulate_cluster(plan, blocks, events=events, online=True,
                                ewma_alpha=0.7, replan_threshold=0.1)
    rows.append({"scenario": "online_recovery",
                 "static_met": r_static.deadline_met,
                 "online_met": r_online.deadline_met,
                 "replans": r_online.n_replans})
    _row("cluster_online_recovery", r_online.makespan_s * 1e6 / 24,
         f"static_met={r_static.deadline_met};"
         f"online_met={r_online.deadline_met};replans={r_online.n_replans}")
    return rows


def bench_runtime():
    """Event-driven cluster runtime scenario grid (repro.runtime).

    Three sub-grids over one Zipf workload on heterogeneous nodes:

      * fault grid — deadline tightness × fault severity × migration
        on/off, all online: shows where clock-up alone recovers and where
        migration is the only recovery.  Asserts the acceptance scenario —
        under the severe fault, the f_max-only run misses the deadline and
        the migration run meets it.
      * power-cap grid — cap levels against the uncapped run's peak draw:
        the capped plans/runs trade deadline slack for lower peak power.
        Asserts at least one capped run meets the deadline at strictly
        lower peak power.
      * 10k-block smoke — fault + migration + power cap + actuation
        latency at once; the row CI guards with a wall-clock ceiling.
    """
    import numpy as np

    from repro.cluster import (NodeSpec, SlowdownEvent, assign_blocks,
                               plan_cluster)
    from repro.core import BlockInfo, FrequencyLadder, zipf_block_sizes
    from repro.runtime import ActuationModel, RuntimeConfig, run_cluster

    deep = FrequencyLadder(
        states=tuple(round(f, 2) for f in np.arange(0.35, 1.001, 0.05)))

    def make(n_blocks, speeds, slack, z=1.0, **plan_kw):
        sizes = zipf_block_sizes(n_blocks, max(10 * n_blocks, 10000), z=z,
                                 seed=0)
        costs = sizes / sizes.mean() * 5.0
        blocks = [BlockInfo(i, float(c)) for i, c in enumerate(costs)]
        nodes = [NodeSpec(f"n{k}", speed=s, ladder=deep)
                 for k, s in enumerate(speeds)]
        mk = max(sum(b.est_time_fmax for b in g) / n.speed
                 for g, n in zip(assign_blocks(blocks, nodes), nodes))
        deadline = mk * slack
        plan = plan_cluster(blocks, nodes, deadline, assignment="lpt",
                            **plan_kw)
        return blocks, nodes, deadline, plan

    rows = []

    # --- fault grid: tightness x severity x migration -----------------------
    recovered_by_migration_only = False
    for tag, slack in (("tight", 1.5), ("ample", 2.2)):
        blocks, nodes, deadline, plan = make(24, (1.0, 0.8, 1.25), slack)
        n0_half = len(plan.node_plans[0].blocks) // 2 - 1
        for fault, factor in (("none", None), ("slow2x", 2.0),
                              ("slow4x", 4.0)):
            events = [] if factor is None else \
                [SlowdownEvent("n0", after_block=n0_half, factor=factor)]
            outcomes = {}
            for mode in ("static", "online", "migrate"):
                cfg = RuntimeConfig(
                    online=mode != "static", migrate=mode == "migrate",
                    ewma_alpha=0.7, replan_threshold=0.1, log_events=False)
                rep = run_cluster(plan, blocks, config=cfg, events=events,
                                  est_blocks=blocks if mode != "static"
                                  else None)
                outcomes[mode] = rep
                rows.append({"scenario": "fault_grid", "deadline": tag,
                             "fault": fault, "mode": mode,
                             "met": rep.deadline_met,
                             "makespan_s": rep.makespan_s,
                             "energy_j": rep.total_energy_j,
                             "replans": rep.n_replans,
                             "migrations": rep.n_migrations})
            if tag == "ample" and fault == "slow4x":
                # acceptance: migration recovers what f_max alone cannot
                assert not outcomes["online"].deadline_met, \
                    "expected the clock-up-only run to miss under slow4x"
                assert outcomes["migrate"].deadline_met, \
                    "expected migration to recover the slow4x deadline"
                recovered_by_migration_only = True
            _row(f"runtime_{tag}_{fault}",
                 outcomes["migrate"].makespan_s * 1e6 / 24,
                 f"static_met={outcomes['static'].deadline_met};"
                 f"online_met={outcomes['online'].deadline_met};"
                 f"migrate_met={outcomes['migrate'].deadline_met};"
                 f"moves={outcomes['migrate'].n_migrations}")
    assert recovered_by_migration_only

    # --- power-cap grid: cap levels vs the uncapped peak --------------------
    blocks, nodes, deadline, plan = make(24, (1.0, 0.8, 1.25), 1.8)
    free = run_cluster(plan, blocks, config=RuntimeConfig(log_events=False))
    cap_traded = False
    rows.append({"scenario": "power_cap", "cap": "none",
                 "met": free.deadline_met, "makespan_s": free.makespan_s,
                 "peak_power_w": free.peak_power_w,
                 "energy_j": free.total_energy_j})
    _row("runtime_cap_none", free.makespan_s * 1e6 / 24,
         f"met={free.deadline_met};peak_w={free.peak_power_w:.0f}")
    for cap_tag, frac in (("cap95", 0.95), ("cap85", 0.85)):
        cap = free.peak_power_w * frac
        _, _, _, plan_c = make(24, (1.0, 0.8, 1.25), 1.8, power_cap_w=cap)
        rep = run_cluster(plan_c, blocks,
                          config=RuntimeConfig(power_cap_w=cap,
                                               log_events=False))
        assert rep.peak_power_w <= cap + 1e-9
        rows.append({"scenario": "power_cap", "cap": cap_tag, "cap_w": cap,
                     "met": rep.deadline_met, "makespan_s": rep.makespan_s,
                     "peak_power_w": rep.peak_power_w,
                     "plan_cap_ok": plan_c.power_cap_ok,
                     "energy_j": rep.total_energy_j})
        if rep.deadline_met and rep.peak_power_w < free.peak_power_w - 1e-6:
            cap_traded = True  # lower peak, deadline still met
        _row(f"runtime_{cap_tag}", rep.makespan_s * 1e6 / 24,
             f"met={rep.deadline_met};peak_w={rep.peak_power_w:.0f};"
             f"vs_free={rep.peak_power_w / free.peak_power_w:.2f}x")
    assert cap_traded, "no capped run traded slack for lower peak power"

    # --- 10k-block smoke: everything on at once (CI wall ceiling) -----------
    # cap sits just under the plan's conservative Σ of per-node peak draws
    # (the quantity the plan-time screen bounds), so the capped plan stays
    # deadline-feasible and migration keeps target capacity to work with
    n = 10_000
    blocks, nodes, deadline, plan_free = make(n, (1.0, 0.8, 1.25, 0.9, 1.1),
                                              2.0)
    sum_peaks = sum(max(np_.node.power.power(1.0, bp.rel_freq)
                        for bp in np_.blocks)
                    for np_ in plan_free.node_plans)
    cap = sum_peaks * 0.95
    plan = plan_cluster(blocks, nodes, deadline, assignment="lpt",
                        power_cap_w=cap)
    assert plan.power_cap_ok, "smoke plan should pass the Σ-power screen"
    events = [SlowdownEvent("n0", after_block=200, factor=3.0)]
    cfg = RuntimeConfig(online=True, migrate=True, power_cap_w=cap,
                        actuation=ActuationModel(latency_s=0.05,
                                                 switch_energy_j=1.0),
                        ewma_alpha=0.7, replan_threshold=0.1,
                        log_events=False)
    t0 = time.perf_counter()
    rep = run_cluster(plan, blocks, config=cfg, events=events,
                      est_blocks=blocks)
    wall = time.perf_counter() - t0
    assert rep.peak_power_w <= cap + 1e-9
    assert rep.deadline_met and rep.n_migrations >= 1, \
        "smoke scenario should recover the deadline via migration"
    rows.append({"scenario": "smoke10k", "n": n, "wall_s": wall,
                 "blocks_per_s": n / wall, "met": rep.deadline_met,
                 "migrations": rep.n_migrations, "replans": rep.n_replans,
                 "switches": rep.n_switches,
                 "peak_power_w": rep.peak_power_w, "cap_w": cap})
    _row("runtime_smoke10k", wall * 1e6 / n,
         f"blocks_per_s={n / wall:,.0f};met={rep.deadline_met};"
         f"moves={rep.n_migrations};peak_w={rep.peak_power_w:.0f}")
    return rows


def _fleet_scenario(n_blocks, n_nodes, speed_step):
    """The everything-on fleet scenario (faults, migration + wire energy,
    power cap, online recalibration) shared by the engine and obs sections
    — rng seed 0, so every caller sees the identical workload."""
    import numpy as np

    from repro.cluster import NodeSpec
    from repro.core import FrequencyLadder, PowerModel
    from repro.core.soa import BlockArrays
    from repro.runtime import (ActuationModel, FaultEvent, MigrationModel,
                               RuntimeConfig)

    rng = np.random.default_rng(0)
    est = rng.uniform(0.2, 2.0, n_blocks)
    blocks = BlockArrays.build(
        est, util=rng.uniform(0.5, 1.0, n_blocks),
        records=rng.integers(100, 2000, n_blocks).astype(float))
    ladder = FrequencyLadder((0.6, 0.8, 1.0))
    nodes = [NodeSpec(f"n{k}", ladder=ladder,
                      power=PowerModel(p_idle=40.0, p_full=160.0,
                                       alpha=2.0),
                      speed=1.0 + speed_step * k)
             for k in range(n_nodes)]
    deadline = float(est.sum()) / n_nodes * 1.15
    events = [FaultEvent(time=deadline * 0.2, node="n3", factor=1.4),
              FaultEvent(time=deadline * 0.5, node="n7", factor=1.3)]
    cfg = RuntimeConfig(
        online=True, migrate=True, actuation=ActuationModel(),
        migration=MigrationModel(latency_s_per_block=1.0,
                                 energy_j_per_record=0.001),
        power_cap_w=n_nodes * 40.0 + 0.9 * n_nodes * 120.0,
        log_events=False)
    return blocks, nodes, deadline, events, cfg


def bench_engine(quick: bool = False):
    """Vectorized vs scalar event engine (repro.runtime.vector).

    The everything-on scenario — faults, migration with wire energy, a
    cluster power cap, online recalibration — at fleet scale:

      * 100k blocks x 16 nodes: both engines run the identical scenario;
        the row asserts the vectorized report EQUALS the scalar oracle's
        (the bit-identity contract from tests/test_runtime_vector.py,
        re-checked here at a scale the test sweep never reaches).
      * 1M blocks x 100 nodes (skipped by --quick): plan + vectorized run
        end-to-end; the scalar oracle is not run at this scale.
    """
    from repro.cluster.planner import plan_cluster_arrays
    from repro.runtime import run_cluster

    scenario = _fleet_scenario
    rows = []

    # --- 100k x 16: vector vs the scalar oracle, same scenario --------------
    n, k = 100_000, 16
    blocks, nodes, deadline, events, cfg = scenario(n, k, 0.02)
    plan = plan_cluster_arrays(blocks, nodes, deadline_s=deadline)
    walls = {}
    reps = {}
    for engine in ("vector", "scalar"):
        t0 = time.perf_counter()
        reps[engine] = run_cluster(plan, blocks, config=cfg, events=events,
                                   engine=engine)
        walls[engine] = time.perf_counter() - t0
        rows.append({"scenario": "equiv100k", "n": n, "nodes": k,
                     "engine": engine, "wall_s": walls[engine],
                     "blocks_per_s": n / walls[engine],
                     "makespan_s": reps[engine].makespan_s,
                     "energy_j": reps[engine].total_energy_j,
                     "migrations": reps[engine].n_migrations})
    assert reps["vector"] == reps["scalar"], \
        "vectorized engine diverged from the scalar oracle at 100k x 16"
    speedup = walls["scalar"] / walls["vector"]
    for engine in ("vector", "scalar"):
        _row(f"engine_100k_{engine}", walls[engine] * 1e6 / n,
             f"blocks_per_s={n / walls[engine]:,.0f};"
             f"speedup={speedup:.1f}x;identical=True")

    if quick:
        return rows

    # --- 1M x 100: plan + vectorized run end-to-end -------------------------
    n, k = 1_000_000, 100
    blocks, nodes, deadline, events, cfg = scenario(n, k, 0.002)
    t0 = time.perf_counter()
    plan = plan_cluster_arrays(blocks, nodes, deadline_s=deadline)
    plan_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep = run_cluster(plan, blocks, config=cfg, events=events,
                      engine="vector")
    run_s = time.perf_counter() - t0
    total = plan_s + run_s
    rows.append({"scenario": "fleet1m", "n": n, "nodes": k,
                 "engine": "vector", "plan_s": plan_s, "run_s": run_s,
                 "wall_s": total, "blocks_per_s": n / total,
                 "makespan_s": rep.makespan_s,
                 "energy_j": rep.total_energy_j,
                 "migrations": rep.n_migrations,
                 "peak_power_w": rep.peak_power_w})
    _row("engine_1m_end_to_end", total * 1e6 / n,
         f"blocks_per_s={n / total:,.0f};plan_s={plan_s:.1f};"
         f"run_s={run_s:.1f};moves={rep.n_migrations}")
    return rows


def bench_obs(quick: bool = False):
    """Observability overhead + reconstruction throughput (repro.obs).

    Overhead grid: the engine section's everything-on fleet scenario with
    the streaming aggregator on vs off, per engine, at 10k (and 100k
    unless --quick) blocks — event log off, so the wall delta is purely
    the inline metrics feed.  ``overhead_frac`` is on/off − 1; the CI
    obs-smoke job separately pins the 100k metrics+ring configuration
    under 5%.  Then, on the full event log: span-forest reconstruction
    and Chrome-trace export throughput.
    """
    import dataclasses

    from repro.cluster.planner import plan_cluster_arrays
    from repro.obs.export import to_chrome_trace, validate_chrome_trace
    from repro.obs.metrics import StreamingMetrics
    from repro.obs.spans import build_spans
    from repro.runtime import run_cluster

    rows = []
    sizes = [(10_000, 16)] if quick else [(10_000, 16), (100_000, 16)]

    for n, k in sizes:
        blocks, nodes, deadline, events, cfg = _fleet_scenario(n, k, 0.02)
        plan = plan_cluster_arrays(blocks, nodes, deadline_s=deadline)
        for engine in ("vector", "scalar"):
            base_wall = None
            for metrics in ("off", "on"):
                mx = StreamingMetrics() if metrics == "on" else None
                c = dataclasses.replace(cfg, metrics=mx)
                t0 = time.perf_counter()
                rep = run_cluster(plan, blocks, config=c, events=events,
                                  engine=engine)
                wall = time.perf_counter() - t0
                row = {"scenario": "overhead", "stage": "run", "n": n,
                       "nodes": k, "engine": engine, "metrics": metrics,
                       "events": "off", "wall_s": wall,
                       "blocks_per_s": n / wall,
                       "makespan_s": rep.makespan_s}
                if metrics == "off":
                    base_wall = wall
                else:
                    row["overhead_frac"] = wall / base_wall - 1.0
                rows.append(row)
                _row(f"obs_{n // 1000}k_{engine}_metrics_{metrics}",
                     wall * 1e6 / n,
                     f"blocks_per_s={n / wall:,.0f};"
                     + (f"overhead={row['overhead_frac']:+.1%}"
                        if metrics == "on" else "baseline"))

    # span reconstruction + export on the full event log (largest size)
    n, k = sizes[-1]
    blocks, nodes, deadline, events, cfg = _fleet_scenario(n, k, 0.02)
    cfg = dataclasses.replace(cfg, log_events=True)
    plan = plan_cluster_arrays(blocks, nodes, deadline_s=deadline)
    rep = run_cluster(plan, blocks, config=cfg, events=events,
                      engine="vector")
    n_rows = len(rep.event_log)
    t0 = time.perf_counter()
    spans = build_spans(rep.event_log)
    span_wall = time.perf_counter() - t0
    rows.append({"scenario": "spans", "stage": "build_spans", "n": n,
                 "nodes": k, "engine": "vector", "events": "full",
                 "wall_s": span_wall, "blocks_per_s": n / span_wall,
                 "rows_per_s": n_rows / span_wall})
    _row("obs_build_spans", span_wall * 1e6 / n,
         f"rows_per_s={n_rows / span_wall:,.0f};log_rows={n_rows}")
    t0 = time.perf_counter()
    doc = to_chrome_trace(rep, spans=spans)
    export_wall = time.perf_counter() - t0
    assert validate_chrome_trace(doc) == []
    rows.append({"scenario": "spans", "stage": "chrome_export", "n": n,
                 "nodes": k, "engine": "vector", "events": "full",
                 "wall_s": export_wall, "blocks_per_s": n / export_wall,
                 "trace_events": len(doc["traceEvents"])})
    _row("obs_chrome_export", export_wall * 1e6 / n,
         f"trace_events={len(doc['traceEvents'])};validated=True")
    return rows


def bench_obs_cf(quick: bool = False):
    """Counterfactual replay, run-diff, and watchdog determinism.

    Three asserted sub-grids on the engine section's everything-on fleet
    scenario (small n — each mechanism costs whole replays on BOTH
    engines):

      * ablation grid — ``profile_mechanisms`` over both engines (report
        identity asserted inside); every row's five channel deltas plus
        the rational-space residual must sum BITWISE to the difference of
        the two reports' own totals, and the DVFS-off row must reproduce
        the paper's headline: DV-DVFS strictly below f_max busy energy at
        equal deadline, deadline still met.
      * watchdog identity — the alert stream must be bitwise-identical
        scalar vs vector AND across two vector runs.
      * run-diff self-check — ``diff_runs(r, r)`` empty; diffing the base
        against the migration-off replay is non-empty and attributes
        moved blocks.
    """
    import dataclasses
    import math

    from repro.cluster.planner import plan_cluster_arrays
    from repro.obs.counterfactual import Scenario, ablate, profile_mechanisms
    from repro.obs.diff import diff_runs
    from repro.obs.metrics import StreamingMetrics
    from repro.obs.watchdog import Watchdog, standard_rules
    from repro.runtime import run_cluster

    rows = []
    n, k = (2_000, 8) if quick else (10_000, 8)
    blocks, nodes, deadline, events, cfg = _fleet_scenario(n, k, 0.02)
    plan = plan_cluster_arrays(blocks, nodes, deadline_s=deadline)
    sc = Scenario(plan=plan, truth=blocks, config=cfg, events=events)

    # --- ablation grid: both engines, exact Δ reconciliation ----------------
    t0 = time.perf_counter()
    cf = profile_mechanisms(sc)
    cf_wall = time.perf_counter() - t0
    n_runs = 2 * (1 + sum(r["changed"] for r in cf))
    chans = ("busy_j", "idle_j", "switch_j", "wire_j", "failed_j")
    for r in cf:
        assert math.fsum([r[f"d_{c}"] for c in chans]
                         + [r["residual_j"]]) == r["d_total_j"], \
            f"Δ-ledger for {r['mechanism']} does not reconcile bitwise"
        rows.append({"scenario": "ablation", "mechanism": r["mechanism"],
                     "n": n, "nodes": k, "changed": r["changed"],
                     "d_total_j": r["d_total_j"], "d_busy_j": r["d_busy_j"],
                     "d_misses": r["d_misses"], "d_slack_s": r["d_slack_s"],
                     "wall_s": cf_wall,
                     "blocks_per_s": n * n_runs / cf_wall})
        _row(f"obs_cf_{r['mechanism']}", cf_wall * 1e6 / (n * n_runs),
             f"d_total_j={r['d_total_j']:+.1f};d_misses={r['d_misses']:+d};"
             f"reconciled=True")

    # --- the paper's headline as a counterfactual -----------------------------
    # dedicated crash-free scenario (the everything-on grid's crashes can
    # push the tight 1.15x base past its deadline at small n, which would
    # make "at equal deadline" vacuous): DV-DVFS must meet the deadline
    # AND pay strictly less busy energy than its own f_max replay
    hd_plan = plan_cluster_arrays(blocks, nodes, deadline_s=deadline * 1.2)
    hd = Scenario(plan=hd_plan, truth=blocks, config=cfg)
    t0 = time.perf_counter()
    hd_base = hd.run(engine="vector")
    hd_fmax = ablate(hd, "dvfs", engines=("vector",))
    hd_wall = time.perf_counter() - t0
    d_busy = hd_fmax.total_energy_j - hd_base.total_energy_j
    assert d_busy > 0.0, \
        "DVFS-off ablation must show DV-DVFS strictly below f_max busy energy"
    assert hd_base.deadline_met, "the DV-DVFS base run must meet its deadline"
    improvement = d_busy / hd_fmax.total_energy_j
    rows.append({"scenario": "dvfs_headline", "n": n, "nodes": k,
                 "improvement_frac": improvement,
                 "base_busy_j": hd_base.total_energy_j,
                 "fmax_busy_j": hd_fmax.total_energy_j,
                 "deadline_met": hd_base.deadline_met,
                 "wall_s": hd_wall, "blocks_per_s": n * 2 / hd_wall})
    _row("obs_cf_dvfs_headline", hd_wall * 1e6 / (n * 2),
         f"improvement={improvement:.1%};deadline_met=True")

    # --- watchdog determinism: scalar vs vector, two runs --------------------
    wcfg = dataclasses.replace(cfg, log_events=True, event_log="full")
    base_total = cf[0]["base_total_j"]    # every ledger row carries it

    def wd_run(engine):
        mx = StreamingMetrics()
        wd = Watchdog(standard_rules(
            deadline, energy_budget_j=0.8 * base_total)).attach(mx)
        run_cluster(plan, blocks,
                    config=dataclasses.replace(wcfg, metrics=mx),
                    events=events, engine=engine)
        return wd.alerts

    t0 = time.perf_counter()
    alerts_v = wd_run("vector")
    alerts_s = wd_run("scalar")
    alerts_v2 = wd_run("vector")
    wd_wall = time.perf_counter() - t0
    assert alerts_v == alerts_s, \
        "watchdog alert streams diverged between scalar and vector"
    assert alerts_v == alerts_v2, \
        "watchdog alert stream is not two-run deterministic"
    rows.append({"scenario": "watchdog", "n": n, "nodes": k,
                 "alerts": len(alerts_v), "wall_s": wd_wall,
                 "blocks_per_s": n * 3 / wd_wall})
    _row("obs_cf_watchdog", wd_wall * 1e6 / (n * 3),
         f"alerts={len(alerts_v)};identical=True")

    # --- run-diff: identity empty, ablated attributed ------------------------
    sc_full = dataclasses.replace(sc, config=wcfg)
    t0 = time.perf_counter()
    rep_a = sc_full.run(engine="vector")
    rep_b = sc_full.run(engine="vector")
    assert diff_runs(rep_a, rep_b).empty, \
        "diff of two identical runs must be empty"
    abl = ablate(sc_full, "migration", engines=("vector",))
    diff = diff_runs(rep_a, abl)
    diff_wall = time.perf_counter() - t0
    assert not diff.empty and (diff.moved or diff.blocks), \
        "migration-off diff must attribute changed work"
    rows.append({"scenario": "diff", "stage": "diff_runs", "n": n,
                 "nodes": k, "changed_blocks": len(diff.blocks),
                 "moved": len(diff.moved), "wall_s": diff_wall,
                 "blocks_per_s": n * 3 / diff_wall})
    _row("obs_cf_diff", diff_wall * 1e6 / (n * 3),
         f"changed_blocks={len(diff.blocks)};moved={len(diff.moved)};"
         f"identity_empty=True")
    return rows


def bench_calibrate(quick: bool = False):
    """Telemetry-driven calibration (repro.calibrate): the
    estimate->plan->measure loop.

    Three sub-grids:

      * fit round-trip — synthetic traces from known ground truth across
        trace noise x trace length: the fitters must recover
        ``(p_idle, p_full, alpha)`` / node speed / ``(cost_per_record,
        mem_fraction)`` within a documented, noise-scaled tolerance
        (asserted — the row fails loudly on a drifting fitter).
      * calibrated vs default — ground-truth model perturbation x trace
        noise: the default-constant plan runs on mis-modeled hardware
        (``run_cluster(..., true_nodes=...)``), its emitted trace is
        fitted, and the calibrated re-plan must DOMINATE the default plan
        whenever the truth deviates >= 10% (deadline met where the default
        misses, or strictly lower busy energy at equal deadline); at zero
        perturbation the two plans must coincide.
      * 10k-block smoke — the full loop at scale (plan, traced run, batch
        refit, re-plan, re-run) with a wall ceiling CI guards; an online
        leg asserts two-run determinism of mid-run recalibration.
    """
    import numpy as np

    from repro.calibrate import (OnlineCalibrator, TraceRecorder,
                                 calibrate_nodes, fit_cost_model,
                                 fit_node_speeds, fit_power_model,
                                 synthetic_trace)
    from repro.cluster import NodeSpec, plan_cluster
    from repro.core import BlockInfo, FrequencyLadder, zipf_block_sizes
    from repro.core.energy import PowerModel
    from repro.runtime import RuntimeConfig, run_cluster

    deep = FrequencyLadder(
        states=tuple(round(f, 2) for f in np.arange(0.35, 1.001, 0.05)))
    rows = []

    # --- fit round-trip: noise x trace length -------------------------------
    truth_power = PowerModel(p_full=230.0, p_idle=80.0, alpha=2.0)
    truth_speed = 0.8
    lengths = (50, 200) if quick else (50, 200, 800)
    for n in lengths:
        for noise in (0.0, 0.02, 0.05):
            t0 = time.perf_counter()
            tr = synthetic_trace("n0", truth_power, speed=truth_speed,
                                 n_samples=n, noise=noise, seed=11)
            pf = fit_power_model(tr)
            sf = fit_node_speeds(tr)["n0"]
            wall = time.perf_counter() - t0
            err_pi = abs(pf.p_idle / truth_power.p_idle - 1)
            err_pf = abs(pf.p_full / truth_power.p_full - 1)
            err_a = abs(pf.alpha - truth_power.alpha)
            err_sp = abs(sf.speed / truth_speed - 1)
            # documented tolerance: grid resolution at zero noise, scaling
            # with noise/sqrt(n) like any LS estimate
            tol = max(0.015, 5.0 * noise * np.sqrt(200.0 / n))
            tol_a = max(0.03, 12.0 * noise * np.sqrt(200.0 / n))
            assert max(err_pi, err_pf) < tol, (n, noise, pf)
            assert err_a < tol_a, (n, noise, pf)
            assert err_sp < max(1e-6, 2.0 * noise), (n, noise, sf)
            rows.append({"scenario": "fit_roundtrip", "n": n, "noise": noise,
                         "err_p_idle": err_pi, "err_p_full": err_pf,
                         "err_alpha": err_a, "err_speed": err_sp,
                         "fit_wall_s": wall})
            _row(f"calibrate_fit_n{n}_noise{noise:g}", wall * 1e6,
                 f"err_p={max(err_pi, err_pf):.4f};err_alpha={err_a:.4f};"
                 f"err_speed={err_sp:.5f};tol={tol:.3f}")

    # cost-model round-trip (per-app record cost + memory-bound fraction)
    rng = np.random.default_rng(5)
    rec_counts = rng.integers(100, 1000, 150).astype(float)
    freqs = rng.choice(np.arange(0.5, 1.001, 0.1), 150)
    c_true, beta_true = 0.004, 0.35
    walls = rec_counts * c_true * np.maximum((1 - beta_true) / freqs, 1.0)
    walls *= 1 + 0.02 * rng.standard_normal(150)
    cf = fit_cost_model(rec_counts, freqs, walls)
    assert abs(cf.cost_per_record / c_true - 1) < 0.05
    assert abs(cf.mem_fraction - beta_true) < 0.05
    rows.append({"scenario": "cost_roundtrip",
                 "err_cost": abs(cf.cost_per_record / c_true - 1),
                 "err_mem_fraction": abs(cf.mem_fraction - beta_true)})
    _row("calibrate_cost_fit", 0.0,
         f"cost={cf.cost_per_record:.5f};mem_frac={cf.mem_fraction:.3f};"
         f"true=({c_true},{beta_true})")

    # --- calibrated vs default: perturbation x trace noise ------------------
    def scenario(perturb, n_blocks=60, seed=0):
        rng = np.random.default_rng(seed)
        blocks = [BlockInfo(i, float(c), util=float(u)) for i, (c, u) in
                  enumerate(zip(rng.lognormal(1.0, 0.5, n_blocks),
                                rng.uniform(0.6, 1.0, n_blocks)))]
        believed = [NodeSpec(f"n{k}", speed=1.0, ladder=deep)
                    for k in range(3)]
        sp = (1.0 - perturb, 1.0 + perturb, 1.0 + perturb / 2)
        true = [NodeSpec(f"n{k}", speed=sp[k], ladder=deep,
                         power=PowerModel(
                             p_full=200.0 * (1 + perturb),
                             p_idle=70.0 * (1 - perturb / 2),
                             alpha=2.4 * (1 - perturb / 3)))
                for k in range(3)]
        deadline = sum(b.est_time_fmax for b in blocks) / 3 * 1.6
        return blocks, believed, true, deadline

    def jitter(trace, noise, seed=0):
        """Measurement noise on a recorded trace (the engine is exact)."""
        if noise == 0.0:
            return trace
        import dataclasses as dc
        rng = np.random.default_rng(seed)
        jit = lambda: np.clip(1 + noise * rng.standard_normal(len(trace)),
                              0.05, None)
        return dc.replace(trace, dur_s=trace.dur_s * jit(),
                          energy_j=trace.energy_j * jit())

    for perturb in (0.0, 0.1, 0.2, 0.3):
        for noise in ((0.0,) if quick else (0.0, 0.03)):
            blocks, believed, true, deadline = scenario(perturb)
            plan_def = plan_cluster(blocks, believed, deadline,
                                    assignment="lpt")
            recd = TraceRecorder()
            rep_def = run_cluster(
                plan_def, blocks,
                config=RuntimeConfig(trace=recd, log_events=False),
                true_nodes=true)
            cal = calibrate_nodes(believed, jitter(recd.trace(), noise))
            plan_cal = plan_cluster(blocks, cal, deadline, assignment="lpt")
            rep_cal = run_cluster(plan_cal, blocks,
                                  config=RuntimeConfig(log_events=False),
                                  true_nodes=true)
            imp = rep_cal.improvement_vs(rep_def)
            if perturb >= 0.10:
                # acceptance: calibrated strictly dominates once the truth
                # deviates >= 10% from the constructed constants
                assert rep_cal.deadline_met, (perturb, noise)
                assert (not rep_def.deadline_met) or \
                    rep_cal.total_energy_j < rep_def.total_energy_j - 1e-6, \
                    (perturb, noise)
            elif noise == 0.0:
                # no deviation: the calibrated plan must NOT degrade
                assert rep_cal.deadline_met == rep_def.deadline_met
                assert rep_cal.total_energy_j \
                    <= rep_def.total_energy_j + 1e-6
            rows.append({"scenario": "calibrated_vs_default",
                         "perturb": perturb, "noise": noise,
                         "def_met": rep_def.deadline_met,
                         "cal_met": rep_cal.deadline_met,
                         "def_energy_j": rep_def.total_energy_j,
                         "cal_energy_j": rep_cal.total_energy_j,
                         "improvement": imp})
            _row(f"calibrate_replan_p{perturb:g}_noise{noise:g}", 0.0,
                 f"def_met={rep_def.deadline_met};"
                 f"cal_met={rep_cal.deadline_met};energy=-{imp:.1%}")

    # --- online recalibration: two-run determinism --------------------------
    blocks, believed, true, deadline = scenario(0.25)
    plan = plan_cluster(blocks, believed, deadline, assignment="lpt")

    def run_online():
        cfg = RuntimeConfig(online=True, calibrator=OnlineCalibrator(),
                            ewma_alpha=0.5, replan_threshold=0.1)
        return run_cluster(plan, blocks, config=cfg, est_blocks=blocks,
                           true_nodes=true)

    r1, r2 = run_online(), run_online()
    assert r1.event_log == r2.event_log and r1 == r2, \
        "online recalibration must be two-run deterministic"
    rows.append({"scenario": "online_determinism", "met": r1.deadline_met,
                 "replans": r1.n_replans})
    _row("calibrate_online_determinism", 0.0,
         f"met={r1.deadline_met};replans={r1.n_replans};identical=True")

    # --- 10k-block calibrated-replan smoke (CI wall ceiling) ----------------
    n = 10_000
    rng = np.random.default_rng(7)
    sizes = zipf_block_sizes(n, 10 * n, z=1.0, seed=7)
    costs = sizes / sizes.mean() * 5.0
    blocks = [BlockInfo(i, float(c), util=float(u)) for i, (c, u) in
              enumerate(zip(costs, rng.uniform(0.6, 1.0, n)))]
    believed = [NodeSpec(f"n{k}", speed=1.0, ladder=deep) for k in range(5)]
    sp = (0.75, 1.25, 1.1, 0.9, 1.3)
    true = [NodeSpec(f"n{k}", speed=sp[k], ladder=deep,
                     power=PowerModel(240.0, 60.0, 2.0))
            for k in range(5)]
    deadline = float(costs.sum()) / 5 * 1.6
    t0 = time.perf_counter()
    plan_def = plan_cluster(blocks, believed, deadline,
                            assignment="round_robin")
    recd = TraceRecorder()
    rep_def = run_cluster(plan_def, blocks,
                          config=RuntimeConfig(trace=recd, log_events=False),
                          true_nodes=true)
    cal = calibrate_nodes(believed, recd.trace())
    plan_cal = plan_cluster(blocks, cal, deadline, assignment="round_robin")
    rep_cal = run_cluster(plan_cal, blocks,
                          config=RuntimeConfig(log_events=False),
                          true_nodes=true)
    wall = time.perf_counter() - t0
    imp = rep_cal.improvement_vs(rep_def)
    assert rep_cal.deadline_met
    assert (not rep_def.deadline_met) or \
        rep_cal.total_energy_j < rep_def.total_energy_j
    rows.append({"scenario": "smoke10k", "n": n, "wall_s": wall,
                 "blocks_per_s": n / wall, "def_met": rep_def.deadline_met,
                 "cal_met": rep_cal.deadline_met, "improvement": imp})
    _row("calibrate_smoke10k", wall * 1e6 / n,
         f"blocks_per_s={n / wall:,.0f};def_met={rep_def.deadline_met};"
         f"cal_met={rep_cal.deadline_met};energy=-{imp:.1%}")
    return rows


def bench_failures(quick: bool = False):
    """Failure-tolerant runtime (repro.runtime.failures / recovery).

    Three sub-grids:

      * chaos campaign — seeded randomized crash/fault scenarios (30 under
        ``--quick``, 200 otherwise) through scalar AND vector engines;
        asserts zero conservation-invariant violations (exactly-once-or-
        reported-missed blocks, energy bookkeeping incl. burned partial
        work, scalar/vector identity).
      * recovery grid — crash time × MTTR × deadline slack over one crash
        on the fastest-queue node; each cell runs the migration-only
        baseline (no recovery ladder) against the recovery run.  Asserts
        the recovery ladder strands no blocks in ANY cell, that every
        permanent-crash baseline loses the orphaned queue, and that at
        ample slack recovery meets the deadline wherever the baseline
        misses it.
      * zero-failure identity — a recovery-configured run with no failure
        events is REPORT-IDENTICAL to the recovery=None run on both
        engines (the ladder must be pure overhead-free configuration).
    """
    import numpy as np

    from repro.cluster import NodeSpec, assign_blocks, plan_cluster
    from repro.core import BlockInfo, FrequencyLadder, zipf_block_sizes
    from repro.runtime import (CheckpointModel, FaultEvent, MigrationModel,
                               NodeFailureEvent, RecoveryPolicy,
                               RuntimeConfig, run_campaign, run_cluster)

    deep = FrequencyLadder(
        states=tuple(round(f, 2) for f in np.arange(0.35, 1.001, 0.05)))

    def make(n_blocks, speeds, slack):
        sizes = zipf_block_sizes(n_blocks, max(10 * n_blocks, 10000), z=1.0,
                                 seed=0)
        costs = sizes / sizes.mean() * 5.0
        blocks = [BlockInfo(i, float(c)) for i, c in enumerate(costs)]
        nodes = [NodeSpec(f"n{k}", speed=s, ladder=deep)
                 for k, s in enumerate(speeds)]
        mk = max(sum(b.est_time_fmax for b in g) / n.speed
                 for g, n in zip(assign_blocks(blocks, nodes), nodes))
        deadline = mk * slack
        plan = plan_cluster(blocks, nodes, deadline, assignment="lpt")
        return blocks, deadline, plan

    rows = []

    # --- chaos campaign: the tentpole acceptance gate -----------------------
    n_scen = 30 if quick else 200
    t0 = time.perf_counter()
    camp = run_campaign(n_scenarios=n_scen, base_seed=0, check_vector=True)
    wall = time.perf_counter() - t0
    assert camp["violations"] == [], \
        f"chaos campaign invariant violations: {camp['violations'][:3]}"
    rows.append({"scenario": "chaos_campaign", "n": n_scen, "wall_s": wall,
                 "blocks_per_s": n_scen / wall,  # scenarios/s, CI-guarded
                 "violations": 0, "crashes": camp["n_crashes"],
                 "repairs": camp["n_repairs"],
                 "deadline_met_runs": camp["deadline_met_runs"],
                 "runs_with_missed": camp["runs_with_missed"],
                 "recovery_decisions": camp["recovery_decisions"]})
    _row("failures_chaos_campaign", wall * 1e6 / n_scen,
         f"scenarios={n_scen};violations=0;crashes={camp['n_crashes']};"
         f"repairs={camp['n_repairs']}")

    # --- recovery grid: crash time x MTTR x slack ---------------------------
    mig = MigrationModel(latency_s_per_block=0.5, energy_j_per_record=0.005)
    recovered_where_baseline_missed = False
    for slack_tag, slack in (("tight", 1.6), ("ample", 2.4)):
        blocks, deadline, plan = make(24, (1.0, 0.8, 1.25), slack)
        for crash_frac in (0.25, 0.55):
            for mttr_tag, mttr_frac in (("perm", None), ("short", 0.15),
                                        ("long", 0.45)):
                fe = NodeFailureEvent(
                    time=crash_frac * deadline, node="n0",
                    flavor="permanent" if mttr_frac is None else "transient",
                    repair_s=None if mttr_frac is None
                    else mttr_frac * deadline)
                kw = dict(online=True, migrate=True, migration=mig,
                          ewma_alpha=0.7, replan_threshold=0.1,
                          log_events=False)
                rb = run_cluster(plan, blocks, config=RuntimeConfig(**kw),
                                 events=[fe], est_blocks=blocks)
                rr = run_cluster(
                    plan, blocks,
                    config=RuntimeConfig(**kw, recovery=RecoveryPolicy(
                        checkpoint=CheckpointModel(
                            interval_s=0.05 * deadline))),
                    events=[fe], est_blocks=blocks)
                base_misses = (not rb.deadline_met) or bool(rb.missed_blocks)
                # the ladder always finds a survivor for every orphan here
                assert rr.missed_blocks == (), \
                    f"recovery stranded blocks at {slack_tag}/{crash_frac}/" \
                    f"{mttr_tag}: {rr.missed_blocks}"
                if mttr_frac is None:
                    # migration-only cannot see the dead node's queue
                    assert rb.missed_blocks, \
                        "permanent crash should strand the baseline's queue"
                if slack_tag == "ample" and base_misses:
                    assert rr.deadline_met, \
                        f"recovery missed an ample-slack deadline the " \
                        f"baseline also missed ({crash_frac}/{mttr_tag})"
                    recovered_where_baseline_missed = True
                salv = sum(nr.salvaged_frac for nr in rr.node_reports)
                rows.append({"scenario": "recovery_grid",
                             "slack": slack_tag, "crash": crash_frac,
                             "mttr": mttr_tag,
                             "base_met": rb.deadline_met,
                             "base_missed": len(rb.missed_blocks),
                             "rec_met": rr.deadline_met,
                             "rec_missed": len(rr.missed_blocks),
                             "rec_makespan_s": rr.makespan_s,
                             "rec_energy_j": rr.total_energy_j,
                             "salvaged_frac": salv,
                             "lost_records": rr.lost_records})
                _row(f"failures_{slack_tag}_c{crash_frac}_{mttr_tag}",
                     rr.makespan_s * 1e6 / 24,
                     f"base_met={rb.deadline_met};"
                     f"base_missed={len(rb.missed_blocks)};"
                     f"rec_met={rr.deadline_met};salv={salv:.2f}")
    assert recovered_where_baseline_missed, \
        "grid produced no ample-slack cell where recovery beat the baseline"

    # --- zero-failure identity: the ladder is inert without crashes ---------
    blocks, deadline, plan = make(24, (1.0, 0.8, 1.25), 1.8)
    events = [FaultEvent(deadline * 0.4, "n1", 1.5)]
    kw = dict(online=True, migrate=True, migration=mig, ewma_alpha=0.7,
              replan_threshold=0.1, log_events=False)
    rec = RecoveryPolicy(checkpoint=CheckpointModel(interval_s=1.0),
                         use_triage=True)
    for eng in ("scalar", "vector"):
        plain = run_cluster(plan, blocks, config=RuntimeConfig(**kw),
                            events=events, est_blocks=blocks, engine=eng)
        armed = run_cluster(plan, blocks,
                            config=RuntimeConfig(**kw, recovery=rec),
                            events=events, est_blocks=blocks, engine=eng)
        assert plain == armed, \
            f"recovery config perturbed a zero-failure {eng} run"
    rows.append({"scenario": "zero_failure_identity", "engines": 2,
                 "identical": True})
    _row("failures_zero_failure_identity", 0.0, "scalar=vector=plain")
    return rows


def bench_serving(quick: bool = False):
    """Open-loop serving fabric (repro.serving).

    Four sub-grids:

      * sustained-overload grid — offered load x tenant mix x SLO
        tightness, admission+shedding against the no-admission baseline.
        Asserts the fabric's headline guarantee in EVERY cell: accepted-job
        SLO-miss rate <= 1% no matter the offered load, and steady-tenant
        isolation under a 10x burst — while the baseline's miss rate
        diverges with load (asserted > 10% in the overloaded cells).
      * drift shedding — arrivals whose true cost runs 1.5x their estimate:
        backpressure sheds stale promises and still keeps accepted misses
        <= 1%.
      * overload campaign — seeded randomized scenarios (12 under
        ``--quick``, 60 otherwise) through scalar AND vector engines;
        asserts zero serving-conservation violations (every job
        exactly-once accepted-and-finished / shed / rejected, runtime
        ledger audit, two-run determinism, scalar/vector identity).
      * zero-traffic identity — a serving run with no arrivals is bitwise
        the closed-batch run on both engines.
    """
    import dataclasses

    import numpy as np

    from repro.cluster import NodeSpec, plan_cluster
    from repro.core import BlockInfo, FrequencyLadder
    from repro.pipeline import ArrivalSpec, TenantSpec
    from repro.runtime import RuntimeConfig, run_cluster
    from repro.serving import (ServingConfig, check_serving_conservation,
                               run_serving, run_serving_campaign)

    ladder = FrequencyLadder((0.5, 0.7, 0.85, 1.0))
    rng = np.random.default_rng(0)
    blocks = [BlockInfo(i, float(rng.uniform(0.3, 0.7)), util=0.8,
                        records=100.0) for i in range(6)]
    nodes = [NodeSpec(f"n{j}", ladder=ladder) for j in range(3)]
    deadline = sum(b.est_time_fmax for b in blocks) / 3 * 1.8
    plan = plan_cluster(blocks, nodes, deadline_s=deadline)
    truth = [dataclasses.replace(b, est_time_fmax=b.est_time_fmax * 1.05)
             for b in blocks]

    def cfg():
        return RuntimeConfig(online=True, log_events=True)

    horizon, cap_hz = 40.0, 3.0   # 3 nodes digesting ~1 s jobs
    rows = []

    # --- sustained-overload grid: load x mix x SLO --------------------------
    loads = (0.5, 3.0) if quick else (0.5, 1.5, 3.0)
    for load in loads:
        for mix in ("even", "burst"):
            for slo_tag, slo in (("tight", 6.0), ("loose", 14.0)):
                ra = load * cap_hz / 2
                steady = TenantSpec(name="steady", rate_hz=ra, slo_s=slo,
                                    priority=2.0, blocks_per_job=(1, 1),
                                    block_time_s=(0.8, 1.2))
                bkw = dict(name="noisy", rate_hz=ra, slo_s=slo, priority=1.0,
                           blocks_per_job=(1, 1), block_time_s=(0.8, 1.2))
                if mix == "burst":
                    bkw.update(process="burst", burst_factor=10.0,
                               burst_start_s=10.0, burst_end_s=20.0)
                spec = ArrivalSpec(tenants=(steady, TenantSpec(**bkw)),
                                   horizon_s=horizon, seed=5)
                t0 = time.perf_counter()
                g = run_serving(plan, truth, spec, config=cfg(),
                                serving=ServingConfig(margin=0.2),
                                est_blocks=blocks)
                wall = time.perf_counter() - t0
                naked = run_serving(
                    plan, truth, spec, config=cfg(),
                    serving=ServingConfig(admission=False, shedding=False),
                    est_blocks=blocks)
                assert check_serving_conservation(g, plan) == [], \
                    f"serving conservation broke at {load}/{mix}/{slo_tag}"
                assert g.accepted_miss_rate <= 0.01, \
                    f"admission broke its promise at {load}/{mix}/" \
                    f"{slo_tag}: miss={g.accepted_miss_rate:.3f}"
                by = {t.tenant: t for t in g.tenants}
                assert by["steady"].miss_rate <= 0.01, \
                    f"isolation broke at {load}/{mix}/{slo_tag}"
                if load >= 1.5 or mix == "burst":
                    assert naked.accepted_miss_rate > 0.1, \
                        f"baseline failed to collapse at {load}/{mix}/" \
                        f"{slo_tag} — the grid is not actually overloaded"
                rows.append({"scenario": "overload_grid", "load": load,
                             "mix": mix, "slo": slo_tag, "tenants": 2,
                             "blocks_per_s": len(g.jobs) / wall,  # jobs/s
                             "jobs": len(g.jobs),
                             "accepted": g.n_accepted,
                             "rejected": g.n_rejected, "shed": g.n_shed,
                             "miss_rate": g.accepted_miss_rate,
                             "baseline_miss_rate":
                                 naked.accepted_miss_rate,
                             "steady_miss_rate": by["steady"].miss_rate,
                             "wall_s": wall})
                _row(f"serving_l{load}_{mix}_{slo_tag}",
                     wall * 1e6 / max(len(g.jobs), 1),
                     f"acc={g.n_accepted};rej={g.n_rejected};"
                     f"shed={g.n_shed};miss={g.accepted_miss_rate:.3f};"
                     f"base_miss={naked.accepted_miss_rate:.3f}")

    # --- drift shedding: stale promises get shed, not missed ----------------
    hot = ArrivalSpec(
        tenants=(TenantSpec(name="steady", rate_hz=1.5, slo_s=6.0,
                            priority=2.0, blocks_per_job=(1, 1),
                            block_time_s=(0.8, 1.2)),
                 TenantSpec(name="noisy", rate_hz=1.5, slo_s=6.0,
                            priority=1.0, blocks_per_job=(1, 1),
                            block_time_s=(0.8, 1.2))),
        horizon_s=horizon, seed=5)
    g = run_serving(plan, truth, hot, config=cfg(),
                    serving=ServingConfig(margin=0.05), arrival_truth=1.5,
                    est_blocks=blocks)
    assert check_serving_conservation(g, plan) == []
    assert g.n_shed > 0, "1.5x drift produced no backpressure sheds"
    assert g.accepted_miss_rate <= 0.01
    rows.append({"scenario": "drift_shedding", "arrival_truth": 1.5,
                 "accepted": g.n_accepted, "shed": g.n_shed,
                 "miss_rate": g.accepted_miss_rate})
    _row("serving_drift_shedding", 0.0,
         f"shed={g.n_shed};miss={g.accepted_miss_rate:.3f}")

    # --- overload campaign: the tentpole acceptance gate --------------------
    n_scen = 12 if quick else 60
    t0 = time.perf_counter()
    camp = run_serving_campaign(n_scenarios=n_scen, base_seed=0,
                                check_vector=True)
    wall = time.perf_counter() - t0
    assert camp["violations"] == [], \
        f"serving campaign violations: {camp['violations'][:3]}"
    rows.append({"scenario": "overload_campaign", "n": n_scen,
                 "wall_s": wall, "violations": 0,
                 "blocks_per_s": n_scen / wall,  # scenarios/s, CI-guarded
                 "jobs": camp["n_jobs"], "accepted": camp["n_accepted"],
                 "rejected": camp["n_rejected"], "shed": camp["n_shed"]})
    _row("serving_overload_campaign", wall * 1e6 / n_scen,
         f"scenarios={n_scen};violations=0;jobs={camp['n_jobs']};"
         f"shed={camp['n_shed']}")

    # --- zero-traffic identity: no arrivals == closed batch, bitwise --------
    quiet = ArrivalSpec(tenants=(TenantSpec(name="t", rate_hz=0.0,
                                            slo_s=6.0),),
                        horizon_s=horizon)
    for eng in ("scalar", "vector"):
        closed = run_cluster(plan, truth, config=cfg(), est_blocks=blocks,
                             engine=eng)
        srep = run_serving(plan, truth, quiet, config=cfg(),
                           est_blocks=blocks, engine=eng)
        assert srep.runtime == closed \
            and srep.event_log == closed.event_log, \
            f"zero-traffic serving perturbed the {eng} closed-batch run"
    rows.append({"scenario": "zero_traffic_identity", "engines": 2,
                 "identical": True})
    _row("serving_zero_traffic_identity", 0.0, "scalar=vector=closed")
    return rows


def bench_roofline():
    out = {}
    for tag, path in (("base", "results/roofline_sp.json"),
                      ("opt", "results/roofline_sp_opt.json")):
        if not os.path.exists(path):
            print(f"# roofline[{tag}]: {path} missing — run launch/dryrun.py "
                  f"--all [--opt] and benchmarks/report.py first")
            continue
        with open(path) as f:
            rows = json.load(f)
        out[tag] = rows
        for r in rows:
            if r["status"] != "ok":
                continue
            _row(f"roofline_{tag}_{r['arch']}_{r['shape']}",
                 r["bound_s"] * 1e6,
                 f"dom={r['dominant']};roofline={r['roofline_fraction']:.3f};"
                 f"useful={r['useful_ratio']:.2f}")
    return out


def bench_train():
    import tempfile

    from repro.configs import smoke_config
    from repro.data import BlockDataset
    from repro.train import TrainConfig, Trainer
    cfg = smoke_config("olmo-1b")
    with tempfile.TemporaryDirectory() as d:
        tc = TrainConfig(batch=2, seq_len=64, total_steps=16, ckpt_every=8,
                         warmup=2, ckpt_dir=d, dvfs_enabled=True,
                         deadline_slack=1.25, seed=0)
        ds = BlockDataset(n_blocks=4, records_per_block=64, max_len=48,
                          vocab=cfg.vocab, seed=1)
        t0 = time.perf_counter()
        res = Trainer(cfg, tc, dataset=ds).run(resume=False)
        us = (time.perf_counter() - t0) * 1e6 / tc.total_steps
    sav = 1 - res["energy"]["busy_j"] / max(res["energy_dvo"]["busy_j"], 1e-9)
    _row("train_dvdvfs_smoke", us,
         f"loss:{res['first_loss']:.2f}->{res['final_loss']:.2f};"
         f"energy=-{sav:.1%};stragglers={len(res['straggler_events'])}")
    return {"first_loss": res["first_loss"], "final_loss": res["final_loss"],
            "energy": res["energy"], "energy_dvo": res["energy_dvo"]}


def bench_serve():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import smoke_config
    from repro.core import RooflineTimeModel
    from repro.models import transformer as T
    from repro.serve import ServeConfig, ServingEngine
    cfg = smoke_config("olmo-1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    # decode on TPU is memory-bound: hand the engine that roofline so the
    # planner can take the free down-clock
    rt = RooflineTimeModel.from_counts(flops=1e9, hbm_bytes=8e9, coll_bytes=0)
    eng = ServingEngine(cfg, params,
                        ServeConfig(batch=2, max_len=256, window=8,
                                    planner="global", slack=1.1), roofline=rt)
    prompts = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab, (2, 32)), jnp.int32)}
    t0 = time.perf_counter()
    out = eng.generate(prompts, n_tokens=64)
    us = (time.perf_counter() - t0) * 1e6 / out["n_generated"]
    sav = 1 - out["energy"]["busy_j"] / max(out["energy_dvo"]["busy_j"], 1e-9)
    _row("serve_dvdvfs_smoke", us,
         f"tokens={out['n_generated']};energy=-{sav:.1%}")
    return {"energy": out["energy"], "energy_dvo": out["energy_dvo"]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the slow paper-figure measurements and cap "
                         "planner_scale at 10k blocks")
    ap.add_argument("--section", default=None,
                    help="run only one section (e.g. planner_scale, cluster)")
    ap.add_argument("--save", default="results/bench.json")
    args = ap.parse_args()

    sections = {
        "table1": (bench_table1, True),      # (runner, skipped by --quick)
        "fig6_10": (bench_fig6_10, True),
        "fig11_12": (bench_fig11_12, True),
        "fig13": (bench_fig13, True),
        "planners": (bench_planners, True),
        "planner_scale": (lambda: bench_planner_scale(quick=args.quick),
                          False),
        "pipeline": (lambda: bench_pipeline(quick=args.quick), False),
        "cluster": (bench_cluster, False),
        "runtime": (bench_runtime, False),
        "engine": (lambda: bench_engine(quick=args.quick), False),
        "obs": (lambda: bench_obs(quick=args.quick), False),
        "obs_cf": (lambda: bench_obs_cf(quick=args.quick), False),
        "calibrate": (lambda: bench_calibrate(quick=args.quick), False),
        "failures": (lambda: bench_failures(quick=args.quick), False),
        "serving": (lambda: bench_serving(quick=args.quick), False),
        "roofline": (bench_roofline, False),
        "train": (bench_train, False),
        "serve": (bench_serve, False),
    }
    if args.section is not None and args.section not in sections:
        raise SystemExit(f"unknown section: {args.section} "
                         f"(choose from {', '.join(sections)})")

    # stamped so compare.py can refuse to diff incompatible blobs and so a
    # saved artifact names the commit that produced it
    results = {"schema_version": SCHEMA_VERSION, "git_sha": _git_sha()}
    print("name,us_per_call,derived")
    for name, (runner, quick_skips) in sections.items():
        if args.section is not None and name != args.section:
            continue
        if args.section is None and args.quick and quick_skips:
            continue
        results[name] = runner()

    os.makedirs(os.path.dirname(args.save), exist_ok=True)
    with open(args.save, "w") as f:
        json.dump(results, f, indent=2, default=str)
    print(f"# saved -> {args.save} (schema v{SCHEMA_VERSION}, "
          f"{results['git_sha']})")


if __name__ == "__main__":
    main()
