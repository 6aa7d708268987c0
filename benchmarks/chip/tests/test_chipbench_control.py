"""``correct`` separates the program from its control and from faults.

The control is the plain reference put in the program's place and computed
one precision lower (bfloat16 for float32 and for exact int32 counts,
float32 for the float64 sampler): it must fail a limit.  Each fault a cell
can have, planted in the timed path under a CPU rehearsal run, must make
``correct`` false.  The control has to fail one of a cell's numbers, not
each: an AVG computed from bfloat16 values stays within TPC-H's 1 % on the
chip.  (One chip: there is no exchange between chips to leave
out.)
"""
import dataclasses
import json

import jax.numpy as jnp
import pytest

import repro.pipeline
from benchmarks.chip import check, readings, run
from benchmarks.chip.cells import load_cell, repo_root
from repro.apps import ALL_APPS, Average, WordCount
from repro.kernels import ops

ROOT = repo_root()
CELLS = ("text-wordcount", "lineitem-avg")
# a size above the rehearsal's, where the control's float32 estimate is not
# exact: at 512 rows a block, 26 sampled costs of 6 or 38 units are summed
# exactly and scaled by a whole factor, as at any size that k divides
CONTROL_SIZES = {"lineitem-avg": {"records_per_block": 99_991}}


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_its_limits(workload):
    cell = load_cell(ROOT, workload)
    config = {**run.cell_config(cell, rehearsal=True),
              **CONTROL_SIZES.get(workload, {})}
    runner = run.setup(ROOT, cell, config, seed=23, traced=False,
                       log=lambda m: None)
    runner.jobs.append(runner.job(0))
    rows = check.numbers(ROOT, runner, 23)
    limits = {n: lim for n, _, lim in rows}
    program = {n: v for n, v, _ in rows}
    control = readings.control_numbers(ROOT, runner, 23)
    assert all(program[n] <= limits[n] for n in program)
    assert any(value > limits[name] for name, value in control.items()), \
        (control, limits)


class WordCountUnchanged(WordCount):
    """Returns its accumulator as it started."""
    def run(self, block):
        return jnp.zeros((self.vocab,), jnp.int32)


class WordCountHalf(WordCount):
    def run(self, block):
        toks = block["tokens"]
        return super().run({"tokens": toks[:toks.shape[0] // 2]})


class WordCountAltered(WordCount):
    def run(self, block):
        return super().run(block).at[17].add(1)


class AverageUnchanged(Average):
    """Returns its per-group means as they started."""
    def run(self, block):
        return jnp.zeros((self.n_groups,), jnp.float32)


class AverageHalf(Average):
    """The mean taken over half of the block's rows."""
    def run(self, block):
        n = block["values"].shape[0] // 2
        return super().run({k: v[:n] for k, v in block.items()})


class AverageAltered(Average):
    def run(self, block):
        return super().run(block).at[0].multiply(1.05)


def _half_sample_kernel(orig):
    def kernel(tokens, lengths=None, pattern=(17, 23, 5), **kw):
        return orig(tokens, lengths // 2, pattern, **kw)
    return kernel


def _off_ladder_planner(orig):
    def plan(*args, **kw):
        p = orig(*args, **kw)
        return dataclasses.replace(p, rel_freq=p.rel_freq * 0.97)
    return plan


FAULTS = {
    "wordcount-unchanged": ("text-wordcount", "app", "wordcount",
                            WordCountUnchanged),
    "wordcount-half-batch": ("text-wordcount", "app", "wordcount",
                             WordCountHalf),
    "wordcount-altered": ("text-wordcount", "app", "wordcount",
                          WordCountAltered),
    "avg-unchanged": ("lineitem-avg", "app", "avg", AverageUnchanged),
    "avg-half-batch": ("lineitem-avg", "app", "avg", AverageHalf),
    "avg-altered": ("lineitem-avg", "app", "avg", AverageAltered),
    "estimate-half-sample": ("text-wordcount", "kernel", None, None),
    "plan-off-ladder": ("lineitem-avg", "planner", None, None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_timed_path_is_not_correct(capsys, monkeypatch, fault):
    workload, where, app, cls = FAULTS[fault]
    if where == "app":
        monkeypatch.setitem(ALL_APPS, app, cls)
    elif where == "kernel":
        monkeypatch.setattr(ops, "block_stats_batched",
                            _half_sample_kernel(ops.block_stats_batched))
    else:
        monkeypatch.setattr(repro.pipeline, "plan_estimates",
                            _off_ladder_planner(repro.pipeline.plan_estimates))
    rc = run.main(["--workload", workload, "--seed", "99", "--seconds", "0.5",
                   "--trace", "0", "--cpu-rehearsal"])
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False
    broken = [n for n, c in res["checks"].items()
              if c["value"] is None or c["value"] > c["limit"]]
    assert broken, res["checks"]


def test_refs_import_nothing_of_the_program():
    for group, name in (("refs", "wordcount"), ("refs", "avg"),
                        ("refs", "estimate_text"),
                        ("refs", "estimate_lineitem")):
        src = (ROOT / "benchmarks" / "chip" / group / f"{name}.py").read_text()
        assert "import repro" not in src and "from repro" not in src, name
