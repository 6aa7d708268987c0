"""The comparison that decides ``correct``: what the window produced against
plain references that import nothing of the program.

  app        every map task of the window against ``refs/<app>.py``;
  estimate   every estimate of the window against
             ``refs/estimate_<kind>.py`` on the blocks it checks;
  plan       every completed job's states on the frozen ladder, and its
             modelled time on its own estimates within the deadline.

Each number has a limit; the run is correct when every number is within
its limit and something was compared.
"""
from __future__ import annotations

import numpy as np

__all__ = ["app_number", "estimate_number", "numbers"]


def app_number(ref, kind, ds, config, outputs: dict, seed: int,
               produce=None) -> float:
    """``ref.NUMBER`` over every output in ``outputs`` (block -> list).

    ``produce(block)`` replaces the outputs with what another path (the
    control) gives for the same blocks."""
    values = []
    for b, outs in sorted(outputs.items()):
        blk = kind.block(ds, b)
        want = ref.expected(blk, config)
        if produce is not None:
            outs = [produce(blk)]
        values.extend(ref.compare(o, want) for o in outs)
    if not values:
        return float("inf")
    return float(sum(values) if ref.AGG == "sum" else max(values))


def estimate_number(ref, ds, config, mix, seed: int, units_list) -> float:
    """Largest relative gap of the estimates' units on the checked blocks."""
    if not units_list:
        return float("inf")
    n = len(units_list[0])
    blocks = ref.check_blocks(n, seed)
    want = ref.expected_units(ds, config, mix, seed, blocks)
    got = np.stack([np.asarray(u)[blocks] for u in units_list])
    return float(np.max(np.abs(got - want[None]) / np.abs(want[None])))


def numbers(root, runner, seed: int) -> list:
    """[(name, value, limit)] of one run."""
    from benchmarks.chip.cells import load_module

    config, mix = runner.config, runner.mix
    app_ref = load_module(root, "refs", mix["app"])
    est_ref = load_module(root, "refs", f"estimate_{config['kind']}")
    done = [j for j in runner.jobs if j.error is None]
    out = [(app_ref.NUMBER, app_number(app_ref, runner.kind, runner.ds, config,
                                       runner.outputs, seed), app_ref.LIMIT),
           (est_ref.NUMBER, estimate_number(
               est_ref, runner.ds, config, mix, seed,
               [u.total for u in runner.estimates]), est_ref.LIMIT),
           ("plan_off_ladder", float(sum(j.acct["off_ladder"] for j in done)),
            0),
           ("plan_over_deadline",
            float(sum(j.acct["over_deadline"] for j in done)), 0)]
    return out
