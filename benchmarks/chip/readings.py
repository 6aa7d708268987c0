"""Readings that the limits of ``correct`` are set from.

  python3 benchmarks/chip/readings.py --workload lineitem-avg \\
      --seeds 11 12 13 ... [--control-seeds 11 12 13]

For each seed, in one process: the cell's set-up at its own size, one whole
job through the timed path (every block), then each number that ``check.py``
compares, for the program and for the control.  The control is the plain
reference put in the program's place and computed one precision lower
(``refs/<app>.control``, ``refs/estimate_<kind>.control_units``).  The lower
reading of a number is the largest the program gives over the seeds, the
upper reading the smallest the control gives.  One JSON line a seed, then a
summary line.  Needs the chip, as ``run.py`` does; ``--cpu-rehearsal`` runs
the tiny sizes on the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __name__ == "__main__":   # run from the checkout's root, as a script or -m
    _ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(_ROOT / "src"), str(_ROOT)]

import numpy as np  # noqa: E402

from benchmarks.chip import check, run  # noqa: E402
from benchmarks.chip.cells import load_cell, load_module, repo_root  # noqa: E402


def control_numbers(root, runner, seed: int) -> dict:
    """The app and estimate numbers with the control in the program's place."""
    import jax

    config, mix = runner.config, runner.mix
    app_ref = load_module(root, "refs", mix["app"])
    est_ref = load_module(root, "refs", f"estimate_{config['kind']}")
    produce = jax.jit(lambda blk: app_ref.control(blk, config))
    app_value = check.app_number(
        app_ref, runner.kind, runner.ds, config, runner.outputs, seed,
        produce=lambda blk: jax.device_get(produce(blk)))
    blocks = est_ref.check_blocks(runner.n_blocks, seed)
    want = est_ref.expected_units(runner.ds, config, mix, seed, blocks)
    got = est_ref.control_units(runner.ds, config, mix, seed, blocks)
    est_value = float(np.max(np.abs(got - want) / np.abs(want)))
    return {app_ref.NUMBER: app_value, est_ref.NUMBER: est_value}


def reading(root, cell, config, seed: int, control: bool) -> dict:
    runner = run.setup(root, cell, config, seed, traced=False,
                       log=lambda m: print(m, file=sys.stderr, flush=True))
    runner.jobs.append(runner.job(0))
    out = {"seed": seed,
           "program": {n: v for n, v, _ in check.numbers(root, runner, seed)}}
    if control:
        out["control"] = control_numbers(root, runner, seed)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    root = repo_root()
    cell = load_cell(root, args.workload)

    import jax

    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.cpu_rehearsal else "tpu"):
        print(f"readings need {'the CPU' if args.cpu_rehearsal else 'a TPU'},"
              f" JAX found {platform!r}", file=sys.stderr)
        return 1
    if not args.cpu_rehearsal:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    config = run.cell_config(cell, args.cpu_rehearsal)
    rows = []
    for seed in args.seeds:
        row = reading(root, cell, config, seed, seed in args.control_seeds)
        rows.append(row)
        print(json.dumps(row), flush=True)
    lower = {n: max(r["program"][n] for r in rows) for n in rows[0]["program"]}
    ctl = [r["control"] for r in rows if "control" in r]
    upper = {n: min(c[n] for c in ctl) for n in ctl[0]} if ctl else {}
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
