"""Run one cell of ``BENCHMARK.json`` on one TPU chip.

  python3 benchmarks/chip/run.py --workload text-wordcount --seed 7 \\
      --seconds 30 --trace 0

Set-up draws the cell's dataset on the device from ``--seed`` and copies it
to host memory once, fits the estimate's ``CostModel`` on three fully
measured blocks (which also compiles every program the window runs, or
loads it from the persistent compile cache), then a window runs DV-DVFS
jobs back to back, starting jobs for ``--seconds`` and running the last one
to its end (``job.py``).  After the window the outputs
are checked against plain references (``check.py``) and one JSON line is
printed: the end-to-end metrics with ``--trace 0``, the per-layer metrics
from a profiler trace of the window with ``--trace 1``.

``--cpu-rehearsal`` runs the cell at a tiny size on the CPU with the Pallas
kernels interpreted, and prints no metric values.  Without it, a run that
finds no TPU, or fewer chips than the cell asks for, exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from process start

import argparse  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

if __name__ == "__main__":   # run from the checkout's root, as a script or -m
    _ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(_ROOT / "src"), str(_ROOT)]

import numpy as np  # noqa: E402

from benchmarks.chip.cells import (load_cell, load_json, load_module,  # noqa: E402
                                   repo_root)

# sizes of the CPU rehearsal; every other key keeps the configuration's value
REHEARSAL = {"blocks": 6, "records_per_block": 512}
CALIBRATION_REPEATS = 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _CompileCounter:
    """Counts backend compilations; one listener for the process."""

    listener = None

    def __init__(self):
        self.count = 0

    @classmethod
    def install(cls) -> "_CompileCounter":
        import jax

        if cls.listener is None:
            cls.listener = cls()
            jax.monitoring.register_event_duration_secs_listener(
                lambda name, secs, **kw: cls.listener._seen(name))
        return cls.listener

    def _seen(self, name: str):
        if name == COMPILE_EVENT:
            self.count += 1


def _named(fn, name: str):
    """``fn`` under a stable name, so its program is ``jit_<name>``."""
    def wrapper(block):
        return fn(block)
    wrapper.__name__ = wrapper.__qualname__ = name
    return wrapper


def app_program_name(app: str) -> str:
    return f"bench_app_{app}"


class Run:
    """What the metric readers see (``metrics/<name>.py``: ``read(run)``)."""

    def __init__(self, *, root, cell, config, runner, setup_s, seconds,
                 trace, peaks):
        self.root, self.cell, self.config = root, cell, config
        self.mix = cell.mix
        self.runner = runner
        self.setup_s, self.seconds = setup_s, seconds
        self.window_s = runner.window_s
        self.trace, self.peaks = trace, peaks
        self.tasks = runner.tasks
        self.jobs = [j for j in runner.jobs if j.error is None]

    def module(self, group: str, name: str):
        return load_module(self.root, group, name)

    @property
    def app(self) -> str:
        return self.mix["app"]

    @property
    def app_program(self) -> str:
        return app_program_name(self.app)

    def sample_block(self) -> dict:
        return self.runner.kind.block(self.runner.ds, 0)

    def kernel_shapes(self) -> dict:
        return self.runner.kind.kernel_shapes(self.config,
                                              self.runner.pipeline_config)


def cell_config(cell, rehearsal: bool) -> dict:
    config = dict(cell.config)
    if rehearsal:
        config.update({k: v for k, v in REHEARSAL.items() if k in config})
    return config


def setup(root: Path, cell, config: dict, seed: int, traced: bool,
          log=print):
    """Dataset, calibration and warm-up: a ``Runner`` ready for the window."""
    import jax
    from repro.apps import ALL_APPS
    from repro.core.estimator import CostModel
    from repro.pipeline import PipelineConfig

    from benchmarks.chip.job import Runner

    kind = load_module(root, "kinds", config["kind"])
    app_name = cell.mix["app"]
    app = ALL_APPS[app_name]()
    app_fn = jax.jit(_named(app.run, app_program_name(app_name)))
    pipe = PipelineConfig(seed=seed)

    t = time.perf_counter()
    ds = kind.generate(config, seed)
    log(f"[setup] dataset {config['name']}: {kind.n_blocks(ds)} blocks of "
        f"{kind.block_bytes(ds)} B drawn on the device and copied to host "
        f"in {time.perf_counter() - t:.3f} s")
    runner = Runner(kind=kind, ds=ds, config=config, mix=cell.mix, app=app,
                    app_fn=app_fn, cost_model=None, pipeline_config=pipe,
                    power=load_json(root, "power.json"), traced=traced)

    # the estimate once over the dataset: compiles its kernel and gives the
    # calibration blocks' cost units
    t = time.perf_counter()
    units = kind.estimate(ds, config, pipe, app)
    log(f"[setup] estimate pass {time.perf_counter() - t:.3f} s")

    # the paper's calibration: 3 fully measured blocks (first, middle, last)
    n = runner.n_blocks
    calib = sorted({0, n // 2, n - 1})[:cell.mix["calibration_blocks"]]
    times = []
    for b in calib:
        runner.map_task(-1, b)  # compiles (or loads) the app's program
        times.append(float(np.median([runner.map_task(-1, b)[0].app_s
                                      for _ in range(CALIBRATION_REPEATS)])))
    runner.cost_model = CostModel(("units", "const")).fit(
        [{"units": float(units.total[b]), "const": 1.0} for b in calib], times)
    log(f"[setup] calibration blocks {calib}: units "
        f"{[float(units.total[b]) for b in calib]}, app {times} s at f_max; "
        f"cost model weights {runner.cost_model.weights.tolist()}")
    return runner


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None, root: Path | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", type=Path, default=None,
                    help="with --trace 1, also write the trace's digest "
                         "(trace.digest) to this .json.gz file")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU, kernels interpreted, no "
                         "metric values")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    root = Path(root) if root is not None else repo_root()
    cell = load_cell(root, args.workload)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if args.cpu_rehearsal and dev.platform != "cpu":
        _stderr(f"--cpu-rehearsal needs the CPU backend, found {dev.platform}")
        return 2
    if not args.cpu_rehearsal and dev.platform != "tpu":
        _stderr(f"no TPU: JAX found platform {dev.platform!r} "
                f"({dev.device_kind}); --cpu-rehearsal runs the tiny CPU "
                "rehearsal")
        return 1
    if len(devices) < cell.chips:
        _stderr(f"{cell.name} needs {cell.chips} chips, JAX found "
                f"{len(devices)}")
        return 1
    peaks = None
    if not args.cpu_rehearsal:
        table = load_json(root, "peaks.json")
        if dev.device_kind not in table:
            _stderr(f"no peaks for device kind {dev.device_kind!r} in "
                    "peaks.json")
            return 1
        peaks = table[dev.device_kind]
        from repro.launch.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        _stderr(f"[setup] compile cache {cache}")

    config = cell_config(cell, args.cpu_rehearsal)
    runner = setup(root, cell, config, args.seed, bool(args.trace),
                   log=_stderr)
    setup_s = time.perf_counter() - T_START
    _stderr(f"[setup] {setup_s:.3f} s from process start")

    compiles = _CompileCounter.install()
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") \
        if args.trace else None
    summary = None
    try:
        if trace_dir:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_trace_options())
        before = compiles.count
        runner.window(args.seconds)
        in_window = compiles.count - before
        if trace_dir:
            jax.profiler.stop_trace()
            from benchmarks.chip.trace import load_xplane, reduce
            files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
            planes = load_xplane(files[-1]) if files else []
            summary = reduce(planes)
            if args.save_trace:
                from benchmarks.chip.trace import digest
                with gzip.open(args.save_trace, "wt") as f:
                    json.dump(digest(planes), f)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    _stderr(f"[window] {len(runner.jobs)} jobs, {len(runner.tasks)} map "
            f"tasks in {runner.window_s:.3f} s (jobs started for "
            f"{args.seconds} s); compilations in the window: {in_window}")

    stats = dev.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    gc.collect()

    from benchmarks.chip.check import numbers
    checks = numbers(root, runner, args.seed)
    correct = all(v <= lim for _, v, lim in checks)

    run = Run(root=root, cell=cell, config=config, runner=runner,
              setup_s=setup_s, seconds=args.seconds, trace=summary,
              peaks=peaks)
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = load_module(root, "metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {
                "value": None if args.cpu_rehearsal else float(value),
                "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": len(runner.jobs),
              "failed": sum(j.failed for j in runner.jobs),
              "metrics": metrics, "device": device}
    if args.trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    # a number that is not finite (nothing was compared) prints as null
    result["checks"] = {name: {"value": v if np.isfinite(v) else None,
                               "limit": lim} for name, v, lim in checks}
    for name, v, lim in checks:
        _stderr(f"check {name} {v!r} limit {lim!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
