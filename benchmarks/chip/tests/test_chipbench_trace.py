"""The trace reduction: busy and idle union, program and kernel time, and
idle time split by the host span it falls in."""
import pytest

from benchmarks.chip.trace import digest, op_name, reduce

NS = 1e-9


def _ev(name, start, dur, **stats):
    return [name, float(start), float(dur), stats]


def _planes():
    """Window [0, 100); device ops at [10, 30) and [20, 40) (overlapping),
    [60, 70); host spans estimate [0, 50), app [50, 80), plan [80, 100)."""
    device = {"name": "/device:TPU:0", "lines": {
        "XLA Ops": [_ev("fusion.1", 10, 20, hlo_module="jit_bench_app_x"),
                    _ev("block_stats.1", 20, 20,
                        hlo_module="jit_block_stats_batched"),
                    _ev("pad.0", 40, 0, hlo_module="jit_block_stats_batched"),
                    _ev("scatter.2", 60, 10, hlo_module="jit_bench_app_x"),
                    _ev("late", 150, 5)],
        "XLA Modules": [_ev("jit_bench_app_x(7)", 10, 20),
                        _ev("jit_bench_app_x(7)", 60, 10)],
        "Steps": [_ev("0", 0, 100)]}}
    host = {"name": "/host:CPU", "lines": {"python": [
        _ev("window", 0, 100), _ev("estimate", 0, 50), _ev("app", 50, 30),
        _ev("plan", 80, 20), _ev("PjitFunction(x)", 50, 1)]}}
    return [device, host, {"name": "/host:metadata", "lines": {}}]


def test_busy_union_programs_kernels():
    s = reduce(_planes())
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(100 * NS)
    assert s.busy_s == pytest.approx(40 * NS)     # [10, 40) and [60, 70)
    assert s.program("bench_app_x") == (2, pytest.approx(30 * NS))
    assert s.kernel("block_stats") == (1, pytest.approx(20 * NS))
    assert s.program("absent") == (0, 0.0)


# the TPU profiler names an op event by its whole HLO instruction
TPU_KERNEL = ("%block_stats.1 = (s32[24,8,256]{2,1,0:T(8,128)S(1)}) "
              "custom-call(s32[24]{0:T(128)S(1)} %copy-done), "
              "custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("event,name", [
    ("block_stats.1", "block_stats.1"),
    (TPU_KERNEL, "block_stats.1"),
    ("%copy = s32[24,6554,256]{2,1,0:T(8,128)} copy(s32[24,6554,256] %t)",
     "copy"),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", "fusion.3"),
])
def test_op_name(event, name):
    assert op_name(event) == name


def test_kernel_named_by_its_whole_instruction():
    planes = _planes()
    ops = planes[0]["lines"]["XLA Ops"]
    ops[1] = _ev(TPU_KERNEL, 20, 20)
    ops.append(_ev("%block_stats_other = f32[8]{0} fusion()", 60, 5))
    s = reduce(planes)
    assert s.kernel("block_stats") == (1, pytest.approx(20 * NS))


def test_idle_split_by_host_span():
    s = reduce(_planes())
    # idle: [0, 10) and [40, 50) in estimate, [50, 60) and [70, 80) in app,
    # [80, 100) in plan
    assert s.idle_by_span == pytest.approx({"estimate": 20 * NS,
                                            "app": 20 * NS, "plan": 20 * NS})
    assert sum(s.idle_by_span.values()) + s.busy_s == pytest.approx(s.window_s)
    b = s.breakdown()
    assert b["device_ops"][0][0].endswith("fusion.1")
    assert len(b["idle_gaps"]) == 3 and len(b["device_ops"]) <= 10


def test_uncovered_idle_and_digest():
    planes = _planes()
    planes[1]["lines"]["python"] = [e for e in planes[1]["lines"]["python"]
                                    if e[0] != "plan"]
    d = digest(planes)
    assert {ln for p in d for ln in p["lines"]} == {"XLA Ops", "XLA Modules",
                                                    "python"}
    assert all(e[0] != "PjitFunction(x)" for p in d
               for evs in p["lines"].values() for e in evs)
    s = reduce(d)
    assert s.idle_by_span["none"] == pytest.approx(20 * NS)


def test_no_device_plane_reads_nothing():
    assert reduce([{"name": "/host:CPU", "lines": {}}]) is None


def test_window_off_the_device_clock_is_an_error():
    planes = _planes()
    planes[1]["lines"]["python"][0] = _ev("window", 1000, 100)
    with pytest.raises(ValueError):
        reduce(planes)


def test_recorded_tpu_trace():
    """The digest of a traced ``text-wordcount`` run on one TPU v5e (51 s
    window that closed on the clock, seed 1938270305): the reduction gives
    the ``busy_s`` and ``window_s`` that run printed, one ``block_stats``
    kernel call a job's estimate and one WordCount program a map task."""
    import gzip
    import json
    from pathlib import Path

    path = Path(__file__).with_name("tpu_trace_text.json.gz")
    with gzip.open(path, "rt") as f:
        s = reduce(json.load(f))
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(51.93674824)
    assert s.busy_s == pytest.approx(39.109747921)
    assert s.kernel("block_stats") == (7, pytest.approx(0.003412183))
    assert s.program("bench_app_wordcount")[0] == 144
    assert s.program("block_stats_batched")[0] == 7
    assert max(s.idle_by_span, key=s.idle_by_span.get) == "estimate"
    assert sum(s.idle_by_span.values()) + s.busy_s == pytest.approx(s.window_s)
