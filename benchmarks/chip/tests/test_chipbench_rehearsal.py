"""Every cell runs end to end at tiny sizes on the CPU (Pallas interpreted)
and prints a last line of the contract's shape; without the rehearsal flag a
CPU backend exits non-zero with no result; a cell defined only by new files
runs with no edit to the harness."""
import json
import shutil

import pytest

from benchmarks.chip import run
from benchmarks.chip.cells import BENCH_DIR, repo_root

ROOT = repo_root()
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def _last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def _rehearse(capsys, workload, *extra, root=None) -> dict:
    rc = run.main(["--workload", workload, "--seed", str(2**31 + 17),
                   "--seconds", "1", "--cpu-rehearsal", *extra], root=root)
    assert rc == 0
    return _last_line(capsys)


def _expected(kind: str, workload: str, spec=SPEC) -> set:
    return {m["name"] for m in spec[kind]
            if "workloads" not in m or workload in m["workloads"]}


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_end_to_end(capsys, workload):
    res = _rehearse(capsys, workload, "--trace", "0")
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["attempted"] >= 1
    assert set(res["metrics"]) == _expected("end_to_end", workload)
    # a CPU run never prints a number under a device metric's name
    assert all(m["value"] is None for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


def test_traced_rehearsal_reads_host_metrics(capsys):
    res = _rehearse(capsys, "text-wordcount", "--trace", "1")
    names = set(res["metrics"])
    assert {"estimate_ms", "plan_ms", "stage_gb_s", "estimate_mape"} <= names
    # no device plane on the CPU: the trace readers report nothing
    assert not names & {"app_roofline", "block_stats_roofline", "device_idle"}
    assert res["correct"] is True


def test_no_tpu_exits_nonzero_without_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


def test_cell_defined_only_by_new_files(capsys, tmp_path):
    """A new configuration, mix and metric: files plus BENCHMARK.json entries."""
    shutil.copytree(ROOT / BENCH_DIR, tmp_path / BENCH_DIR,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = tmp_path / BENCH_DIR
    cfg = json.loads((bench / "configs" / "hibench-text-large.json").read_text())
    cfg.update(name="text-flat", variety_z=0.0)
    (bench / "configs" / "text-flat.json").write_text(json.dumps(cfg))
    (bench / "mixes" / "flat-wordcount-loose.json").write_text(json.dumps(
        {"app": "wordcount", "slack": 1.5, "calibration_blocks": 3}))
    (bench / "metrics" / "tasks_per_job.py").write_text(
        "def read(run):\n"
        "    return len(run.tasks) / max(len(run.jobs), 1)\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "text-flat", "source": "x",
                            "file": f"{BENCH_DIR}/configs/text-flat.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "flat-wordcount", "config": "text-flat",
                              "traffic": "flat-wordcount-loose", "chips": 1,
                              "why": "x"})
    spec["end_to_end"].append({"name": "tasks_per_job", "unit": "tasks",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["flat-wordcount"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    res = _rehearse(capsys, "flat-wordcount", "--trace", "0", root=tmp_path)
    assert res["correct"] is True
    assert set(res["metrics"]) == _expected("end_to_end", "flat-wordcount",
                                            spec)
    assert "tasks_per_job" in res["metrics"]


def test_window_runs_whole_jobs():
    """The last job started runs to its end, so every job counts each block
    once, and the window's length is read after it."""
    from benchmarks.chip.cells import load_cell

    cell = load_cell(ROOT, "text-wordcount")
    runner = run.setup(ROOT, cell, run.cell_config(cell, rehearsal=True),
                       seed=5, traced=False, log=lambda m: None)
    runner.window(0.01)
    assert len(runner.jobs) >= 1
    assert len(runner.tasks) == len(runner.jobs) * runner.n_blocks
    assert runner.window_s >= 0.01
    assert runner.window_s >= runner.tasks[-1].t_done - runner.jobs[0].t0
