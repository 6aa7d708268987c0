"""``chip_smoke.py`` end to end on the CPU: ``--cpu-rehearsal`` runs every
phase at tiny sizes with the Pallas kernels interpreted, and without it a
machine with no TPU is refused."""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # its dataclass resolves the module
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules["chip_smoke"]


def test_cpu_rehearsal_runs_every_phase(chip_smoke, tmp_path, monkeypatch,
                                        capsys):
    # JAX reads the variable only at import: set now, it keeps the run
    # from pointing the cache at the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    rc = chip_smoke.main(["--cpu-rehearsal", "--ckpt-dir",
                          str(tmp_path / "ck")])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    text = "\n".join(lines)
    for app in ("wordcount", "grep", "inverted_index", "avg", "sum"):
        assert f"[phase1] {app}: oracle ok" in text
    assert "stream_run over 4 simulated nodes" in text
    assert "all finite" in text and "equal the plain decode loop" in text
    assert not (tmp_path / "ck").exists()


def test_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err and "'cpu'" in err
    assert out == ""
