"""The DV-DVFS layers import one way.

    core -> pipeline -> cluster -> runtime -> serving

with ``calibrate`` and the ``obs`` views above ``core``, and the LM stack
(``models``, ``train``, ``optim``, ``checkpoint``, ``configs``,
``parallel``, ``serve``, ``launch``) beside all of them: nothing in the
DV-DVFS layers imports it.

Each case imports one package in a fresh interpreter and checks that every
``repro`` subpackage it loaded is in that package's allowed lower set.  It
also reads the package's source, function-local imports included, for the
``repro`` packages it names; the only names past the allowed set are the
cycles ROADMAP D4 lists, spelled out in ``CYCLES``.
"""
import ast
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"

BASE = {"core", "obs"}
ALLOWED = {
    "core": BASE,
    "obs": {"obs"},
    "kernels": {"kernels"} | BASE,
    "apps": {"apps", "kernels"} | BASE,
    "data": {"data", "apps", "kernels"} | BASE,
    "pipeline": {"pipeline", "kernels"} | BASE,
    "calibrate": {"calibrate", "pipeline"} | BASE,
    "cluster": {"cluster", "calibrate", "pipeline"} | BASE,
    "runtime": {"runtime", "cluster", "calibrate", "pipeline"} | BASE,
    "serving": {"serving", "runtime", "cluster", "calibrate",
                "pipeline"} | BASE,
}
# function-local imports that point up a layer (ROADMAP D4)
CYCLES = {"calibrate": {"cluster"}, "cluster": {"runtime"}}
# the obs views read the runtime; only the recorder is below core
VIEWS = {"serving", "runtime", "cluster", "calibrate", "pipeline"}


def _loaded(package: str) -> set:
    code = (f"import sys, repro.{package}; "
            "print(' '.join(sorted({m.split('.')[1] for m in sys.modules "
            "if m.startswith('repro.')})))")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, check=True,
        env=dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu"))
    return set(out.stdout.split())


def _named(package: str) -> dict:
    """repro package -> the first file of ``package`` that imports it."""
    named = {}
    for path in sorted((SRC / "repro" / package).glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom) and node.module:
                mods = [node.module]
            elif isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            else:
                continue
            for mod in mods:
                parts = mod.split(".")
                if parts[0] == "repro" and len(parts) > 1:
                    named.setdefault(parts[1], path.name)
    return named


@pytest.mark.parametrize("package", sorted(ALLOWED))
def test_package_imports_only_its_lower_layers(package):
    loaded = _loaded(package)
    assert package in loaded
    assert loaded <= ALLOWED[package], sorted(loaded - ALLOWED[package])
    may_name = ALLOWED[package] | CYCLES.get(package, set())
    if package == "obs":
        may_name = may_name | BASE | VIEWS
    named = _named(package)
    stray = {p: f for p, f in named.items() if p not in may_name}
    assert not stray, stray
