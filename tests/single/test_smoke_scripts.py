"""The two scripts that stand on their own: ``chip_smoke.py`` (bring-up
on the chip) still starts, and nothing below a top-level script imports
one (a 1,600-line benchmark script was once kept alive as a library for
``chip_smoke.py`` and ``common/wire_smoke.py``)."""

import ast
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Top-level scripts: programs to run, never modules to import.
SCRIPTS = {"bench", "chip_smoke"}


def test_chip_smoke_rehearsal_passes(tmp_path):
    """``chip_smoke.py --rehearse`` — every one-chip phase at tiny sizes
    on the CPU, from the files of this checkout alone — exits 0 and ends
    with the rehearsal's line, which can never read ``"ok": true``."""
    # The script's own environment, not this worker's: conftest's eight
    # virtual devices, and whatever rank layout an earlier test left in
    # os.environ (test_runner's launcher translation leaves HOROVOD_SIZE=8).
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"
           and not k.startswith(("HOROVOD_", "OMPI_", "SLURM_"))}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rehearse"], cwd=REPO, env=env, text=True,
        capture_output=True, timeout=600)
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-3000:])
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["rehearsal"] == "passed", last
    assert last["device"]["platform"] == "cpu", last


def _imported_scripts(path):
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            found.add((node.module or "").split(".")[0])
    return found & SCRIPTS


@pytest.mark.parametrize("where", [
    "chip_smoke.py", "horovod_tpu/common/wire_smoke.py",
    "__graft_entry__.py", "horovod_tpu"])
def test_nothing_imports_a_top_level_script(where):
    path = os.path.join(REPO, where)
    files = [path] if os.path.isfile(path) else [
        os.path.join(d, f) for d, _, fs in os.walk(path)
        for f in fs if f.endswith(".py")]
    assert files
    bad = {os.path.relpath(f, REPO): sorted(found)
           for f in files if (found := _imported_scripts(f))}
    assert not bad, f"imports of a top-level script: {bad}"
