"""chip_smoke.py off the chip: it must refuse, and its data must be
reproducible. What it does on the chip only a chip run shows."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_chip():
    """No accelerator: non-zero exit at the device phase, before any data
    is generated, and a last line that says so."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, SCRIPT], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "phase glm" not in proc.stdout and "data:" not in proc.stdout


def test_seeded_data_is_deterministic(tmp_path):
    cs = _load()
    paths = []
    for name in ("a", "b"):
        idx, lab = cs.glm_rows(300, seed=5)
        path = tmp_path / f"{name}.libsvm"
        cs.write_libsvm(str(path), idx, lab)
        paths.append(path)
    assert paths[0].read_bytes() == paths[1].read_bytes()
    first = paths[0].read_text().splitlines()[0].split()
    assert len(first) == 1 + cs.GLM_K and first[1] == f"{cs.GLM_DIM}:1"
    other, _ = cs.glm_rows(300, seed=6)
    assert (other != idx).any()
    assert idx.min() >= 0 and idx.max() < cs.GLM_DIM
    # the plain reference agrees with itself: zero loss gap at its own w
    loss, gnorm, gnorm0 = cs.glm_reference(idx, lab, np.zeros(cs.GLM_DIM))
    assert abs(loss - 300 * np.log(2.0)) < 1e-9 and gnorm == gnorm0 > 0
