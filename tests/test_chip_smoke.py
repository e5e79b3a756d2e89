"""``chip_smoke.py``: refuses to run without a TPU, and its phases are right.

The script's phases run here on the CPU at a few thousand rows (kernels
interpreted), so a change to the service API or the reference comparison
shows up before a chip run.  The ``tpu_custom_call`` check cannot pass on
the CPU; it is replaced by a lowering of the same dispatch.
"""

import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(script, cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_contract(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "ok" in json.loads(lines[-1])
    except ValueError:
        return False


def test_refuses_without_tpu():
    out = _run(SCRIPT, ROOT)
    assert out.returncode != 0
    assert not _printed_contract(out.stdout), out.stdout[-500:]
    assert "no TPU" in out.stderr


def test_fails_outside_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert out.returncode != 0
    assert not _printed_contract(out.stdout), out.stdout[-500:]


@pytest.fixture(scope="module")
def smoke():
    return _load()


@pytest.fixture
def lowered_only(smoke, monkeypatch):
    """Swap the chip-only kernel check for a lowering of the dispatch."""
    seen = []

    def check_kernel(svc, name, label, **kw):
        seen.append(svc.lower(name, batch=smoke.MAX_BATCH, **kw).as_text())
        return 0.0

    monkeypatch.setattr(smoke, "check_kernel", check_kernel)
    return seen


@pytest.mark.parametrize("phase", ["hamming", "l1", "ternary", "sharded",
                                   "launcher"])
def test_phase_matches_ref_on_cpu(smoke, lowered_only, monkeypatch, phase):
    rng = np.random.default_rng(3)
    if phase == "hamming":
        smoke.phase_nearest(rng, 1024, 64, distance="hamming", ks=(10, 100))
    elif phase == "l1":
        smoke.phase_nearest(rng, 512, 32, distance="l1", ks=(10,))
    elif phase == "ternary":
        smoke.phase_ternary(rng, 1024, 64)
    elif phase == "sharded":
        smoke.phase_sharded(rng, 2048, len(jax.devices()),
                            waves=((64, 10), (16, 64)))
    else:
        # tests never turn the persistent compilation cache on
        monkeypatch.setattr("repro.launch.serve.enable_compile_cache",
                            lambda: None)
        smoke.phase_launcher(rng)
    assert lowered_only and all(lowered_only)


def test_check_responses_catches_a_wrong_row(smoke):
    from repro.core import am
    from repro.serve import AMService
    rng = np.random.default_rng(4)
    codes = rng.integers(0, 8, (256, smoke.WIDTH)).astype(np.int32)
    svc = AMService()
    svc.create_table("t", width=smoke.WIDTH, capacity=256, backend="pallas")
    svc.append("t", codes)
    queries = codes[:4]
    responses = [svc.lookup("t", q, k=3) for q in queries]
    want = smoke.ref_search(am.make_table(codes), queries, k=3)
    smoke.check_responses("same", responses, want)
    want["indices"][2, 1] += 1
    with pytest.raises(AssertionError, match="lookup 2 field 'indices'"):
        smoke.check_responses("mutated", responses, want)
