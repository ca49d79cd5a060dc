"""``chip_smoke.py`` refuses the CPU, and its CPU rehearsal passes.

The rehearsal runs every phase of the chip smoke at smoke widths with the
kernels in interpret mode — one device for the default phases, four
virtual devices for the ``--chips 4`` legs — so a broken path or argument
shows up here before it costs chip time.  Each run keeps its compile cache
in ``JAX_COMPILATION_CACHE_DIR``; the rehearsal also checks that the
entries land there.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(tmp_path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        env=env, capture_output=True, text=True, timeout=560, cwd=ROOT)


def test_refuses_cpu(tmp_path):
    r = _smoke(tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "not a TPU" in r.stderr


def test_refuses_without_the_repo(tmp_path):
    """Alone in a directory, the script has no program to drive."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(ROOT, "chip_smoke.py")).read())
    r = subprocess.run([sys.executable, str(lone)], capture_output=True,
                       text=True, timeout=120, cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=""))
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


@pytest.mark.parametrize("chips", [1, 4])
def test_cpu_rehearsal_passes(tmp_path, chips):
    r = _smoke(tmp_path, "--rehearse", "--chips", str(chips))
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"rehearsal_ok": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": chips}}
    if chips == 1:
        assert "step-0 loss pallas=" in r.stdout
        assert "peak_bytes_in_use=" in r.stdout
    else:
        for leg in ("dense pp2·tp2·sp 1f1b", "dense pp2·tp2·sp zb1p",
                    "moe pp1·dp2·tp2·ep2 os+g"):
            assert f"[{leg}]" in r.stdout
    assert any((tmp_path / "cache").iterdir()), \
        "no compile-cache entry landed in JAX_COMPILATION_CACHE_DIR"
