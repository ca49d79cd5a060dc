"""The four-chip cell's harness at a small size on four CPU devices: a
sound run is correct, one with the all-to-alls between the expert shards
left out is not.  Needs the devices before JAX starts, so it runs in a
child process."""

import os
import subprocess
import sys
import textwrap

from bench import cells

SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
    import jax
    from conftest import tiny_cell
    from bench import faults, run
    cell = tiny_cell("moe4")
    assert len(jax.devices()) == 4 and faults.applies("no_exchange",
                                                      cell.config)
    out = run.run_cell(cell, 2**31 + 12345, 0.5, False, jax.devices())
    assert out["correct"], out["check"]
    assert out["device"]["count"] == 4
    out = run.run_cell(cell, 2**31 + 12345, 0.5, False, jax.devices(),
                       step_wrapper=faults.no_exchange)
    assert not out["correct"], out["check"]
    print("OK")
""")


def test_four_devices_sound_and_no_exchange():
    root = cells.ROOT
    code = SCRIPT.format(root=str(root), src=str(root / "src"),
                         tests=str(root / "tests" / "bench"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-4000:]
