"""Distribution must not change numerics: the same train step on a 1-device
mesh and a (2,4) mesh with ZeRO-3 sharding produces the same loss and
updated master params (up to collective reduction reassociation).

Runs in a subprocess (needs 8 fake devices before jax init)."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.parallel.compat import make_mesh
    from repro.configs import get_spec
    from repro.core.parallel_config import ZeROStage
    from repro.data.synthetic import config_for, make_batch
    from repro.launch.specs import batch_shardings
    from repro.models import build_model
    from repro.optim.adamw import init_train_state
    from repro.parallel.axes import axis_rules
    from repro.parallel.sharding import state_shardings
    from repro.train.loop import TrainConfig, make_train_step

    spec = get_spec("olmoe-1b-7b", smoke=True)   # MoE: exercises EP sharding
    model = build_model(spec)
    params = model.init(jax.random.PRNGKey(0))
    state = init_train_state(params)
    batch = make_batch(config_for(spec, 4, 32), 0)
    step = make_train_step(model, TrainConfig(n_micro=2))

    # single device
    s1, m1 = jax.jit(step)(state, batch)

    # 2x4 mesh, ZeRO os+g+params
    mesh = make_mesh((2, 4), ("data", "model"))
    abstract = jax.eval_shape(lambda: state)
    st_sh = state_shardings(abstract, mesh, ZeROStage.OS_G_PARAMS)
    b_sh = batch_shardings(jax.eval_shape(lambda: batch), mesh)
    with axis_rules(mesh):
        fn = jax.jit(step, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, None))
        s2, m2 = fn(jax.device_put(state, st_sh), jax.device_put(batch, b_sh))

    dl = abs(float(m1["loss"]) - float(m2["loss"]))
    assert dl < 5e-2, f"loss diverged: {dl}"
    worst = 0.0
    for a, b in zip(jax.tree.leaves(s1.master), jax.tree.leaves(s2.master)):
        worst = max(worst, float(jnp.abs(a - jax.device_get(b)).max()))
    assert worst < 5e-2, f"master params diverged: {worst}"
    print("MULTIDEV_OK", dl, worst)
""")


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "MULTIDEV_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
