"""Schedule-driven pipeline executor vs the pp=1 train loop: same loss and
post-update master params within bf16-accumulation tolerance on fake-device
meshes, for every schedule (1f1b / interleaved / dualpipe) at pp ∈ {2, 4}.

Needs >1 fake device set before jax initialises — subprocess with XLA_FLAGS.
"""

import os
import subprocess
import sys
import textwrap

import pytest

DENSE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.parallel.compat import make_mesh
    from repro.configs import get_spec
    from repro.data.synthetic import config_for, make_batch
    from repro.models import build_model
    from repro.optim.adamw import init_train_state
    from repro.train.loop import TrainConfig, make_train_step
    from repro.train.pipeline_loop import make_pipeline_train_step

    SCHEDULE = {schedule!r}
    N_CHUNKS = {n_chunks}
    # interleaved pp=4 needs pp*v=8 model chunks -> 8 layers
    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 8, 32), 0)
    # loss mask exercises the masked-CE path on both executors
    batch["mask"] = jnp.broadcast_to(
        (jnp.arange(32) < 28).astype(jnp.float32)[None], (8, 32))
    s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=4)))(state, batch)

    for pp, data in [(2, 2), (4, 2)]:
        mesh = make_mesh((pp, data), ("pipe", "data"))
        step = make_pipeline_train_step(model, TrainConfig(n_micro=4), mesh,
                                        schedule=SCHEDULE, n_chunks=N_CHUNKS)
        s2, m2 = jax.jit(step)(state, batch)
        dl = abs(float(m1["loss"]) - float(m2["loss"]))
        assert dl < 5e-3, f"pp={{pp}}: loss diverged {{dl}}"
        worst = max(float(jnp.abs(a - jax.device_get(b)).max())
                    for a, b in zip(jax.tree.leaves(s1.master),
                                    jax.tree.leaves(s2.master)))
        assert worst < 2e-2, f"pp={{pp}}: master params diverged {{worst}}"
        print(f"PP{{pp}}_OK", dl, worst)
""")

MOE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.parallel.compat import make_mesh
    from repro.configs import get_spec
    from repro.data.synthetic import config_for, make_batch
    from repro.models import build_model
    from repro.optim.adamw import init_train_state
    from repro.train.loop import TrainConfig, make_train_step
    from repro.train.pipeline_loop import make_pipeline_train_step

    SCHEDULE = {schedule!r}
    N_CHUNKS = {n_chunks}
    # olmoe: all-MoE layers; deepseek: mixed dense+MoE with MLA — exercises
    # the union-slot select path end to end.  Both padded to 4 layers so
    # every schedule fits its chunk count (interleaved pp=2 v=2 -> 4 chunks).
    # olmoe's loss tolerance is routing noise, not executor error: bf16
    # differences between the stacked and pp=1 layouts flip top-k expert
    # picks, shifting the *metric* ~1.5e-2/layer while post-update params
    # still agree to 6e-4 (the strict check below); identical across all
    # three schedules.
    for name, layers, data, tol in [("olmoe-1b-7b", 4, 2, 1e-1),
                                    ("deepseek-v3", 4, 1, 1e-3)]:
        spec = get_spec(name, smoke=True)
        if layers and spec.n_layers != layers:
            spec = dataclasses.replace(spec, n_layers=layers)
        model = build_model(spec)
        state = init_train_state(model.init(jax.random.PRNGKey(0)))
        batch = make_batch(config_for(spec, 4, 32), 0)
        s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=2)))(state, batch)
        mesh = make_mesh((2, data), ("pipe", "data"))
        step = make_pipeline_train_step(model, TrainConfig(n_micro=2), mesh,
                                        schedule=SCHEDULE, n_chunks=N_CHUNKS)
        s2, m2 = jax.jit(step)(state, batch)
        dl = abs(float(m1["loss"]) - float(m2["loss"]))
        assert dl < tol, f"{{name}}: loss diverged {{dl}}"
        worst = max(float(jnp.abs(a - jax.device_get(b)).max())
                    for a, b in zip(jax.tree.leaves(s1.master),
                                    jax.tree.leaves(s2.master)))
        assert worst < 2e-2, f"{{name}}: master params diverged {{worst}}"
        print(f"{{name}}_MOE_OK", dl, worst)
""")

SCHEDULES = [("1f1b", 1), ("interleaved", 2), ("dualpipe", 2)]


def _run(script):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560,
                          cwd=os.path.dirname(os.path.dirname(__file__)))


@pytest.mark.parametrize("schedule,n_chunks", SCHEDULES)
def test_pipeline_matches_pp1_dense(schedule, n_chunks):
    r = _run(DENSE_SCRIPT.format(schedule=schedule, n_chunks=n_chunks))
    assert "PP2_OK" in r.stdout and "PP4_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
@pytest.mark.parametrize("schedule,n_chunks", SCHEDULES)
def test_pipeline_matches_pp1_moe(schedule, n_chunks):
    r = _run(MOE_SCRIPT.format(schedule=schedule, n_chunks=n_chunks))
    assert "olmoe-1b-7b_MOE_OK" in r.stdout \
        and "deepseek-v3_MOE_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
