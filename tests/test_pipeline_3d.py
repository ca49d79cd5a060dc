"""3D runtime equivalence: the pipeline executor on ('pipe','data','model')
meshes — TP inside every rank (manual Megatron collectives, vocab-parallel
CE) and ZeRO state sharding over the per-stage DP group — reproduces the
single-device step's loss and post-update master params to
bf16-accumulation tolerance.

Fast tier: one dense pp2×dp2×tp2 run with ZeRO-1 on.  Slow tier: the full
schedule × pp{2,4} × tp{2} × dp{1,2} grid, the MoE/MLA families, and the
ZeRO-1 state-sharding invariant (each DP shard holds 1/dp of the optimizer
bytes; the sharded AdamW update reassembles to the replicated one).

Needs >1 fake device set before jax initialises — subprocess with XLA_FLAGS.
"""

import os
import subprocess
import sys
import textwrap

import pytest

HEADER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.parallel.compat import make_mesh
    from repro.configs import get_spec
    from repro.core.parallel_config import ZeROStage
    from repro.data.synthetic import config_for, make_batch
    from repro.models import build_model
    from repro.optim.adamw import init_train_state
    from repro.train.loop import TrainConfig, make_train_step
    from repro.train.pipeline_loop import make_pipeline_train_step

    def check(tag, m1, s1, m2, s2, tol_loss=5e-3, tol_p=2e-2):
        dl = abs(float(m1["loss"]) - float(m2["loss"]))
        assert dl < tol_loss, f"{tag}: loss diverged {dl}"
        worst = max(float(jnp.abs(a - jax.device_get(b)).max())
                    for a, b in zip(jax.tree.leaves(s1.master),
                                    jax.tree.leaves(s2.master)))
        assert worst < tol_p, f"{tag}: master params diverged {worst}"
        print(f"{tag}_OK", dl, worst)
""")

DENSE_FAST = HEADER + textwrap.dedent("""
    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 8, 32), 0)
    batch["mask"] = jnp.broadcast_to(
        (jnp.arange(32) < 28).astype(jnp.float32)[None], (8, 32))
    s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=4)))(state, batch)
    mesh = make_mesh((2, 2, 2), ("pipe", "data", "model"))
    step = make_pipeline_train_step(model, TrainConfig(n_micro=4), mesh,
                                    zero=ZeROStage.OS)
    s2, m2 = jax.jit(step)(state, batch)
    check("PP2_DP2_TP2_ZOS", m1, s1, m2, s2)
""")

DENSE_GRID_BODY = textwrap.dedent("""
    SCHEDULE = {schedule!r}
    N_CHUNKS = {n_chunks}
    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 8, 32), 0)
    batch["mask"] = jnp.broadcast_to(
        (jnp.arange(32) < 28).astype(jnp.float32)[None], (8, 32))
    s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=4)))(state, batch)
    for pp, data, tp in [(2, 2, 2), (2, 1, 2), (4, 1, 2)]:
        mesh = make_mesh((pp, data, tp), ("pipe", "data", "model"))
        step = make_pipeline_train_step(model, TrainConfig(n_micro=4), mesh,
                                        schedule=SCHEDULE, n_chunks=N_CHUNKS,
                                        zero=ZeROStage.OS)
        s2, m2 = jax.jit(step)(state, batch)
        check(f"PP{{pp}}_DP{{data}}_TP{{tp}}", m1, s1, m2, s2)
""")


def dense_grid_script(schedule, n_chunks):
    return HEADER + DENSE_GRID_BODY.format(schedule=schedule,
                                           n_chunks=n_chunks)

MOE_TP = HEADER + textwrap.dedent("""
    # olmoe: all-MoE softmax router (loss tol = routing noise, see
    # test_pipeline_1f1b); deepseek: MLA + mixed dense/MoE + sigmoid router
    # + shared expert — expert-ff (ETP) sharding and the MLA latent-tower
    # collectives end to end, with ZeRO-1 on.
    for name, layers, data, tol in [("olmoe-1b-7b", 4, 2, 1e-1),
                                    ("deepseek-v3", 4, 1, 5e-3)]:
        spec = dataclasses.replace(get_spec(name, smoke=True), n_layers=layers)
        model = build_model(spec)
        state = init_train_state(model.init(jax.random.PRNGKey(0)))
        batch = make_batch(config_for(spec, 4, 32), 0)
        s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=2)))(state, batch)
        mesh = make_mesh((2, data, 2), ("pipe", "data", "model"))
        step = make_pipeline_train_step(model, TrainConfig(n_micro=2), mesh,
                                        zero=ZeROStage.OS)
        s2, m2 = jax.jit(step)(state, batch)
        check(f"{name}_TP2", m1, s1, m2, s2, tol_loss=tol)
""")

MOE_AUX_DP = HEADER + textwrap.dedent("""
    # fp32 weights take bf16 rounding out of the comparison, and a dropless
    # capacity takes out the per-shard capacity: what is left at dp2 is the
    # aux loss, which must be the whole-microbatch value of the one-device
    # step although each data shard routes only half the microbatch
    from repro.models.transformer import ModelOptions
    spec = dataclasses.replace(get_spec("deepseek-v3", smoke=True), n_layers=4)
    model = build_model(spec, ModelOptions(capacity_factor=4.0))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          model.init(jax.random.PRNGKey(0)))
    state = init_train_state(params)
    batch = make_batch(config_for(spec, 4, 32), 0)
    s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=2)))(state, batch)
    mesh = make_mesh((2, 2, 1), ("pipe", "data", "model"),
                     devices=jax.devices()[:4])
    step = make_pipeline_train_step(model, TrainConfig(n_micro=2), mesh,
                                    zero=ZeROStage.OS)
    s2, m2 = jax.jit(step)(state, batch)
    dl = abs(float(m1["loss"]) - float(m2["loss"]))
    assert dl < 1e-5, f"loss diverged {dl}"
    # m is stored in bf16, so fp32 grads equal to round-off can still land
    # one bf16 ulp apart; an aux cotangent off by the dp factor reads 5e-3
    def rel(a, b):
        a = a.astype(jnp.float32)
        b = jnp.asarray(jax.device_get(b), jnp.float32)
        return float(jnp.linalg.norm(b - a) / jnp.linalg.norm(a))
    worst = max(rel(a, b) for a, b in zip(jax.tree.leaves(s1.m),
                                          jax.tree.leaves(s2.m)))
    assert worst < 1e-3, f"first moments diverged {worst}"
    print("MOE_AUX_DP2_OK", dl, worst)
""")

ZERO_INVARIANT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.parallel.compat import make_mesh
    from repro.configs import get_spec
    from repro.core.parallel_config import ZeROStage
    from repro.models import build_model
    from repro.optim.adamw import (AdamWConfig, adamw_update,
                                   init_train_state)
    from repro.parallel.sharding import state_shardings

    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    mesh = make_mesh((2, 2, 2), ("pipe", "data", "model"))
    dp = mesh.shape["data"]

    def dev0_bytes(tree):
        return sum(x.addressable_shards[0].data.nbytes
                   for x in jax.tree.leaves(tree))

    sh_none = state_shardings(state, mesh, ZeROStage.NONE)
    sh_os = state_shardings(state, mesh, ZeROStage.OS)
    st_none = jax.device_put(state, sh_none)
    st_os = jax.device_put(state, sh_os)
    for field in ("master", "m", "v"):
        full = dev0_bytes(getattr(st_none, field))
        shard = dev0_bytes(getattr(st_os, field))
        ratio = shard / full
        # every leaf of the smoke model admits a DP dim -> exactly 1/dp
        assert abs(ratio - 1.0 / dp) < 0.05, (field, ratio)
        print(f"{field}: per-device {ratio:.3f} of replicated (dp={dp})")
    # params stay un-DP-sharded below ZeRO-3
    assert dev0_bytes(st_os.params) == dev0_bytes(st_none.params)

    # the sharded AdamW update reassembles to the replicated one
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.PRNGKey(1), p.shape,
                                    jnp.float32) * 1e-3, state.params)
    ref, _ = jax.jit(adamw_update, static_argnums=2)(state, grads,
                                                     AdamWConfig())
    out, _ = jax.jit(adamw_update, static_argnums=2,
                     out_shardings=((sh_os, None)))(st_os, grads,
                                                    AdamWConfig())
    for a, b in zip(jax.tree.leaves(ref.master), jax.tree.leaves(out.master)):
        assert jnp.allclose(a, jax.device_get(b), atol=1e-6), "update diverged"
    print("ZERO1_INVARIANT_OK")
""")


def _run(script):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560,
                          cwd=os.path.dirname(os.path.dirname(__file__)))


def test_pipeline_3d_dense_fast():
    """pp2 × dp2 × tp2 with ZeRO-1: the tier-1 3D smoke."""
    r = _run(DENSE_FAST)
    assert "PP2_DP2_TP2_ZOS_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
@pytest.mark.parametrize("schedule,n_chunks",
                         [("1f1b", 1), ("interleaved", 2), ("dualpipe", 2)])
def test_pipeline_3d_grid(schedule, n_chunks):
    """schedule × pp{2,4} × tp2 × dp{1,2} vs the single-device step."""
    r = _run(dense_grid_script(schedule, n_chunks))
    for tag in ("PP2_DP2_TP2_OK", "PP2_DP1_TP2_OK", "PP4_DP1_TP2_OK"):
        assert tag in r.stdout, \
            f"missing {tag}\nstdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
def test_pipeline_3d_moe():
    r = _run(MOE_TP)
    assert "olmoe-1b-7b_TP2_OK" in r.stdout \
        and "deepseek-v3_TP2_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


def test_moe_aux_whole_microbatch_under_dp():
    """At pp2·dp2 the MoE aux loss and every gradient match the one-device
    step in fp32: the load-balance statistics span all data shards."""
    r = _run(MOE_AUX_DP)
    assert "MOE_AUX_DP2_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


def test_zero1_state_sharding_invariant():
    """Each DP shard holds 1/dp of the optimizer bytes; the sharded AdamW
    update matches the replicated one after reassembly."""
    r = _run(ZERO_INVARIANT)
    assert "ZERO1_INVARIANT_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
