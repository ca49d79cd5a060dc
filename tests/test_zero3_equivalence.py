"""ZeRO-3 (os+g+params) executor equivalence and invariants.

The gather-on-use path (``parallel.tp.gather_params`` + the DP stage specs
from ``parallel.sharding.zero3_stage_specs``) must be a pure memory
optimisation: the pp2×dp2×tp2 step under ``os+g+params`` reproduces the
``os+g`` step's loss and post-update master params to bf16-accumulation
tolerance, while each device holds ~1/dp of the bf16 working params.

Needs >1 fake device set before jax initialises — subprocess with XLA_FLAGS
(same harness as test_pipeline_3d).
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

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

Z3_EQUIVALENCE = HEADER + textwrap.dedent("""
    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 8, 32), 0)
    batch["mask"] = jnp.broadcast_to(
        (jnp.arange(32) < 28).astype(jnp.float32)[None], (8, 32))
    mesh = make_mesh((2, 2, 2), ("pipe", "data", "model"))
    ref_step = make_pipeline_train_step(model, TrainConfig(n_micro=4), mesh,
                                        zero=ZeROStage.OS_G)
    s1, m1 = jax.jit(ref_step)(state, batch)
    z3_step = make_pipeline_train_step(model, TrainConfig(n_micro=4), mesh,
                                       zero=ZeROStage.OS_G_PARAMS)
    s2, m2 = jax.jit(z3_step)(state, batch)
    check("Z3_VS_OSG_PP2_DP2_TP2", m1, s1, m2, s2)
""")

Z3_STATE_INVARIANT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.parallel.compat import make_mesh
    from repro.configs import get_spec
    from repro.core.parallel_config import ZeROStage
    from repro.models import build_model
    from repro.optim.adamw import init_train_state
    from repro.parallel.sharding import state_shardings

    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    mesh = make_mesh((2, 2, 2), ("pipe", "data", "model"))
    dp = mesh.shape["data"]

    def dev0_bytes(tree):
        return sum(x.addressable_shards[0].data.nbytes
                   for x in jax.tree.leaves(tree))

    sh_osg = state_shardings(state, mesh, ZeROStage.OS_G)
    sh_z3 = state_shardings(state, mesh, ZeROStage.OS_G_PARAMS)
    st_osg = jax.device_put(state, sh_osg)
    st_z3 = jax.device_put(state, sh_z3)
    # os+g leaves the bf16 working copy replicated over DP; ZeRO-3 shards
    # it — per-device param bytes drop to ~1/dp (every smoke-model leaf
    # admits a DP dim, so the ratio is exact)
    full = dev0_bytes(st_osg.params)
    shard = dev0_bytes(st_z3.params)
    ratio = shard / full
    assert abs(ratio - 1.0 / dp) < 0.05, ratio
    # optimizer state shards identically under both stages
    for field in ("master", "m", "v"):
        assert dev0_bytes(getattr(st_z3, field)) == \
            dev0_bytes(getattr(st_osg, field)), field
    print(f"Z3_STATE_INVARIANT_OK {ratio:.3f} (dp={dp})")
""")


Z3_CHECKPOINT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json, tempfile
    import jax, jax.numpy as jnp
    from repro.parallel.compat import make_mesh
    import numpy as np
    from repro.checkpoint import latest_step, restore, save
    from repro.configs import get_spec
    from repro.core.parallel_config import ZeROStage
    from repro.models import build_model
    from repro.optim.adamw import init_train_state
    from repro.parallel.sharding import state_shardings

    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    mesh = make_mesh((2, 2, 2), ("pipe", "data", "model"))
    sh = state_shardings(state, mesh, ZeROStage.OS_G_PARAMS)
    st = jax.device_put(state, sh)
    with tempfile.TemporaryDirectory() as d:
        save(d, 7, st)
        assert latest_step(d) == 7
        man = json.load(open(os.path.join(d, "step_00000007",
                                          "manifest.json")))
        # DP/TP-sharded leaves were gathered to full arrays at save time
        assert any(v["gathered"] for v in man["leaves"].values())
        like = jax.device_put(jax.tree.map(jnp.zeros_like, state), sh)
        back = restore(d, 7, like)
        for a, b, l in zip(jax.tree.leaves(state), jax.tree.leaves(back),
                           jax.tree.leaves(like)):
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(a), np.float32),
                np.asarray(jax.device_get(b), np.float32))
            assert b.sharding == l.sharding     # re-adopted the Z3 layout
    print("Z3_CHECKPOINT_OK")
""")


def _run(script):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560,
                          cwd=os.path.dirname(os.path.dirname(__file__)))


def test_zero3_reproduces_osg_step():
    """pp2 × dp2 × tp2: the ZeRO-3 gather-on-use step reproduces the os+g
    step to bf16 tolerance (the tentpole acceptance)."""
    r = _run(Z3_EQUIVALENCE)
    assert "Z3_VS_OSG_PP2_DP2_TP2_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


def test_zero3_param_sharding_invariant():
    """Each DP shard holds ~1/dp of the bf16 working-param bytes under
    ZeRO-3 (measured from device buffers), with optimizer state unchanged
    vs os+g."""
    r = _run(Z3_STATE_INVARIANT)
    assert "Z3_STATE_INVARIANT_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


def test_zero3_checkpoint_roundtrip():
    """A ZeRO-3 DP-sharded TrainState checkpoints via gather-on-save (the
    manifest marks gathered leaves) and restores back onto its sharded
    layout with identical values."""
    r = _run(Z3_CHECKPOINT)
    assert "Z3_CHECKPOINT_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


def test_zero_ladder_monotone_per_component():
    """Walking up the ZeRO ladder never increases any state component —
    including at DP degrees that don't divide the parameter count (the
    ceil-rounding regression: floor division made a coarser shard look
    *smaller* than a finer one)."""
    import pytest
    pytest.importorskip(
        "hypothesis",
        reason="property test needs hypothesis (requirements-dev.txt)")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    from repro.configs import get_spec
    from repro.core.parallel_config import (ParallelConfig, RecomputePolicy,
                                            ZeROStage)
    from repro.core.zero import zero_memory

    spec = get_spec("qwen2-1.5b")

    @settings(max_examples=40, deadline=None)
    @given(dp=st.integers(1, 64), tp=st.sampled_from([1, 2, 4]),
           pp=st.sampled_from([1, 2, 4]))
    def invariant(dp, tp, pp):
        cfg = ParallelConfig(
            dp=dp, tp=tp, pp=pp, ep=1, etp=1, sp=False,
            zero=ZeROStage.NONE, recompute=RecomputePolicy.NONE,
            micro_batch=1, seq_len=4096)
        ladder = [zero_memory(spec, dataclasses.replace(cfg, zero=z))
                  for z in ZeROStage]
        for a, b in zip(ladder, ladder[1:]):
            assert b.params <= a.params
            assert b.grads <= a.grads
            assert b.optimizer <= a.optimizer
            assert b.total <= a.total

    invariant()
