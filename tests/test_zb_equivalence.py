"""ZB-H1 executor equivalence: ``make_pipeline_train_step(...,
schedule="zb1p")`` reproduces the pp=1 single-device step to
bf16-accumulation tolerance.

The zb1p executor runs the real ZB-H1 split: the B tick runs the full
chunk vjp once (no recompute replay) and parks the fp32 pending-dW in the
scan-carried stash ring; the dedicated W tick flushes that stash slot into
the grad accumulator — so the post-step master params, loss and
first-moment norms must match the reference exactly as tightly as the
1f1b path does (``check()``'s 5e-3 / 2e-2 / 5e-2 bands, shared with
``test_sp_equivalence.py``).  Shared embed/head/final-norm grads
accumulate at B (they never enter the stash), which this grid would catch
as a first-moment norm mismatch if either side double- or under-counted.

Fast tier: one dense pp2 × dp2 × tp2 run with ZeRO-1 on, plus the overlap
engine's A/B check — ``gate_compute=False`` replaces every ``lax.cond``
with compute-both + ``jnp.where`` (the pre-overlap masked executor) and
must agree with the gated step bit-for-bit, proving the cond gating
changes cost, never numerics.  Slow tier: pp{2,4} × tp2 × {dense,
MLA+MoE} × ZeRO-1, plus zb1p×SP composition.

Needs >1 fake device set before jax initialises — subprocess with XLA_FLAGS.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from test_sp_equivalence import HEADER  # noqa: F401  (reuse check())

ZB_FAST = HEADER + textwrap.dedent("""
    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 8, 32), 0)
    batch["mask"] = jnp.broadcast_to(
        (jnp.arange(32) < 28).astype(jnp.float32)[None], (8, 32))
    s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=4)))(state, batch)
    mesh = make_mesh((2, 2, 2), ("pipe", "data", "model"))
    step = make_pipeline_train_step(model, TrainConfig(n_micro=4), mesh,
                                    schedule="zb1p", zero=ZeROStage.OS)
    s2, m2 = jax.jit(step)(state, batch)
    check("ZB1P_PP2_DP2_TP2_ZOS", m1, s1, m2, s2)
""")

ZB_GATE_AB = HEADER + textwrap.dedent("""
    import numpy as np
    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 8, 32), 0)
    mesh = make_mesh((2, 2, 2), ("pipe", "data", "model"))
    outs = {}
    for sched in ("zb1p", "1f1b"):
        for gate in (True, False):
            step = make_pipeline_train_step(
                model, TrainConfig(n_micro=4), mesh, schedule=sched,
                zero=ZeROStage.OS, gate_compute=gate)
            outs[(sched, gate)] = jax.jit(step)(state, batch)
        (sg, mg), (su, mu) = outs[(sched, True)], outs[(sched, False)]
        assert float(mg["loss"]) == float(mu["loss"]), \
            (sched, float(mg["loss"]), float(mu["loss"]))
        for a, b in zip(jax.tree.leaves(sg.master),
                        jax.tree.leaves(su.master)):
            assert np.array_equal(jax.device_get(a), jax.device_get(b)), \
                f"{sched}: gated vs ungated master params differ bitwise"
        print(f"GATE_AB_{sched}_OK")
""")

ZB_DENSE_GRID = HEADER + textwrap.dedent("""
    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 8, 32), 0)
    batch["mask"] = jnp.broadcast_to(
        (jnp.arange(32) < 28).astype(jnp.float32)[None], (8, 32))
    s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=4)))(state, batch)
    for pp, data, tp, sp in [(2, 2, 2, False), (4, 1, 2, False),
                             (2, 2, 2, True), (4, 1, 2, True)]:
        mesh = make_mesh((pp, data, tp), ("pipe", "data", "model"))
        step = make_pipeline_train_step(model, TrainConfig(n_micro=4), mesh,
                                        schedule="zb1p", zero=ZeROStage.OS,
                                        sp=sp)
        s2, m2 = jax.jit(step)(state, batch)
        check(f"ZB1P_PP{pp}_DP{data}_TP{tp}_SP{int(sp)}", m1, s1, m2, s2)
""")

ZB_MOE_MLA = HEADER + textwrap.dedent("""
    from repro.models.transformer import ModelOptions
    # olmoe: all-MoE softmax router (routing noise gets the same wide loss
    # band the sp/pipeline suites grant it); deepseek: MLA latents + mixed
    # dense/MoE + shared expert.  capacity_factor=4.0 keeps routing
    # dropless so the comparison isolates the W-split, not capacity drops.
    for name, layers, tol in [("olmoe-1b-7b", 4, 1e-1),
                              ("deepseek-v3", 4, 5e-3)]:
        spec = dataclasses.replace(get_spec(name, smoke=True), n_layers=layers)
        model = build_model(spec, ModelOptions(capacity_factor=4.0))
        state = init_train_state(model.init(jax.random.PRNGKey(0)))
        batch = make_batch(config_for(spec, 4, 32), 0)
        s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=2)))(state, batch)
        for pp, data, tp in [(2, 2, 2), (4, 1, 2)]:
            mesh = make_mesh((pp, data, tp), ("pipe", "data", "model"))
            step = make_pipeline_train_step(model, TrainConfig(n_micro=2),
                                            mesh, schedule="zb1p",
                                            zero=ZeROStage.OS)
            s2, m2 = jax.jit(step)(state, batch)
            check(f"{name}_ZB1P_PP{pp}", m1, s1, m2, s2, tol_loss=tol)
""")


def _run(script):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560,
                          cwd=os.path.dirname(os.path.dirname(__file__)))


def test_zb1p_dense_fast():
    """pp2 × dp2 × tp2 with ZeRO-1: the tier-1 zb1p smoke."""
    r = _run(ZB_FAST)
    assert "ZB1P_PP2_DP2_TP2_ZOS_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


def test_gate_compute_ab_bitwise():
    """The overlap engine's cond gating is cost-only: gated (lax.cond) and
    ungated (compute-both + jnp.where) steps agree bit-for-bit on loss and
    post-update master params, for both the split (zb1p) and fused (1f1b)
    backward."""
    r = _run(ZB_GATE_AB)
    for tag in ["GATE_AB_zb1p_OK", "GATE_AB_1f1b_OK"]:
        assert tag in r.stdout, \
            f"missing {tag}\nstdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
def test_zb1p_dense_grid():
    """pp{2,4} × tp2 × sp{off,on} vs the single-device step."""
    r = _run(ZB_DENSE_GRID)
    for tag in ["ZB1P_PP2_DP2_TP2_SP0_OK", "ZB1P_PP4_DP1_TP2_SP0_OK",
                "ZB1P_PP2_DP2_TP2_SP1_OK", "ZB1P_PP4_DP1_TP2_SP1_OK"]:
        assert tag in r.stdout, \
            f"missing {tag}\nstdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
def test_zb1p_moe_mla():
    """MoE (olmoe) and MLA+MoE (deepseek-v3) under zb1p at pp{2,4}."""
    r = _run(ZB_MOE_MLA)
    for tag in ["olmoe-1b-7b_ZB1P_PP2_OK", "olmoe-1b-7b_ZB1P_PP4_OK",
                "deepseek-v3_ZB1P_PP2_OK", "deepseek-v3_ZB1P_PP4_OK"]:
        assert tag in r.stdout, \
            f"missing {tag}\nstdout={r.stdout}\nstderr={r.stderr[-3000:]}"
