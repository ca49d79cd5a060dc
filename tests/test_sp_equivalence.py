"""Sequence-parallel executor equivalence: `make_pipeline_train_step(...,
sp=True)` — the Megatron ğ/dual boundary construction of `parallel/tp.py`
with seq-sharded residuals, boundary payloads and slot rings — reproduces
the sp=1 (single-device) step's loss and post-update master params to
bf16-accumulation tolerance.

Fast tier: one dense pp2×dp2×tp2×sp2 run with ZeRO-1 on, plus the loud
indivisible-seq guard, plus the overlap engine's SP-composed A/B check —
``gate_compute=False`` swaps every ``lax.cond`` for compute-both +
``jnp.where`` and must agree with the gated step bit-for-bit, proving the
gating changes cost, never SP numerics.  Slow tier: the full schedule × pp{1,2,4} × tp2 ×
sp grid (pp=1 only under 1f1b — interleaved/dualpipe require pp >= 2; the
sp=1 legs of the grid are exactly `tests/test_pipeline_3d.py` /
`test_pipeline_1f1b.py`, so only the sp=tp legs run here), the MoE/MLA
families (capacity_factor=4.0 so routing is dropless — per-shard capacity
C/sp vs global C drops different tokens near the capacity cliff, a real
behavioural difference of sharded routing, not an executor bug; params
match exactly either way), and the ZeRO-1-composes-with-SP invariant
(state arriving DP-sharded per `state_shardings`, the SP step still
matching, optimizer shards at 1/dp bytes).

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

    def check(tag, m1, s1, m2, s2, tol_loss=5e-3, tol_p=2e-2, tol_g=5e-2):
        dl = abs(float(m1["loss"]) - float(m2["loss"]))
        assert dl < tol_loss, f"{tag}: loss diverged {dl}"
        worst = max(float(jnp.abs(a - jax.device_get(b)).max())
                    for a, b in zip(jax.tree.leaves(s1.master),
                                    jax.tree.leaves(s2.master)))
        assert worst < tol_p, f"{tag}: master params diverged {worst}"
        # grads must reproduce, not just the post-update params: one AdamW
        # step from zero moments is per-leaf scale-invariant
        # (m/(sqrt(v)+eps) cancels any scaling of g), so a tp x-wrong
        # gradient would still pass the master check.  After step 1,
        # m = (1-b1) g exactly — compare per-leaf *norms* (the tp=2 MLA
        # double-count this guards against showed ratios 0.5-2.0; the
        # sp=1 executor control sits at 1.00 +- 0.03, element-wise diffs
        # being bf16 accumulation noise shared with the TP-only path).
        worst_g = 0.0
        for a, b in zip(jax.tree.leaves(s1.m), jax.tree.leaves(s2.m)):
            n1 = float(jnp.linalg.norm(a.astype(jnp.float32)))
            n2 = float(jnp.linalg.norm(
                jax.device_get(b).astype(jnp.float32)))
            worst_g = max(worst_g, abs(n2 / max(n1, 1e-12) - 1.0))
        assert worst_g < tol_g, \
            f"{tag}: grad (first-moment) norms diverged {worst_g}"
        print(f"{tag}_OK", dl, worst, worst_g)
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
                                    zero=ZeROStage.OS, sp=True)
    s2, m2 = jax.jit(step)(state, batch)
    check("PP2_DP2_TP2_SP2_ZOS", m1, s1, m2, s2)

    # indivisible seq_len % sp raises loudly (no silent pad/replicate)
    bad = {k: v[:, :31] for k, v in batch.items()}
    try:
        jax.jit(step)(state, bad)
        raise SystemExit("indivisible seq was accepted")
    except ValueError as e:
        assert "sp=2" in str(e) and "s=31" in str(e), e
        print("SP_GUARD_OK")
""")

SP_GATE_AB = HEADER + textwrap.dedent("""
    import numpy as np
    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 8, 32), 0)
    mesh = make_mesh((2, 2, 2), ("pipe", "data", "model"))
    outs = {}
    for gate in (True, False):
        step = make_pipeline_train_step(model, TrainConfig(n_micro=4), mesh,
                                        zero=ZeROStage.OS, sp=True,
                                        gate_compute=gate)
        outs[gate] = jax.jit(step)(state, batch)
    (sg, mg), (su, mu) = outs[True], outs[False]
    assert float(mg["loss"]) == float(mu["loss"]), \
        (float(mg["loss"]), float(mu["loss"]))
    for a, b in zip(jax.tree.leaves(sg.master), jax.tree.leaves(su.master)):
        assert np.array_equal(jax.device_get(a), jax.device_get(b)), \
            "gated vs ungated SP master params differ bitwise"
    print("SP_GATE_AB_OK")
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
    meshes = [(1, 2, 2), (2, 2, 2), (4, 1, 2)] if SCHEDULE == "1f1b" \\
        else [(2, 2, 2), (4, 1, 2)]
    for pp, data, tp in meshes:
        mesh = make_mesh((pp, data, tp), ("pipe", "data", "model"))
        step = make_pipeline_train_step(model, TrainConfig(n_micro=4), mesh,
                                        schedule=SCHEDULE, n_chunks=N_CHUNKS,
                                        zero=ZeROStage.OS, sp=True)
        s2, m2 = jax.jit(step)(state, batch)
        check(f"PP{{pp}}_DP{{data}}_TP{{tp}}_SP{{tp}}", m1, s1, m2, s2)
""")


def dense_grid_script(schedule, n_chunks):
    return HEADER + DENSE_GRID_BODY.format(schedule=schedule,
                                           n_chunks=n_chunks)


MOE_MLA_SP = HEADER + textwrap.dedent("""
    from repro.models.transformer import ModelOptions
    # olmoe: all-MoE softmax router (loss tol = the routing noise the sp=1
    # pipeline tests already grant it); deepseek: MLA latent towers
    # (gathered full-seq view, NO copy_to_tp on the latents — the entry
    # ğ's reduce-scatter backward does the cross-shard sum; the grad-norm
    # check below is what catches the tp× double-count if that ever
    # regresses) + mixed dense/MoE + sigmoid router + shared expert, with
    # seq-shard routing/dispatch.  capacity_factor=4.0 keeps both the
    # global and the per-shard routers dropless, so the SP step is
    # comparable to 5e-3 for deepseek (see module docstring).
    for name, layers, data, tol in [("olmoe-1b-7b", 4, 2, 1e-1),
                                    ("deepseek-v3", 4, 1, 5e-3)]:
        spec = dataclasses.replace(get_spec(name, smoke=True), n_layers=layers)
        model = build_model(spec, ModelOptions(capacity_factor=4.0))
        state = init_train_state(model.init(jax.random.PRNGKey(0)))
        batch = make_batch(config_for(spec, 4, 32), 0)
        s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=2)))(state, batch)
        mesh = make_mesh((2, data, 2), ("pipe", "data", "model"))
        step = make_pipeline_train_step(model, TrainConfig(n_micro=2), mesh,
                                        zero=ZeROStage.OS, sp=True)
        s2, m2 = jax.jit(step)(state, batch)
        check(f"{name}_SP2", m1, s1, m2, s2, tol_loss=tol)
""")

ZERO_SP_INVARIANT = HEADER + textwrap.dedent("""
    from repro.parallel.sharding import state_shardings
    from repro.train.pipeline_loop import _EXEC_TP_RULES

    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 8, 32), 0)
    s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=4)))(state, batch)
    mesh = make_mesh((2, 2, 2), ("pipe", "data", "model"))
    dp = mesh.shape["data"]

    def dev0_bytes(tree):
        return sum(x.addressable_shards[0].data.nbytes
                   for x in jax.tree.leaves(tree))

    # SP only re-shards activations: the ZeRO-1 state layout is untouched,
    # so a state arriving DP-sharded must run and reproduce the reference.
    sh_none = state_shardings(state, mesh, ZeROStage.NONE,
                              rules=_EXEC_TP_RULES)
    sh_os = state_shardings(state, mesh, ZeROStage.OS, rules=_EXEC_TP_RULES)
    st_os = jax.device_put(state, sh_os)
    for field in ("master", "m", "v"):
        ratio = dev0_bytes(getattr(st_os, field)) / dev0_bytes(
            jax.device_put(getattr(state, field), getattr(sh_none, field)))
        assert abs(ratio - 1.0 / dp) < 0.05, (field, ratio)
        print(f"{field}: per-device {ratio:.3f} of ZeRO-none (dp={dp})")

    step = make_pipeline_train_step(model, TrainConfig(n_micro=4), mesh,
                                    zero=ZeROStage.OS, sp=True)
    s2, m2 = jax.jit(step)(st_os, batch)
    check("ZERO1_SP_COMPOSED", m1, s1, m2, s2)
""")


def _run(script):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560,
                          cwd=os.path.dirname(os.path.dirname(__file__)))


def test_sp_dense_fast():
    """pp2 × dp2 × tp2 × sp2 with ZeRO-1 + the indivisible-seq guard: the
    tier-1 SP smoke."""
    r = _run(DENSE_FAST)
    assert "PP2_DP2_TP2_SP2_ZOS_OK" in r.stdout and "SP_GUARD_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


def test_sp_gate_compute_ab_bitwise():
    """Cond gating composes with SP: the gated (lax.cond) and ungated
    (compute-both + jnp.where) executors agree bit-for-bit on loss and
    post-update master params when the tick body carries SP's
    all-gather/reduce-scatter collectives inside the gated branches."""
    r = _run(SP_GATE_AB)
    assert "SP_GATE_AB_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
@pytest.mark.parametrize("schedule,n_chunks",
                         [("1f1b", 1), ("interleaved", 2), ("dualpipe", 2)])
def test_sp_grid(schedule, n_chunks):
    """schedule × pp{1,2,4} × tp2 × sp2 vs the single-device (sp=1) step."""
    r = _run(dense_grid_script(schedule, n_chunks))
    tags = ["PP2_DP2_TP2_SP2_OK", "PP4_DP1_TP2_SP2_OK"]
    if schedule == "1f1b":
        tags.append("PP1_DP2_TP2_SP2_OK")
    for tag in tags:
        assert tag in r.stdout, \
            f"missing {tag}\nstdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
def test_sp_moe_mla():
    r = _run(MOE_MLA_SP)
    assert "olmoe-1b-7b_SP2_OK" in r.stdout \
        and "deepseek-v3_SP2_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
def test_zero1_composes_with_sp():
    """ZeRO-1 state sharded 1/dp per DP shard; the SP step consumes the
    sharded state and still reproduces the reference step."""
    r = _run(ZERO_SP_INVARIANT)
    assert "ZERO1_SP_COMPOSED_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
