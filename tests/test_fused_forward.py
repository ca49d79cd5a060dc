"""The last model chunk's forward runs once, inside its backward tick.

``make_pipeline_train_step`` drops the F tick of the last model chunk:
that chunk's output has no consumer, and the backward tick's ``jax.vjp``
runs the same forward, so the loss, the MoE aux and the MoE counters are
taken from the vjp's primal pass.  Checked here:

* the step still matches ``train.loop.make_train_step`` (loss, the
  post-update master params, the first moment, i.e. the gradient) to the
  bands of ``test_sp_equivalence.check``, for every schedule at pp 1 and 2
  (interleaved and dualpipe need pp >= 2), gated and ungated, dense and
  MoE; gated and ungated agree bit for bit;
* ``moe_routed`` / ``moe_kept`` equal the counts of the single-device
  model's own ``moe_forward`` calls on the same microbatches, and
  ``fwd_fused`` is ``n_micro`` (one last model chunk per microbatch);
* the routing guard (``train.schedules.forward_runs``): no send or
  receive table is active for a dropped forward, for every schedule,
  pp in {1, 2, 4} and the ``n_chunks`` each allows, and a table that
  would read one is refused.

The steps run in a child process with two virtual CPU devices."""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import get_spec
from repro.models.pipeline import chunked_partition
from repro.train.schedules import (SCHEDULES, build_exec_tables,
                                   forward_runs, make_schedule)

N_MICRO = 2
# (schedule, pp): interleaved and dualpipe need pp >= 2
LAYOUTS = (("1f1b", 1), ("zb1p", 1), ("1f1b", 2), ("interleaved", 2),
           ("dualpipe", 2), ("zb1p", 2))
MODELS = {"dense": "qwen2-1.5b", "moe": "olmoe-1b-7b"}
# test_sp_equivalence.check's bands: loss, master params, first-moment norms
TOL_LOSS, TOL_P, TOL_G = 5e-3, 2e-2, 5e-2

CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import dataclasses, json, sys
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_spec
    from repro.data.synthetic import config_for, make_batch
    from repro.models import build_model, moe as moe_mod
    from repro.optim.adamw import init_train_state
    from repro.parallel.compat import make_mesh
    from repro.train.loop import TrainConfig, _split_micro, make_train_step
    from repro.train.pipeline_loop import make_pipeline_train_step

    name, n_micro, layouts = sys.argv[1], int(sys.argv[2]), \\
        json.loads(sys.argv[3])
    spec = dataclasses.replace(get_spec(name, smoke=True), n_layers=4)
    model = build_model(spec)
    params = model.init(jax.random.PRNGKey(0))
    state = init_train_state(params)
    batch = make_batch(config_for(spec, 4, 32), 0)
    batch["mask"] = jnp.broadcast_to(
        (jnp.arange(32) < 28).astype(jnp.float32)[None], (4, 32))
    cfg = TrainConfig(n_micro=n_micro)
    s1, m1 = jax.jit(make_train_step(model, cfg))(state, batch)

    # the reference's MoE counts: every moe_forward call of the
    # single-device model on the same microbatches
    seen = []
    real = moe_mod.moe_forward

    def counted(*a, **k):
        o = real(*a, **k)
        jax.debug.callback(lambda r, kk: seen.append((int(r), int(kk))),
                           o.routed, o.kept)
        return o

    moe_mod.moe_forward = counted
    micro = _split_micro(batch, n_micro)
    for i in range(n_micro):
        jax.block_until_ready(jax.jit(model.loss)(
            params, jax.tree.map(lambda x: x[i], micro)))
    moe_mod.moe_forward = real
    ref = np.sum(seen, axis=0).tolist() if seen else [0, 0]

    def leaves(st):
        return [np.asarray(jax.device_get(a), np.float32)
                for a in jax.tree.leaves((st.master, st.m))]

    out = {}
    for sched, pp in layouts:
        mesh = make_mesh((pp, 1, 1), ("pipe", "data", "model"),
                         devices=jax.devices()[:pp])
        gated = None
        for gate in (True, False):
            step = make_pipeline_train_step(
                model, cfg, mesh, schedule=sched,
                n_chunks=2 if sched == "interleaved" else 1,
                gate_compute=gate)
            s2, m2 = jax.jit(step)(state, batch)
            worst_p = max(float(jnp.abs(a - jax.device_get(b)).max())
                          for a, b in zip(jax.tree.leaves(s1.master),
                                          jax.tree.leaves(s2.master)))
            worst_g = 0.0
            for a, b in zip(jax.tree.leaves(s1.m), jax.tree.leaves(s2.m)):
                n1 = float(jnp.linalg.norm(a.astype(jnp.float32)))
                n2 = float(jnp.linalg.norm(
                    jax.device_get(b).astype(jnp.float32)))
                worst_g = max(worst_g, abs(n2 / max(n1, 1e-12) - 1.0))
            got = (float(m2["loss"]), leaves(s2))
            if gate:
                gated = got
            same = got[0] == gated[0] and all(
                np.array_equal(a, b) for a, b in zip(got[1], gated[1]))
            out[f"{sched}-pp{pp}-{'gated' if gate else 'ungated'}"] = {
                "dl": abs(float(m1["loss"]) - got[0]), "dp": worst_p,
                "dg": worst_g, "same_as_gated": same,
                "routed": int(m2["moe_routed"]), "kept": int(m2["moe_kept"]),
                "fused": int(m2["fwd_fused"]), "ref": ref}
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs():
    """One child per model, run on first use: {model: {case: numbers}}."""
    got = {}

    def get(model):
        if model not in got:
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            r = subprocess.run(
                [sys.executable, "-c", CHILD, MODELS[model], str(N_MICRO),
                 json.dumps(LAYOUTS)],
                capture_output=True, text=True, env=env, timeout=900)
            assert r.returncode == 0, r.stderr[-4000:]
            line = [x for x in r.stdout.splitlines()
                    if x.startswith("RESULT ")][-1]
            got[model] = json.loads(line[len("RESULT "):])
        return got[model]
    return get


@pytest.mark.parametrize("gate", ["gated", "ungated"])
@pytest.mark.parametrize("sched,pp", LAYOUTS)
@pytest.mark.parametrize("model", sorted(MODELS))
def test_fused_forward_matches_reference(runs, model, sched, pp, gate):
    r = runs(model)[f"{sched}-pp{pp}-{gate}"]
    assert r["dl"] < TOL_LOSS, r
    assert r["dp"] < TOL_P, r
    assert r["dg"] < TOL_G, r
    assert r["same_as_gated"], r
    assert [r["routed"], r["kept"]] == r["ref"], r
    if model == "moe":
        assert 0 < r["kept"] <= r["routed"], r
    assert r["fused"] == N_MICRO, r


def _guard_cases():
    for name in SCHEDULES:
        for pp in (1, 2, 4):
            if pp == 1 and name in ("interleaved", "dualpipe"):
                continue
            chunks = {"interleaved": (2, 3), "dualpipe": (2,)}.get(name, (1,))
            for v in chunks:
                yield name, pp, v


@pytest.mark.parametrize("name,pp,v", list(_guard_cases()))
def test_no_receive_reads_a_dropped_forward(name, pp, v):
    spec = get_spec("qwen2-1.5b")
    sched = make_schedule(name, pp, 2 * pp, n_chunks=v)
    tab = build_exec_tables(sched)
    last = chunked_partition(spec, pp, schedule=name, n_chunks=v).last_flag
    f_run = forward_runs(tab, last)
    is_last = last[np.arange(pp)[None, :], tab.f_chunk] > 0.5
    dropped = (tab.f_act > 0.5) & is_last
    # the last chunk's forwards, one per microbatch, and only they, drop
    assert int(dropped.sum()) == sched.n_micro
    np.testing.assert_array_equal(f_run, np.where(dropped, 0.0, tab.f_act))
    # a payload received at (t, r) left the sender's forward at tick t
    for act, shift in ((tab.rfd_act, -1), (tab.rfu_act, 1)):
        from_sender = np.roll(act, shift, axis=1) > 0.5
        assert not (from_sender & dropped).any()
    assert not ((tab.fsend_down + tab.fsend_up > 0.5) & dropped).any()
    # a table that would receive a dropped forward's output is refused
    t, r = np.argwhere(dropped)[0]
    bad = tab.rfd_act.copy()
    bad[t, (r + 1) % pp] = 1.0
    with pytest.raises(ValueError, match="cannot be dropped"):
        forward_runs(dataclasses.replace(tab, rfd_act=bad), last)
