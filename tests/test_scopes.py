"""The executor's named scopes and MoE counters (``repro.scopes``).

Compiles the pipeline executor's step for tiny dense, MoE and (four
virtual devices, in a child process) MoE-EP meshes and reads the compiled
HLO's ``op_name`` metadata: every scope is there, the backward tick holds
both the replayed forward (``jvp(``) and the backward (``transpose(``),
and the matmuls and kernels carry a layer scope.  The last model chunk's
forward runs only inside its backward tick, so at pp = 1 no op sits under
``tick.F``; at pp = 2 the first chunk's layers still do, and the head,
which only the last chunk's loss reads, does not.  The step's
``moe_routed`` / ``moe_kept`` counters are checked against the token
count and, at a small capacity, against a numpy count of the same
routing for ep 1 and ep 2."""

import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes as S

SEQ, BATCH, N_MICRO = 64, 2, 2
KIND = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\S.*?\s([a-z][\w\-]*)\(")
MATMULS = ("dot", "convolution", "custom-call")


def build_step(name, *, mesh_shape=(1, 1, 1), ep=1, zero="none",
               schedule="1f1b", capacity_factor=1.25, batch=BATCH,
               n_micro=N_MICRO, seq=SEQ, seed=0):
    """(compiled HLO text, step metrics as Python numbers, spec) of one
    step of the smoke-width ``name`` on the first devices."""
    from repro.configs import get_spec
    from repro.core.parallel_config import ZeROStage
    from repro.data.synthetic import config_for, make_batch
    from repro.models import build_model
    from repro.models.transformer import ModelOptions
    from repro.optim.adamw import init_train_state
    from repro.parallel.compat import make_mesh
    from repro.train.loop import TrainConfig
    from repro.train.pipeline_loop import make_pipeline_train_step

    spec = get_spec(name, smoke=True)
    model = build_model(spec, ModelOptions(backend="pallas",
                                           capacity_factor=capacity_factor))
    n = int(np.prod(mesh_shape))
    mesh = make_mesh(mesh_shape, ("pipe", "data", "model"),
                     devices=jax.devices()[:n])
    step = make_pipeline_train_step(model, TrainConfig(n_micro=n_micro),
                                    mesh, schedule=schedule,
                                    zero=ZeROStage(zero), ep=ep)
    state = init_train_state(model.init(jax.random.PRNGKey(seed)))
    b = make_batch(config_for(spec, batch, seq, seed=seed), 0)
    compiled = jax.jit(step).lower(state, b).compile()
    _, metrics = compiled(state, b)
    return (compiled.as_text(),
            {k: v.item() for k, v in jax.device_get(metrics).items()}, spec)


def scope_facts(text):
    """What the tests assert about one compiled step's metadata."""
    smap = S.scope_map(text)
    paths = set(smap.values())
    found = set()
    for p in paths:
        found.update(c for c in p.split("/") if c in S.TICKS)
        if S.layer_of(p):
            found.add(S.layer_of(p))
    b_paths = [p for p in paths if S.TICK_B in p.split("/")]
    mm = [n for n, k in (KIND.match(line).groups() for line in
                         text.splitlines() if KIND.match(line))
          if k in MATMULS]
    scoped = sum(1 for n in mm if S.layer_of(smap.get(n, "")))
    f_layers = [S.layer_of(smap.get(n, "")) for n in mm
                if S.TICK_F in smap.get(n, "").split("/")]
    return {"found": sorted(found),
            "b_replay": any(S.phase_of(p) == S.REPLAY for p in b_paths),
            "b_backward": any("transpose(jvp(" in p for p in b_paths),
            "matmuls": [scoped, len(mm)],
            "f_matmul_layers": sorted(
                {x for x in f_layers if x in S.MODEL_LAYERS}),
            "f_matmuls": len(f_layers)}


def assert_no_forward_tick(facts):
    """pp = 1: the only model chunk is the last one, whose forward runs
    inside the backward tick's vjp, so the step has no ``tick.F`` op."""
    assert S.TICK_F not in facts["found"]
    assert facts["f_matmuls"] == 0 and facts["f_matmul_layers"] == []


def kept_ep1(eids, n_expert, cap):
    """Assignments (token-major, then k) that find room in their
    expert's first ``cap`` slots."""
    seen = np.zeros(n_expert, int)
    kept = 0
    for e in np.asarray(eids).reshape(-1):
        kept += seen[e] < cap
        seen[e] += 1
    return int(kept)


def kept_ep(eids_per_rank, n_expert, ep, c_send, c_loc):
    """The EP dispatch's count: each rank's assignments bucketed by
    destination rank (``c_send`` slots each), then each receiving rank's
    rows, in source-rank then slot order, into its experts' ``c_loc``
    slots."""
    e_loc = n_expert // ep
    recv = [[] for _ in range(ep)]
    for eids in eids_per_rank:
        flat = np.asarray(eids).reshape(-1)
        slots = [[] for _ in range(ep)]
        for e in flat:
            if len(slots[e // e_loc]) < c_send:
                slots[e // e_loc].append(e % e_loc)
        for q in range(ep):
            recv[q].extend(slots[q])
    kept = 0
    for rows in recv:
        seen = np.zeros(e_loc, int)
        for e in rows:
            kept += seen[e] < c_loc
            seen[e] += 1
    return int(kept)


@pytest.fixture(scope="module")
def dense():
    return build_step("qwen2-1.5b")


@pytest.fixture(scope="module")
def moe():
    return build_step("olmoe-1b-7b")


def test_scopes_in_dense_step(dense):
    facts = scope_facts(dense[0])
    assert set(facts["found"]) >= {S.TICK_B, S.EMBED, S.LAYER_SCAN,
                                   S.ATTENTION, S.MLP, S.HEAD, S.GRAD_ACCUM,
                                   S.OPTIMIZER}
    assert_no_forward_tick(facts)
    assert S.MOE_ROUTE not in facts["found"]
    assert dense[1]["fwd_fused"] == N_MICRO
    assert facts["b_replay"] and facts["b_backward"]
    scoped, total = facts["matmuls"]
    assert total > 0 and scoped >= 0.95 * total, facts["matmuls"]


# the scopes of mechanisms olmoe does not have: latent attention and
# shared experts
NOT_OLMOE = {S.MLA_TOWERS, S.MOE_SHARED}


def test_scopes_in_moe_step(moe):
    facts = scope_facts(moe[0])
    assert set(facts["found"]) >= set(S.LAYERS) - {S.GRAD_SYNC} \
        - NOT_OLMOE | {S.TICK_B}
    assert not NOT_OLMOE & set(facts["found"])
    assert_no_forward_tick(facts)
    assert facts["b_replay"] and facts["b_backward"]
    scoped, total = facts["matmuls"]
    assert total > 0 and scoped >= 0.95 * total, facts["matmuls"]


def test_scopes_in_mla_moe_step():
    # DeepSeek-V2-Lite's smoke: latent attention's towers and the shared
    # experts have scopes of their own; C = round(64 * 2 / 8 * 1.5) = 24
    # slots, 8-aligned for the grouped-matmul kernel
    text, metrics, spec = build_step("deepseek-v2-lite",
                                     capacity_factor=1.5)
    facts = scope_facts(text)
    assert set(facts["found"]) >= set(S.LAYERS) - {S.GRAD_SYNC} | {
        S.TICK_B}
    scoped, total = facts["matmuls"]
    assert total > 0 and scoped >= 0.95 * total, facts["matmuls"]
    # the held experts' share of the assignments: 3 MoE layers, 2
    # microbatches of 64 tokens, top-2, of which those to experts 0-3 of 8
    every = spec.n_moe_layers() * N_MICRO * SEQ * spec.moe.n_active
    assert 0 < metrics["moe_kept"] <= metrics["moe_routed"] < every


def test_weight_tick_scope_under_zb1p():
    text, _, _ = build_step("qwen2-1.5b", schedule="zb1p")
    facts = scope_facts(text)
    assert {S.TICK_W, S.GRAD_ACCUM} <= set(facts["found"])


def test_counters_zero_for_dense(dense):
    assert dense[1]["moe_routed"] == 0 and dense[1]["moe_kept"] == 0


def test_counters_routed_is_every_assignment(moe):
    _, m, spec = moe
    assert m["moe_routed"] == BATCH * SEQ * spec.moe.n_active * spec.n_layers
    assert 0 < m["moe_kept"] <= m["moe_routed"]


def test_counters_dropless_at_capacity_t():
    # capacity factor E / k = 2 gives every expert T slots
    _, m, spec = build_step("olmoe-1b-7b", capacity_factor=2.0)
    assert spec.moe.n_routed / spec.moe.n_active == 2.0
    assert m["moe_kept"] == m["moe_routed"] == \
        BATCH * SEQ * spec.moe.n_active * spec.n_layers


def test_counters_unchanged_numbers(moe):
    """The counters change no number the step returns: the loss and the
    gradient norm are those of a step that counts nothing."""
    from repro.models import moe as moe_mod
    with pytest.MonkeyPatch.context() as mp:
        real = moe_mod.moe_forward

        def uncounted(*a, **k):
            out = real(*a, **k)
            return out._replace(routed=jnp.int32(0), kept=jnp.int32(0))
        mp.setattr("repro.models.pipeline.moe_forward", uncounted)
        _, m0, _ = build_step("olmoe-1b-7b")
    assert m0["moe_routed"] == 0
    assert m0["loss"] == moe[1]["loss"]
    assert m0["grad_norm"] == moe[1]["grad_norm"]


def test_kept_matches_numpy_count_ep1():
    from repro.configs import get_spec
    from repro.models.moe import _route, moe_forward, moe_init
    spec = get_spec("olmoe-1b-7b", smoke=True)
    p = moe_init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, spec.h),
                          jnp.float32)
    cf = 0.5
    out = moe_forward(p, spec, x, capacity_factor=cf)
    T, K, E = 64, spec.moe.n_active, spec.moe.n_routed
    _, _, eids = _route(p["router"], spec, x.reshape(T, spec.h), "softmax")
    cap = int(max(1, round(T * K / E * cf)))
    want = kept_ep1(eids, E, cap)
    assert int(out.routed) == T * K
    assert int(out.kept) == want < T * K


FOUR = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {tests!r})
    import functools
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import test_scopes as TS
    from repro.configs import get_spec
    from repro.models.moe import _route, moe_forward, moe_init
    from repro.parallel.compat import make_mesh, shard_map
    assert len(jax.devices()) == 4
    out = {{}}
    text, m, spec = TS.build_step("olmoe-1b-7b", mesh_shape=(1, 2, 2), ep=2,
                                  zero="os+g", batch=4)
    out["facts"] = TS.scope_facts(text)
    out["metrics"] = m
    _, m, _ = TS.build_step("olmoe-1b-7b", mesh_shape=(1, 2, 2), ep=2,
                            zero="os+g", batch=4, capacity_factor=2.0)
    out["dropless"] = m
    text, m, _ = TS.build_step("qwen2-1.5b", mesh_shape=(2, 1, 1))
    out["pp2"] = TS.scope_facts(text)
    out["pp2_metrics"] = m

    # moe_forward under EP at a small capacity against the numpy count
    spec = get_spec("olmoe-1b-7b", smoke=True)
    E, K, ep, cf = spec.moe.n_routed, spec.moe.n_active, 2, 0.5
    mesh = make_mesh((2,), ("model",), devices=jax.devices()[:2])
    p = moe_init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, spec.h), jnp.float32)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=({{"router": P(None, None),
                    "we_gate": P("model", None, None),
                    "we_up": P("model", None, None),
                    "we_down": P("model", None, None)}}, P()),
        out_specs=(P("model"), P("model")))
    def body(lp, xs):
        o = moe_forward(lp, spec, xs, capacity_factor=cf, ep=ep,
                        ep_axis="model")
        return o.routed[None], o.kept[None]

    routed, kept = jax.jit(body)(p, x)
    T = 64
    t_loc = T // ep
    xt = x.reshape(T, spec.h)
    eids = [_route(p["router"], spec, xt[r * t_loc:(r + 1) * t_loc],
                   "softmax")[2] for r in range(ep)]
    tk = t_loc * K
    c_send = int(max(1, round(tk / ep * cf)))
    c_loc = int(max(1, round(tk * ep / E * cf)))
    out["ep_routed"] = int(routed.sum())
    out["ep_kept"] = int(kept.sum())
    out["ep_want"] = TS.kept_ep(eids, E, ep, c_send, c_loc)
    out["T_K"] = T * K
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def four():
    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", FOUR.format(tests=tests)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_scopes_in_ep_step(four):
    facts = four["facts"]
    assert set(facts["found"]) >= set(S.LAYERS) - NOT_OLMOE | {S.TICK_B}
    assert_no_forward_tick(facts)
    assert facts["b_replay"] and facts["b_backward"]
    scoped, total = facts["matmuls"]
    assert total > 0 and scoped >= 0.95 * total, facts["matmuls"]


def test_scopes_in_pp2_step(four):
    # 1f1b over two pipe ranks: rank 0's first chunk still runs its
    # forward in tick.F; the last chunk's does not, and nothing reads the
    # head's output there, so no head matmul is under tick.F
    facts = four["pp2"]
    assert {S.TICK_F, S.TICK_B} <= set(facts["found"])
    assert {S.ATTENTION, S.MLP} <= set(facts["f_matmul_layers"])
    assert S.HEAD not in facts["f_matmul_layers"]
    assert facts["b_replay"] and facts["b_backward"]
    assert four["pp2_metrics"]["fwd_fused"] == N_MICRO


def test_counters_ep_whole_step(four):
    # batch 4 over two data shards, tokens split over two model shards,
    # two MoE layers of top-2: every assignment counted once
    routed = 4 * SEQ * 2 * 2
    assert four["metrics"]["moe_routed"] == routed
    assert 0 < four["metrics"]["moe_kept"] <= routed
    assert four["dropless"]["moe_kept"] == four["dropless"]["moe_routed"] \
        == routed


def test_kept_matches_numpy_count_ep2(four):
    assert four["ep_routed"] == four["T_K"]
    assert four["ep_kept"] == four["ep_want"] < four["T_K"]


def test_scope_names_fixed():
    names = S.TICKS + S.LAYERS
    assert len(set(names)) == len(names) == 16
    assert all(re.fullmatch(r"[a-z_]+(\.[A-Za-z_]+)?", n) for n in names)
