"""a2a (shard_map all-to-all) MoE vs the GSPMD scatter path: numerical
equivalence on a small multi-device mesh.

Needs >1 fake device, which must be set before jax initialises — so the
mesh-dependent checks run in a subprocess with XLA_FLAGS set.
"""

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
    from jax.sharding import Mesh
    from repro.configs import get_spec
    from repro.models.moe import moe_forward, moe_init
    from repro.models.moe_a2a import moe_forward_a2a

    spec = get_spec("olmoe-1b-7b", smoke=True)   # 4 experts top-2
    mesh = make_mesh((2, 4), ("data", "model"))
    p = moe_init(jax.random.PRNGKey(0), spec)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, spec.h), jnp.float32)

    # capacity high enough that neither path drops tokens
    cap = float(spec.moe.n_routed) * 4
    with mesh:
        out = jax.jit(lambda p_, x_: moe_forward_a2a(
            p_, spec, x_, mesh=mesh, capacity_factor=cap))(p32, x)
    ref = moe_forward(p32, spec, x, capacity_factor=cap)
    err = float(jnp.abs(out.y - ref.y).max())
    assert err < 2e-3, f"a2a vs scatter max err {err}"

    # router_probs regression: the zeros stub is gone — a2a returns the
    # assembled global (T, E) probs, identical to the scatter path's
    # (routing is per-token, so sharding cannot change it)
    assert out.router_probs.shape == ref.router_probs.shape, \
        (out.router_probs.shape, ref.router_probs.shape)
    perr = float(jnp.abs(out.router_probs - ref.router_probs).max())
    assert perr < 1e-5, f"a2a router_probs diverged {perr}"
    assert float(jnp.abs(out.router_probs).max()) > 0

    # gradients flow through the exchange
    with mesh:
        g = jax.jit(jax.grad(lambda x_: moe_forward_a2a(
            p32, spec, x_, mesh=mesh, capacity_factor=cap).y.sum()))(x)
    assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0
    print("A2A_OK", err, perr)
""")


@pytest.mark.slow
def test_a2a_matches_scatter_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "A2A_OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


def test_local_capacity_applies_factor_once():
    """Regression for the double-applied capacity_factor: ``c_send``
    already includes it, and ``c_loc`` was derived from ``M·c_send`` and
    multiplied by it AGAIN (~cf× oversized local buffer).  The local
    (E_loc, C, h) capacity must match the estimator's per-expert term:
    C = E_token·cf = tk/E_loc·cf — the same C the ep=1 scatter path books
    (``moe_forward``'s  round(T·K/E·cf)  with T·K = tk·M, E = E_loc·M)."""
    from repro.models.moe_a2a import local_expert_capacity

    for tk, e_loc, cf in [(64, 1, 1.25), (64, 2, 1.25), (256, 8, 1.0),
                          (1024, 16, 1.25), (100, 3, 2.0)]:
        got = local_expert_capacity(tk, e_loc, cf)
        assert got == max(1, round(tk / e_loc * cf)), (tk, e_loc, cf, got)
        # the old formula: round(M*c_send/E_loc * cf) with c_send already
        # cf-scaled — strictly larger whenever cf > 1
        for m in (2, 4):
            c_send = max(1, round(tk / m * cf))
            old = max(1, round(m * c_send / e_loc * cf))
            if cf > 1 and tk / e_loc * cf > 4:
                assert got < old, (tk, e_loc, cf, m, got, old)


def test_local_capacity_matches_estimator_dispatch_row():
    """The buffer the a2a path allocates is byte-for-byte the estimator's
    ``(E/ep, C, h)`` dispatch term: n_local·C·h at the activation width
    equals the E_token-based routed buffer row of
    ``core.activations.moe_activation_bytes`` (cf=1 ⇒ C == E_token)."""
    from repro.configs import get_spec
    from repro.models.moe_a2a import local_expert_capacity

    spec = get_spec("olmoe-1b-7b")
    e = spec.moe
    b, s, M = 2, 4096, 8           # 8-way model axis, tokens seq-sharded
    t_loc = b * s // M
    tk = t_loc * e.n_active
    e_loc = e.n_routed // M
    c = local_expert_capacity(tk, e_loc, 1.0)
    e_token_global = b * s * e.n_active / e.n_routed
    assert c == round(e_token_global), (c, e_token_global)
