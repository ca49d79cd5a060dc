"""Plain reference: the configured model's loss, gradients and AdamW steps.

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``, written from
the model's equations and importing nothing of the program.  The model's
forward and loss of one microbatch is the ``micro_loss`` of the
configuration's architecture description (``bench/archs``), built from
the pieces here (``ein``, ``rmsnorm``, ``rope``).  What the configuration
states it keeps: the forward pass takes the working copy of the weights
(the float32 master rounded to ``param_dtype``) and the optimizer stores
its moments in ``moment_dtype``.  It takes the benchmark's weights
(``weights.make``) and batches (``data``) from the seed, and follows the
program's first three steps: the mean of the microbatch losses, its
gradient, clipping by the global norm, then AdamW with bias correction
and decoupled weight decay on every leaf.

``precision`` rounds the operands of every matrix product, forward and
backward: ``float32`` leaves them alone; ``int8`` rounds them to 8-bit
integers and ``fp8`` to float8 (e4m3 forward, e5m2 for the cotangents),
each with one scale per tensor.  Both lie below the configured bfloat16,
and the check must fail each of them in the program's place.

A description runs attention over blocks of queries (``BLOCK``) and the
loss over blocks of positions, each rematerialised, and rematerialises
every layer, so that the reference fits beside its own optimizer state at
the timed sizes.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch, data
from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 512
NEG = -1e30


def _scaled(x, dt, top):
    s = jnp.max(jnp.abs(x)) / top
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dt).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    return _scaled(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, g: (_scaled(g, jnp.float8_e5m2, 57344.0),))


def _int8_round(x):
    s = jnp.max(jnp.abs(x)) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(x / s) * s


@jax.custom_vjp
def _int8(x):
    return _int8_round(x)


_int8.defvjp(lambda x: (_int8(x), None), lambda _, g: (_int8_round(g),))


ROUNDING: Dict[str, Callable] = {"float32": lambda x: x, "fp8": _fp8,
                                 "int8": _int8}


def ein(q, spec, *xs):
    return jnp.einsum(spec, *(q(x) for x in xs), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x (b, s, n, d): rotate the pairs (i, i + d/2) by position·θ^(-2i/d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (s, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss_weights(b: int, s: int) -> np.ndarray:
    """(b, s) weights of the targets: every position but the last."""
    w = np.ones((b, s), np.float32)
    w[:, -1] = 0.0
    return w


def norm(x):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x))


def by_path(tree) -> Dict[str, float]:
    """A tree of scalars as ``{path: float}``, the paths the program's
    parameter tree has (``['embed']['w']``)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(v) for k, v in flat}


def on_host(tree) -> Dict[str, np.ndarray]:
    """A tree of arrays as ``{path: numpy array}``, copied to the host."""
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat}


def change_of(desc, d, key, master):
    """Per leaf: the norm of the change from the seed's weights (those of
    the description ``desc`` with dims ``d``), and the change in bfloat16
    (for the norm of a difference)."""
    def one(a, b):
        c = a.astype(jnp.float32) - b.astype(jnp.float32)
        return norm(c), c.astype(jnp.bfloat16)
    pairs = jax.tree.map(one, master, W.make(desc, d, key, jnp.float32))
    leaf = lambda x: isinstance(x, tuple)
    return (jax.tree.map(lambda t: t[0], pairs, is_leaf=leaf),
            jax.tree.map(lambda t: t[1], pairs, is_leaf=leaf))


def readings(config: Dict[str, Any], traffic: Dict[str, Any], seed: int,
             precision: str = "float32", steps: int = 3) -> Dict[str, Any]:
    """The reference's numbers for the first ``steps`` steps: the loss of
    each, the per-leaf norm of the first (clipped) gradient, and the
    per-leaf norm of the parameters' change over all of them; beside the
    norms, on the host, the first moment after the first step and the
    change in bfloat16 (``grad_arrays``, ``change_arrays``)."""
    desc = arch.of(config)
    d = desc.dims_of(config)
    dp, ep = int(config["parallel"]["mesh"][1]), int(config["parallel"]["ep"])
    opt = config["training"]["optimizer"]
    M = int(traffic["n_micro"])
    s = int(traffic["seq_len"])
    key = W.seed_key(seed)
    with jax.default_matmul_precision("highest"):
        master = jax.jit(lambda k: W.make(desc, d, k, jnp.float32))(key)
        tr = config["training"]
        m = jax.tree.map(lambda x: jnp.zeros(x.shape, tr["moment_dtype"]),
                         master)
        v = jax.tree.map(lambda x: jnp.zeros(x.shape, tr["moment_dtype"]),
                         master)
        # the forward takes the working copy of the weights and the
        # optimizer keeps its moments in the types the configuration
        # states; everything else is float32
        working = lambda x: x.astype(tr["param_dtype"]).astype(jnp.float32)
        moment = lambda x: x.astype(tr["moment_dtype"])
        grad_fn = jax.jit(jax.value_and_grad(
            lambda w, t, wt: desc.micro_loss(w, t, wt, d, precision, dp,
                                              ep)))
        rounded = jax.jit(lambda tree: jax.tree.map(working, tree))
        add = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g),
                      donate_argnums=0)

        def _adamw(master, m, v, g, t):
            gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
            clip = jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-12))
            g = jax.tree.map(lambda x: x * clip, g)
            m = jax.tree.map(lambda a, x: opt["b1"] * a.astype(jnp.float32)
                             + (1 - opt["b1"]) * x, m, g)
            v = jax.tree.map(lambda a, x: opt["b2"] * a.astype(jnp.float32)
                             + (1 - opt["b2"]) * x * x, v, g)
            bc1, bc2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
            master = jax.tree.map(
                lambda p, a, b: p - opt["lr"] * (
                    (a / bc1) / (jnp.sqrt(b / bc2) + opt["eps"])
                    + opt["weight_decay"] * p), master, m, v)
            m, v = jax.tree.map(moment, m), jax.tree.map(moment, v)
            return master, m, v, jax.tree.map(norm, g)

        adamw = jax.jit(_adamw, donate_argnums=(0, 1, 2, 3))
        losses, first, first_m = [], None, None
        for i in range(steps):
            toks = data.make_tokens(traffic, d.vocab, seed, i)
            toks = toks.reshape(M, -1, s)
            wt = jnp.asarray(loss_weights(toks.shape[1], s))
            acc, total = None, 0.0
            w = rounded(master)
            for j in range(M):
                l, g = grad_fn(w, jnp.asarray(toks[j]), wt)
                total += float(l)
                acc = g if acc is None else add(acc, g)
                del g
            del w
            acc = jax.tree.map(lambda x: x / M, acc) if M > 1 else acc
            master, m, v, gnorm = adamw(master, m, v, acc, float(i + 1))
            del acc
            if first is None:
                first, first_m = by_path(gnorm), on_host(m)
            losses.append(total / M)
        del m, v
        norms, delta = jax.jit(lambda k, p: change_of(desc, d, k, p))(
            key, master)
        del master
    return {"loss": losses, "grad": first, "change": by_path(norms),
            "grad_arrays": first_m, "change_arrays": on_host(delta)}
