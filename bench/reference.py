"""Plain reference: the configured model's loss, gradients and AdamW steps.

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``, written from
the model's equations and importing nothing of the program.  What the
configuration states it keeps: the forward pass takes the working copy of
the weights (the float32 master rounded to ``param_dtype``) and the
optimizer stores its moments in ``moment_dtype``.  It takes the
benchmark's weights (``weights.make``) and batches (``data``) from the
seed, and follows the program's first three steps:

* pre-norm decoder layers: RMSNorm, attention with rotary embeddings
  (half-split pairs) and grouped KV heads, then a SwiGLU MLP or a routed
  MoE; a final RMSNorm and the output head (the embedding, transposed,
  when tied); token cross-entropy over the shifted targets, its mean over
  the microbatch;
* the MoE: softmax router in float32, top-k experts with their gates
  renormalised to sum to one, a static capacity of
  ``round(T·k/E·capacity_factor)`` per expert (assignments taken in token
  order, then slot order; the rest dropped), and the Switch load-balance
  loss ``E·Σ_e mean(p_e)·frac_e`` over the whole microbatch times the
  configured coefficient.  Each data shard of the mesh (consecutive rows
  of the microbatch) drops on its own; with ``ep`` expert shards its
  tokens are cut into ``ep`` consecutive chunks, and each chunk sends at
  most ``round(T_c·k/ep·capacity_factor)`` assignments to each expert
  shard (token, then slot order) before the per-expert capacity;
* the step: the mean of the microbatch losses, its gradient, clipping
  by the global norm, then AdamW with bias correction and decoupled
  weight decay on every leaf.

``precision`` rounds the operands of every matrix product, forward and
backward: ``float32`` leaves them alone; ``int8`` rounds them to 8-bit
integers and ``fp8`` to float8 (e4m3 forward, e5m2 for the cotangents),
each with one scale per tensor.  Both lie below the configured bfloat16,
and the check must fail each of them in the program's place.

Attention runs over blocks of queries and the loss over blocks of
positions, each rematerialised, and every layer is rematerialised, so the
reference fits beside its own optimizer state at the timed sizes.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import data
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


def _ein(q, spec, *xs):
    return jnp.einsum(spec, *(q(x) for x in xs), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (b, s, n, d): rotate the pairs (i, i + d/2) by position·θ^(-2i/d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (s, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q_, q, k, v):
    """Causal softmax attention, (b, s, n, d) each, over query blocks."""
    b, s, n, d = q.shape
    bq = min(BLOCK, s)
    qb = q.reshape(b, s // bq, bq, n, d).transpose(1, 0, 2, 3, 4)

    def block(args):
        qi, i = args
        sc = _ein(q_, "bqnd,bknd->bnqk", qi, k) * d ** -0.5
        qpos = i * bq + jnp.arange(bq)
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos[:, None], sc, NEG)
        return _ein(q_, "bnqk,bknd->bqnd", jax.nn.softmax(sc, -1), v)

    out = jax.lax.map(jax.checkpoint(block), (qb, jnp.arange(s // bq)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, n, d)


def _rank(onehot, axis):
    """Rank of each entry within its column of ``onehot``, along ``axis``."""
    return jnp.sum((jnp.cumsum(onehot, axis) - 1) * onehot, -1)


def _moe(q_, p, x, d: W.Dims, dp: int = 1, ep: int = 1):
    """Routed experts over the flat tokens x (T, h) of one microbatch, whose
    ``dp`` data shards (T/dp consecutive tokens each) drop on their own;
    returns (y, aux)."""
    T, E, K = x.shape[0], d.experts, d.top_k
    G = T // dp
    probs = jax.nn.softmax(_ein(lambda t: t, "th,he->te", x, p["router"]),
                           -1)
    top, eid = jax.lax.top_k(probs, K)
    gate = top / (top.sum(-1, keepdims=True) + 1e-20)
    onehot = jax.nn.one_hot(eid.reshape(dp, G * K), E, dtype=jnp.int32)
    frac = jnp.mean(onehot.reshape(T, K, E).sum(1), 0) / K
    aux = E * jnp.sum(jnp.mean(probs, 0) * frac)
    if ep > 1:
        # each chunk's send buckets, one per expert shard
        n = G // ep * K
        to = onehot.reshape(dp, ep, n, ep, E // ep).sum(-1)
        sent = (_rank(to, 2) < int(max(1, round(n / ep
                                                * d.capacity_factor))))
        onehot = onehot * sent.reshape(dp, G * K, 1)
    # rank of each assignment within its expert, in token-then-slot order
    pos = _rank(onehot, 1)
    C = int(max(1, round(G * K / E * d.capacity_factor)))
    keep = (pos < C) & (onehot.sum(-1) > 0)
    flat_e = eid.reshape(dp, G * K)
    g = jnp.arange(dp)[:, None]
    tok = jnp.broadcast_to(jnp.repeat(jnp.arange(G), K), (dp, G * K))
    slot = jnp.full((dp, E, C), G, jnp.int32).at[
        g, flat_e, jnp.where(keep, pos, C)].set(tok, mode="drop")
    xg = jnp.concatenate([x.reshape(dp, G, -1),
                          jnp.zeros((dp, 1, x.shape[1]), x.dtype)], 1)
    xe = xg[g[:, :, None], slot]                              # (dp, E, C, h)
    a = jax.nn.silu(_ein(q_, "gech,ehf->gecf", xe, p["we_gate"])) \
        * _ein(q_, "gech,ehf->gecf", xe, p["we_up"])
    ye = _ein(q_, "gecf,efh->gech", a, p["we_down"])
    got = ye[g, flat_e, jnp.minimum(pos, C - 1)] \
        * (gate.reshape(dp, G * K) * keep)[..., None]
    y = got.reshape(T, K, -1).sum(1)
    return y, aux


def _layer(q_, d: W.Dims, x, p, dp: int, ep: int):
    b, s, h = x.shape
    a = p["attn"]
    h1 = _rmsnorm(x, p["ln1"]["scale"], d.eps)
    q = _ein(q_, "bsh,hf->bsf", h1, a["wq"])
    k = _ein(q_, "bsh,hf->bsf", h1, a["wk"])
    v = _ein(q_, "bsh,hf->bsf", h1, a["wv"])
    if d.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(b, s, d.n_h, d.d_head), d.rope_theta)
    k = _rope(k.reshape(b, s, d.n_kv, d.d_head), d.rope_theta)
    v = v.reshape(b, s, d.n_kv, d.d_head)
    rep = d.n_h // d.n_kv
    ctx = _attention(q_, q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2))
    x = x + _ein(q_, "bsf,fh->bsh", ctx.reshape(b, s, -1), a["wo"])
    h2 = _rmsnorm(x, p["ln2"]["scale"], d.eps)
    if d.moe:
        y, aux = _moe(q_, p["moe"], h2.reshape(b * s, h), d, dp, ep)
        return x + y.reshape(b, s, h), aux
    m = p["mlp"]
    act = jax.nn.silu(_ein(q_, "bsh,hf->bsf", h2, m["gate"])) \
        * _ein(q_, "bsh,hf->bsf", h2, m["up"])
    return x + _ein(q_, "bsf,fh->bsh", act, m["down"]), jnp.float32(0)


def micro_loss(w, tokens, weight, d: W.Dims, precision: str = "float32",
               dp: int = 1, ep: int = 1):
    """Loss of one microbatch: tokens (b, s) int32; weight (b, s) float32,
    the loss weight of each target position (target t + 1 at position t);
    ``dp`` data shards of its rows, ``ep`` expert shards (``_moe``)."""
    q_ = ROUNDING[precision]
    b, s = tokens.shape
    x = w["embed"]["w"][tokens]
    group = w["moe_layers"] if d.moe else w["dense_layers"]

    def body(carry, p):
        x, aux = carry
        x, a = _layer(q_, d, x, p, dp, ep)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(jax.checkpoint(body), (x, jnp.float32(0)),
                               group)
    z = _rmsnorm(x, w["final_norm"]["scale"], d.eps)
    w_out = w["embed"]["w"].T if d.tied else w["head"]["w"]
    tgt = jnp.roll(tokens, -1, axis=1)
    cs = min(BLOCK, s)

    def chunk(args):
        zc, tc, wc = args
        lg = _ein(q_, "bch,hv->bcv", zc, w_out)
        gold = jnp.take_along_axis(lg, tc[..., None], -1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(lg, -1) - gold) * wc)

    split = lambda t: jnp.moveaxis(t.reshape(b, s // cs, cs, *t.shape[2:]),
                                   1, 0)
    ce = jnp.sum(jax.lax.map(jax.checkpoint(chunk),
                             (split(z), split(tgt), split(weight))))
    return ce / jnp.sum(weight) + d.aux_coef * aux


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


def change_of(d: W.Dims, key, master):
    """Per leaf: the norm of the change from the seed's weights, and the
    change in bfloat16 (for the norm of a difference)."""
    def one(a, b):
        c = a.astype(jnp.float32) - b.astype(jnp.float32)
        return norm(c), c.astype(jnp.bfloat16)
    pairs = jax.tree.map(one, master, W.make(d, key, jnp.float32))
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
    d = W.dims_of(config)
    dp, ep = int(config["parallel"]["mesh"][1]), int(config["parallel"]["ep"])
    opt = config["training"]["optimizer"]
    M = int(traffic["n_micro"])
    s = int(traffic["seq_len"])
    key = W.seed_key(seed)
    with jax.default_matmul_precision("highest"):
        master = jax.jit(lambda k: W.make(d, k, jnp.float32))(key)
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
            lambda w, t, wt: micro_loss(w, t, wt, d, precision, dp, ep)))
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
        norms, delta = jax.jit(lambda k, p: change_of(d, k, p))(key, master)
        del master
    return {"loss": losses, "grad": first, "change": by_path(norms),
            "grad_arrays": first_m, "change_arrays": on_host(delta)}
