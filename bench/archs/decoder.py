"""The pre-norm decoder with grouped-query attention: qwen2 and olmoe.

The architecture description (``bench/arch.py``) of every configuration
whose ``arch`` is ``decoder``:

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
  shard (token, then slot order) before the per-expert capacity.

Every layer is dense or every layer is MoE; ``d_head`` is
``hidden_size / num_attention_heads`` for queries, keys and values alike.
``dims_of`` refuses a file with a key it does not read.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.reference import BLOCK, NEG, ROUNDING, ein, rmsnorm, rope

# keys that describe the run, not the model
HARNESS = ("name", "source", "paper", "model_type", "repro_spec", "arch",
           "reduced", "assumed", "deployment", "departures", "parallel",
           "training")
# the shape keys dims_of reads
READS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "intermediate_size", "vocab_size", "num_hidden_layers",
         "tie_word_embeddings", "qkv_bias", "rope_theta", "rms_norm_eps",
         "num_experts", "num_experts_per_tok", "router_aux_loss_coef")
# keys the decoder builds at one value only
FIXED = {"attention_bias": False, "hidden_act": "silu",
         "norm_topk_prob": True}
# rotary positions have no table: the traffic's length is the one run
UNUSED = ("max_position_embeddings",)


@dataclasses.dataclass(frozen=True)
class Dims:
    h: int
    n_h: int
    n_kv: int
    d_head: int
    ff: int                      # dense MLP width (0: every layer is MoE)
    vocab: int
    layers: int
    tied: bool
    qkv_bias: bool
    rope_theta: float
    eps: float
    experts: int = 0             # routed experts (0: dense)
    top_k: int = 0
    expert_ff: int = 0
    capacity_factor: float = 1.25
    aux_coef: float = 0.0

    @property
    def moe(self) -> bool:
        return self.experts > 0


def _refuse_unread(config: Dict[str, Any]) -> None:
    for key, value in config.items():
        if key in FIXED:
            if value != FIXED[key]:
                raise ValueError(f"bench/archs/decoder.py builds {key} = "
                                 f"{FIXED[key]!r} only; the file states "
                                 f"{value!r}")
        elif key not in HARNESS + READS + UNUSED:
            raise ValueError(f"bench/archs/decoder.py does not read the key "
                             f"{key!r}: this file describes another "
                             "architecture")


def dims_of(config: Dict[str, Any]) -> Dims:
    """Read a ``bench/configs`` file (Hugging Face key names)."""
    _refuse_unread(config)
    c = config
    h, n_h, n_kv = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"])
    if h % n_h or n_h % n_kv:
        raise ValueError(f"bench/archs/decoder.py needs heads that divide "
                         f"the width and KV heads that divide the heads: "
                         f"{h}, {n_h}, {n_kv}")
    moe = "num_experts" in c
    return Dims(
        h=h, n_h=n_h, n_kv=n_kv, d_head=h // n_h,
        ff=0 if moe else c["intermediate_size"], vocab=c["vocab_size"],
        layers=c["num_hidden_layers"], tied=bool(c["tie_word_embeddings"]),
        qkv_bias=bool(c.get("qkv_bias", False)),
        rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        experts=c.get("num_experts", 0), top_k=c.get("num_experts_per_tok", 0),
        expert_ff=c["intermediate_size"] if moe else 0,
        capacity_factor=float(c["training"].get("capacity_factor", 1.25)),
        aux_coef=float(c.get("router_aux_loss_coef", 0.0)))


def layout(d: Dims) -> Dict[str, Any]:
    """The parameter tree, as ``(shape, dtype, rule)`` leaves (the rules
    ``weights.make`` knows)."""
    L, h, bf = d.layers, d.h, jnp.bfloat16
    attn = {"wq": ((L, h, d.n_h * d.d_head), bf, "in"),
            "wk": ((L, h, d.n_kv * d.d_head), bf, "in"),
            "wv": ((L, h, d.n_kv * d.d_head), bf, "in"),
            "wo": ((L, d.n_h * d.d_head, h), bf, "in")}
    if d.qkv_bias:
        attn.update(bq=((L, d.n_h * d.d_head), bf, "bias"),
                    bk=((L, d.n_kv * d.d_head), bf, "bias"),
                    bv=((L, d.n_kv * d.d_head), bf, "bias"))
    layer = {"ln1": {"scale": ((L, h), bf, "one")},
             "ln2": {"scale": ((L, h), bf, "one")},
             "attn": attn}
    if d.moe:
        E, f = d.experts, d.expert_ff
        layer["moe"] = {"router": ((L, h, E), jnp.float32, "h"),
                        "we_gate": ((L, E, h, f), bf, "in"),
                        "we_up": ((L, E, h, f), bf, "in"),
                        "we_down": ((L, E, f, h), bf, "in")}
    else:
        layer["mlp"] = {"gate": ((L, h, d.ff), bf, "in"),
                        "up": ((L, h, d.ff), bf, "in"),
                        "down": ((L, d.ff, h), bf, "in")}
    tree = {"embed": {"w": ((d.vocab, h), bf, "h")},
            "dense_layers": {} if d.moe else layer,
            "moe_layers": layer if d.moe else {},
            "final_norm": {"scale": ((h,), bf, "one")}}
    if not d.tied:
        tree["head"] = {"w": ((h, d.vocab), bf, "in")}
    return tree


def spec_of(config: Dict[str, Any]):
    """The program's ``ModelSpec`` with every size the file states, and
    the ``ModelOptions`` fields this description fixes."""
    from repro.configs import get_spec
    d = dims_of(config)
    if d.moe and d.aux_coef != 0.01:
        raise ValueError("the executor weighs the MoE aux loss by 0.01; "
                         f"the file states {d.aux_coef}")
    base = get_spec(config["repro_spec"])
    moe = base.moe
    if d.moe:
        moe = dataclasses.replace(moe, n_routed=d.experts, n_active=d.top_k,
                                  d_ff_expert=d.expert_ff)
    spec = dataclasses.replace(
        base, n_layers=d.layers, h=d.h, n_h=d.n_h, n_kv=d.n_kv,
        d_head=d.d_head, h_ff=d.ff, vocab=d.vocab, rope_theta=d.rope_theta,
        norm_eps=d.eps, tie_embeddings=d.tied, qkv_bias=d.qkv_bias, moe=moe)
    return spec, {"router_impl": "softmax",
                  "capacity_factor": d.capacity_factor}


# ---- the plain reference (bench/reference.py drives it) ----

def _attention(q_, q, k, v):
    """Causal softmax attention, (b, s, n, d) each, over query blocks."""
    b, s, n, d = q.shape
    bq = min(BLOCK, s)
    qb = q.reshape(b, s // bq, bq, n, d).transpose(1, 0, 2, 3, 4)

    def block(args):
        qi, i = args
        sc = ein(q_, "bqnd,bknd->bnqk", qi, k) * d ** -0.5
        qpos = i * bq + jnp.arange(bq)
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos[:, None], sc, NEG)
        return ein(q_, "bnqk,bknd->bqnd", jax.nn.softmax(sc, -1), v)

    out = jax.lax.map(jax.checkpoint(block), (qb, jnp.arange(s // bq)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, n, d)


def _rank(onehot, axis):
    """Rank of each entry within its column of ``onehot``, along ``axis``."""
    return jnp.sum((jnp.cumsum(onehot, axis) - 1) * onehot, -1)


def _moe(q_, p, x, d: Dims, dp: int = 1, ep: int = 1):
    """Routed experts over the flat tokens x (T, h) of one microbatch, whose
    ``dp`` data shards (T/dp consecutive tokens each) drop on their own;
    returns (y, aux)."""
    T, E, K = x.shape[0], d.experts, d.top_k
    G = T // dp
    probs = jax.nn.softmax(ein(lambda t: t, "th,he->te", x, p["router"]),
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
    a = jax.nn.silu(ein(q_, "gech,ehf->gecf", xe, p["we_gate"])) \
        * ein(q_, "gech,ehf->gecf", xe, p["we_up"])
    ye = ein(q_, "gecf,efh->gech", a, p["we_down"])
    got = ye[g, flat_e, jnp.minimum(pos, C - 1)] \
        * (gate.reshape(dp, G * K) * keep)[..., None]
    y = got.reshape(T, K, -1).sum(1)
    return y, aux


def _layer(q_, d: Dims, x, p, dp: int, ep: int):
    b, s, h = x.shape
    a = p["attn"]
    h1 = rmsnorm(x, p["ln1"]["scale"], d.eps)
    q = ein(q_, "bsh,hf->bsf", h1, a["wq"])
    k = ein(q_, "bsh,hf->bsf", h1, a["wk"])
    v = ein(q_, "bsh,hf->bsf", h1, a["wv"])
    if d.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(b, s, d.n_h, d.d_head), d.rope_theta)
    k = rope(k.reshape(b, s, d.n_kv, d.d_head), d.rope_theta)
    v = v.reshape(b, s, d.n_kv, d.d_head)
    rep = d.n_h // d.n_kv
    ctx = _attention(q_, q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2))
    x = x + ein(q_, "bsf,fh->bsh", ctx.reshape(b, s, -1), a["wo"])
    h2 = rmsnorm(x, p["ln2"]["scale"], d.eps)
    if d.moe:
        y, aux = _moe(q_, p["moe"], h2.reshape(b * s, h), d, dp, ep)
        return x + y.reshape(b, s, h), aux
    m = p["mlp"]
    act = jax.nn.silu(ein(q_, "bsh,hf->bsf", h2, m["gate"])) \
        * ein(q_, "bsh,hf->bsf", h2, m["up"])
    return x + ein(q_, "bsf,fh->bsh", act, m["down"]), jnp.float32(0)


def micro_loss(w, tokens, weight, d: Dims, precision: str = "float32",
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
    z = rmsnorm(x, w["final_norm"]["scale"], d.eps)
    w_out = w["embed"]["w"].T if d.tied else w["head"]["w"]
    tgt = jnp.roll(tokens, -1, axis=1)
    cs = min(BLOCK, s)

    def chunk(args):
        zc, tc, wc = args
        lg = ein(q_, "bch,hv->bcv", zc, w_out)
        gold = jnp.take_along_axis(lg, tc[..., None], -1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(lg, -1) - gold) * wc)

    split = lambda t: jnp.moveaxis(t.reshape(b, s // cs, cs, *t.shape[2:]),
                                   1, 0)
    ce = jnp.sum(jax.lax.map(jax.checkpoint(chunk),
                             (split(z), split(tgt), split(weight))))
    return ce / jnp.sum(weight) + d.aux_coef * aux


# ---- the work the metrics count ----

def flops_per_token(config, seq_len: int) -> float:
    """6 x the active matmul parameters (the attention projections, the
    dense MLP or the router and ``k`` of the ``E`` experts, and the output
    head; not the embedding lookup) plus causal attention,
    ``6 * layers * (s / 2) * heads * (d_qk + d_v)``."""
    d = dims_of(config)
    attn = d.h * d.n_h * d.d_head + 2 * d.h * d.n_kv * d.d_head \
        + d.n_h * d.d_head * d.h
    ffn = (d.top_k * 3 * d.h * d.expert_ff + d.h * d.experts) if d.moe \
        else 3 * d.h * d.ff
    active = d.layers * (attn + ffn) + d.h * d.vocab
    return 6.0 * active + 6.0 * d.layers * (seq_len / 2) * d.n_h \
        * (2 * d.d_head)


def _flash_work(config, traffic):
    """The local microbatch ``b``, sequence ``s``, the device's ``n_h``
    heads of ``d_qk = d_v = d_head``, as causal work:
    ``2 * b * n_h * (s**2 / 2) * (d_qk + d_v)`` FLOPs, and q, k, v read
    and o written once in bf16."""
    d = dims_of(config)
    pp, dp, tp = config["parallel"]["mesh"]
    b = int(traffic["global_batch"]) // int(traffic["n_micro"]) // dp
    s = int(traffic["seq_len"])
    n_h = d.n_h // tp
    dq = dv = d.d_head
    flops = 2.0 * b * n_h * (s * s / 2) * (dq + dv)
    nbytes = 2.0 * b * s * n_h * (dq + dq + dv + dv)
    return flops, nbytes


def _gmm_work(config, traffic):
    """One of the layer's three calls (gate, up, down), on one chip: its
    data shard's tokens; with expert parallelism its share of the experts
    at their full width and the rows routed to them, else every expert
    with its width split over the model axis.  Counted on the ``T * k``
    routed rows (``T * k / ep`` under expert parallelism), not the
    ``E * C`` capacity rows the static-capacity kernel computes:
    ``2 * T * k * h * f`` FLOPs and, in bf16, the routed rows read and
    written once and every expert matrix read once."""
    d = dims_of(config)
    pp, dp, tp = config["parallel"]["mesh"]
    ep = int(config["parallel"]["ep"])
    T = int(traffic["global_batch"]) // int(traffic["n_micro"]) // dp \
        * int(traffic["seq_len"])
    if ep > 1:
        rows, f, E = T * d.top_k // ep, d.expert_ff, d.experts // ep
    else:
        rows, f, E = T * d.top_k, d.expert_ff // tp, d.experts
    h = d.h
    flops = 2.0 * rows * h * f
    nbytes = 2.0 * (rows * h + E * h * f + rows * f)
    return flops, nbytes


_WORK = {"_flash_attention_jit": _flash_work, "_gmm_jit": _gmm_work}


def call_work(kernel: str, config, traffic):
    """(FLOPs, bytes) of one call of ``kernel`` in this cell."""
    return _WORK[kernel](config, traffic)
