"""DeepSeek-V2's decoder: latent attention, a dense prefix, shared experts.

The architecture description (``bench/arch.py``) of every configuration
whose ``arch`` is ``mla_moe``, written from the DeepSeek-V2 report
(arXiv:2405.04434, §2 and appendix B) and the published ``config.json``:

* pre-norm layers: RMSNorm, multi-head latent attention, RMSNorm, then a
  SwiGLU MLP in the first ``first_k_dense_replace`` layers and an MoE in
  the rest; a final RMSNorm and an untied output head; token
  cross-entropy over the shifted targets, its mean over the microbatch,
  plus ``aux_loss_alpha`` times the summed balance losses;
* the attention without query compression (``q_lora_rank`` null):
  ``q = W^Q x`` gives each head ``qk_nope_head_dim`` + ``qk_rope_head_dim``
  columns; the key-value latent ``c = RMSNorm(W^DKV x)`` gives each
  head's ``k_nope = W^UK c`` and ``v = W^UV c``; one rope key
  ``W^KR x`` is shared by the heads; the rope parts of q and k turn by
  YaRN's frequencies (``rope_scaling``, half-split pairs), and the
  softmax scale is ``(d_nope + d_rope)^-1/2 · mscale²`` with
  ``mscale = 0.1 · mscale_all_dim · ln(factor) + 1``;
* the MoE: a float32 softmax router over all ``deployment.n_routed_experts``
  experts, the greedy top-k with the gates as they are
  (``norm_topk_prob`` false, ``routed_scaling_factor`` 1), the
  ``n_shared_experts`` shared experts on every token; this chip holds
  experts ``[0, n_routed_experts)`` and computes their part only: a
  static capacity of ``round(T·k/E·capacity_factor)`` slots per held
  expert, assignments taken in token order, then slot order, the rest
  dropped; the balance loss ``E·Σ_e mean(p_e)·frac_e`` over all ``E``
  experts and the microbatch (one sequence per microbatch makes it
  V2's sequence-level loss, ``seq_aux``).

``dims_of`` refuses a file with a key it does not read, or a value of a
key that this description builds at one value only.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.reference import BLOCK, NEG, ROUNDING, ein, rmsnorm

# keys that describe the run, not the model
HARNESS = ("name", "source", "paper", "model_type", "repro_spec", "arch",
           "reduced", "assumed", "deployment", "sizing", "departures",
           "parallel", "training")
# the shape keys dims_of reads
READS = ("hidden_size", "intermediate_size", "kv_lora_rank",
         "first_k_dense_replace", "moe_intermediate_size",
         "n_routed_experts", "n_shared_experts", "num_attention_heads",
         "num_key_value_heads", "num_experts_per_tok", "num_hidden_layers",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rms_norm_eps", "rope_scaling", "rope_theta", "vocab_size")
# keys the description builds at one value only
FIXED = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
         "n_group": 1, "topk_group": 1, "topk_method": "greedy",
         "norm_topk_prob": False, "q_lora_rank": None,
         "routed_scaling_factor": 1, "scoring_func": "softmax",
         "seq_aux": True, "tie_word_embeddings": False}
# rotary positions have no table: the traffic's length is the one run
UNUSED = ("max_position_embeddings",)


@dataclasses.dataclass(frozen=True)
class Dims:
    h: int
    n_h: int
    d_nope: int
    d_rope: int
    d_v: int
    d_c: int                     # kv_lora_rank
    layers: int
    dense_layers: int            # first_k_dense_replace
    ff: int                      # the dense layers' MLP width
    experts: int                 # routed experts the router scores
    held: int                    # of them, the ones this chip holds
    top_k: int
    expert_ff: int
    shared: int                  # shared experts, each expert_ff wide
    vocab: int
    rope_theta: float
    eps: float
    yarn_factor: float
    yarn_original: int
    beta_fast: float
    beta_slow: float
    mscale_all_dim: float
    capacity_factor: float
    aux_coef: float


def _refuse_unread(config: Dict[str, Any]) -> None:
    for key, value in config.items():
        if key in FIXED:
            if value != FIXED[key]:
                raise ValueError(f"bench/archs/mla_moe.py builds {key} = "
                                 f"{FIXED[key]!r} only; the file states "
                                 f"{value!r}")
        elif key not in HARNESS + READS + UNUSED:
            raise ValueError(f"bench/archs/mla_moe.py does not read the key "
                             f"{key!r}: this file describes another "
                             "architecture")


def dims_of(config: Dict[str, Any]) -> Dims:
    """Read a ``bench/configs`` file (Hugging Face key names; the experts
    the router scores in ``deployment``, the balance-loss weight and the
    capacity factor in ``assumed``)."""
    _refuse_unread(config)
    c = config
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("bench/archs/mla_moe.py builds a key and a value "
                         "per query head")
    rs = c["rope_scaling"]
    if rs.get("type") != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError("bench/archs/mla_moe.py builds YaRN rope scaling "
                         "with mscale equal to mscale_all_dim only (cos and "
                         f"sin unscaled); the file states {rs}")
    assumed = c["assumed"]
    return Dims(
        h=c["hidden_size"], n_h=c["num_attention_heads"],
        d_nope=c["qk_nope_head_dim"], d_rope=c["qk_rope_head_dim"],
        d_v=c["v_head_dim"], d_c=c["kv_lora_rank"],
        layers=c["num_hidden_layers"],
        dense_layers=c["first_k_dense_replace"],
        ff=c["intermediate_size"],
        experts=int(c["deployment"]["n_routed_experts"]),
        held=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        expert_ff=c["moe_intermediate_size"], shared=c["n_shared_experts"],
        vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        eps=float(c["rms_norm_eps"]), yarn_factor=float(rs["factor"]),
        yarn_original=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale_all_dim=float(rs["mscale_all_dim"]),
        capacity_factor=float(assumed["capacity_factor"]["value"]),
        aux_coef=float(assumed["aux_loss_alpha"]["value"]))


def _layer_layout(d: Dims, L: int, moe: bool) -> Dict[str, Any]:
    h, bf = d.h, jnp.bfloat16
    attn = {"w_q": ((L, h, d.n_h * (d.d_nope + d.d_rope)), bf, "in"),
            "w_dkv": ((L, h, d.d_c), bf, "in"),
            "w_kr": ((L, h, d.d_rope), bf, "in"),
            "w_uk": ((L, d.d_c, d.n_h * d.d_nope), bf, "in"),
            "w_uv": ((L, d.d_c, d.n_h * d.d_v), bf, "in"),
            "w_o": ((L, d.n_h * d.d_v, h), bf, "in"),
            "kv_norm": {"scale": ((L, d.d_c), bf, "one")}}
    layer = {"ln1": {"scale": ((L, h), bf, "one")},
             "ln2": {"scale": ((L, h), bf, "one")},
             "attn": attn}
    if moe:
        H, f, fs = d.held, d.expert_ff, d.expert_ff * d.shared
        layer["moe"] = {"router": ((L, h, d.experts), jnp.float32, "h"),
                        "we_gate": ((L, H, h, f), bf, "in"),
                        "we_up": ((L, H, h, f), bf, "in"),
                        "we_down": ((L, H, f, h), bf, "in"),
                        # the shared experts side by side: one SwiGLU
                        "shared": {"gate": ((L, h, fs), bf, "in"),
                                   "up": ((L, h, fs), bf, "in"),
                                   "down": ((L, fs, h), bf, "in")}}
    else:
        layer["mlp"] = {"gate": ((L, h, d.ff), bf, "in"),
                        "up": ((L, h, d.ff), bf, "in"),
                        "down": ((L, d.ff, h), bf, "in")}
    return layer


def layout(d: Dims) -> Dict[str, Any]:
    """The parameter tree, as ``(shape, dtype, rule)`` leaves (the rules
    ``weights.make`` knows)."""
    return {"embed": {"w": ((d.vocab, d.h), jnp.bfloat16, "h")},
            "dense_layers": _layer_layout(d, d.dense_layers, False),
            "moe_layers": _layer_layout(d, d.layers - d.dense_layers, True),
            "final_norm": {"scale": ((d.h,), jnp.bfloat16, "one")},
            "head": {"w": ((d.h, d.vocab), jnp.bfloat16, "in")}}


def spec_of(config: Dict[str, Any]):
    """The program's ``ModelSpec`` with every size the file states, and
    the ``ModelOptions`` fields this description fixes."""
    from repro.configs import get_spec
    from repro.core.notation import MLASpec, MoESpec, YaRNSpec
    d = dims_of(config)
    spec = dataclasses.replace(
        get_spec(config["repro_spec"]), n_layers=d.layers, h=d.h,
        n_h=d.n_h, n_kv=d.n_h, d_head=d.d_nope, h_ff=d.ff, vocab=d.vocab,
        rope_theta=d.rope_theta, norm_eps=d.eps, tie_embeddings=False,
        mla=MLASpec(d_cq=None, d_c=d.d_c, d_h=d.d_nope, d_hr=d.d_rope,
                    d_v=d.d_v),
        moe=MoESpec(n_routed=d.experts, n_active=d.top_k,
                    n_shared=d.shared, d_ff_expert=d.expert_ff,
                    first_k_dense=d.dense_layers, n_held=d.held,
                    norm_topk=False, aux_coef=d.aux_coef),
        yarn=YaRNSpec(factor=d.yarn_factor,
                      original_max_position=d.yarn_original,
                      beta_fast=d.beta_fast, beta_slow=d.beta_slow,
                      mscale=d.mscale_all_dim,
                      mscale_all_dim=d.mscale_all_dim))
    return spec, {"router_impl": "softmax",
                  "capacity_factor": d.capacity_factor}


# ---- the plain reference (bench/reference.py drives it) ----

def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn(d: Dims):
    """YaRN's inverse frequencies of the d_rope / 2 rotary pairs: a pair
    turning fewer than ``beta_slow`` times over the original length is
    slowed by ``factor``, one turning more than ``beta_fast`` times is
    kept, with a linear ramp between.  (With ``mscale`` equal to
    ``mscale_all_dim`` cos and sin keep their amplitude.)"""
    n = d.d_rope
    base = 1.0 / d.rope_theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n)

    def dim(turns):
        return n * math.log(d.yarn_original / (turns * 2 * math.pi)) \
            / (2 * math.log(d.rope_theta))
    lo = max(math.floor(dim(d.beta_fast)), 0)
    hi = min(math.ceil(dim(d.beta_slow)), n - 1)
    ramp = jnp.clip((jnp.arange(n // 2, dtype=jnp.float32) - lo)
                    / (hi - lo if hi != lo else 0.001), 0.0, 1.0)
    return base / d.yarn_factor * ramp + base * (1.0 - ramp)


def _rope(x, inv):
    """x (b, s, n, d): rotate the pairs (i, i + d/2) by position·inv_i."""
    s, n = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # (s, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : n // 2], x[..., n // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q_, q, k, v, scale):
    """Causal softmax attention over query blocks: q, k (b, s, n, d_qk),
    v (b, s, n, d_v)."""
    b, s, n, dq = q.shape
    bq = min(BLOCK, s)
    qb = q.reshape(b, s // bq, bq, n, dq).transpose(1, 0, 2, 3, 4)

    def block(args):
        qi, i = args
        sc = ein(q_, "bqnd,bknd->bnqk", qi, k) * scale
        qpos = i * bq + jnp.arange(bq)
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos[:, None], sc, NEG)
        return ein(q_, "bnqk,bknd->bqnd", jax.nn.softmax(sc, -1), v)

    out = jax.lax.map(jax.checkpoint(block), (qb, jnp.arange(s // bq)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, n, v.shape[-1])


def _mla(q_, a, x, d: Dims):
    b, s, _ = x.shape
    n, dn, dr = d.n_h, d.d_nope, d.d_rope
    inv = _yarn(d)
    q = ein(q_, "bsh,hf->bsf", x, a["w_q"]).reshape(b, s, n, dn + dr)
    c = rmsnorm(ein(q_, "bsh,hc->bsc", x, a["w_dkv"]),
                a["kv_norm"]["scale"], d.eps)
    k_rope = _rope(ein(q_, "bsh,hr->bsr", x, a["w_kr"])[:, :, None, :],
                   inv)
    k = jnp.concatenate(
        [ein(q_, "bsc,cf->bsf", c, a["w_uk"]).reshape(b, s, n, dn),
         jnp.broadcast_to(k_rope, (b, s, n, dr))], -1)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv)], -1)
    v = ein(q_, "bsc,cf->bsf", c, a["w_uv"]).reshape(b, s, n, d.d_v)
    scale = (dn + dr) ** -0.5 \
        * _mscale(d.yarn_factor, d.mscale_all_dim) ** 2
    ctx = _attention(q_, q, k, v, scale)
    return ein(q_, "bsf,fh->bsh", ctx.reshape(b, s, -1), a["w_o"])


def _swiglu(q_, m, x):
    act = jax.nn.silu(ein(q_, "...h,hf->...f", x, m["gate"])) \
        * ein(q_, "...h,hf->...f", x, m["up"])
    return ein(q_, "...f,fh->...h", act, m["down"])


def _rank(onehot):
    """Rank of each row's one entry within its column, in row order."""
    return jnp.sum((jnp.cumsum(onehot, 0) - 1) * onehot, -1)


def _moe(q_, p, x, d: Dims):
    """The held experts' part and the shared experts over the flat tokens
    x (T, h) of one microbatch; returns (y, aux)."""
    T, E, K, H = x.shape[0], d.experts, d.top_k, d.held
    probs = jax.nn.softmax(ein(lambda t: t, "th,he->te", x, p["router"]),
                           -1)
    gate, eid = jax.lax.top_k(probs, K)
    onehot = jax.nn.one_hot(eid.reshape(T * K), E, dtype=jnp.int32)
    frac = jnp.mean(onehot.reshape(T, K, E).sum(1), 0) / K
    aux = E * jnp.sum(jnp.mean(probs, 0) * frac)
    # rank of each assignment within its expert, in token-then-slot order;
    # the capacity is that of all E experts
    pos = _rank(onehot)
    C = int(max(1, round(T * K / E * d.capacity_factor)))
    flat_e = eid.reshape(T * K)
    keep = (pos < C) & (flat_e < H)
    tok = jnp.repeat(jnp.arange(T), K)
    # each held expert's C slots hold token ids; T marks an empty slot
    slot = jnp.full((H, C), T, jnp.int32).at[
        jnp.where(keep, flat_e, H), jnp.where(keep, pos, C)].set(
            tok, mode="drop")
    xe = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])[slot]
    a = jax.nn.silu(ein(q_, "ech,ehf->ecf", xe, p["we_gate"])) \
        * ein(q_, "ech,ehf->ecf", xe, p["we_up"])
    ye = ein(q_, "ecf,efh->ech", a, p["we_down"])              # (H, C, h)
    got = ye[jnp.minimum(flat_e, H - 1), jnp.minimum(pos, C - 1)] \
        * (gate.reshape(T * K) * keep)[:, None]
    y = got.reshape(T, K, -1).sum(1)
    return y + _swiglu(q_, p["shared"], x), aux


def _layer(q_, d: Dims, x, p, moe: bool):
    b, s, h = x.shape
    x = x + _mla(q_, p["attn"], rmsnorm(x, p["ln1"]["scale"], d.eps), d)
    h2 = rmsnorm(x, p["ln2"]["scale"], d.eps)
    if moe:
        y, aux = _moe(q_, p["moe"], h2.reshape(b * s, h), d)
        return x + y.reshape(b, s, h), aux
    return x + _swiglu(q_, p["mlp"], h2), jnp.float32(0)


def micro_loss(w, tokens, weight, d: Dims, precision: str = "float32",
               dp: int = 1, ep: int = 1):
    """Loss of one microbatch: tokens (b, s) int32; weight (b, s) float32,
    the loss weight of each target position (target t + 1 at position t).
    One chip's share, without the exchange: ``dp`` and ``ep`` are 1."""
    if dp != 1 or ep != 1:
        raise ValueError("bench/archs/mla_moe.py describes one chip's "
                         f"share only (dp {dp}, ep {ep})")
    q_ = ROUNDING[precision]
    b, s = tokens.shape
    x = w["embed"]["w"][tokens]
    aux = jnp.float32(0)
    for group, moe in (("dense_layers", False), ("moe_layers", True)):
        def body(carry, p, moe=moe):
            x, aux = carry
            x, a = _layer(q_, d, x, p, moe)
            return (x, aux + a), None
        (x, aux), _ = jax.lax.scan(jax.checkpoint(body), (x, aux),
                                   w[group])
    z = rmsnorm(x, w["final_norm"]["scale"], d.eps)
    tgt = jnp.roll(tokens, -1, axis=1)
    cs = min(BLOCK, s)

    def chunk(args):
        zc, tc, wc = args
        lg = ein(q_, "bch,hv->bcv", zc, w["head"]["w"])
        gold = jnp.take_along_axis(lg, tc[..., None], -1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(lg, -1) - gold) * wc)

    split = lambda t: jnp.moveaxis(t.reshape(b, s // cs, cs, *t.shape[2:]),
                                   1, 0)
    ce = jnp.sum(jax.lax.map(jax.checkpoint(chunk),
                             (split(z), split(tgt), split(weight))))
    return ce / jnp.sum(weight) + d.aux_coef * aux


# ---- the work the metrics count ----

def flops_per_token(config, seq_len: int) -> float:
    """6 x the active matmul parameters plus causal attention,
    ``6 * layers * (s / 2) * heads * (d_qk + d_v)``.  Active: the MLA
    projections of every layer; the dense MLP of the dense layers; in each
    MoE layer the router, the shared experts and ``k * held / E`` of one
    expert's three matrices (the assignments routed to the experts held
    here); the output head over the vocabulary slice.  Not the embedding
    lookup."""
    d = dims_of(config)
    h, n = d.h, d.n_h
    attn = h * n * (d.d_nope + d.d_rope) + h * (d.d_c + d.d_rope) \
        + d.d_c * n * (d.d_nope + d.d_v) + n * d.d_v * h
    dense = 3 * h * d.ff
    moe = h * d.experts + 3 * h * d.expert_ff * (
        d.shared + d.top_k * d.held / d.experts)
    n_moe = d.layers - d.dense_layers
    active = d.layers * attn + d.dense_layers * dense + n_moe * moe \
        + h * d.vocab
    return 6.0 * active + 6.0 * d.layers * (seq_len / 2) * n \
        * (d.d_nope + d.d_rope + d.d_v)


def _flash_work(config, traffic):
    """The local microbatch ``b``, sequence ``s``, ``n_h`` heads of
    ``d_qk = d_nope + d_rope`` and ``d_v``, as causal work:
    ``2 * b * n_h * (s**2 / 2) * (d_qk + d_v)`` FLOPs, and q, k, v read
    and o written once in bf16."""
    d = dims_of(config)
    b = int(traffic["global_batch"]) // int(traffic["n_micro"])
    s = int(traffic["seq_len"])
    dq, dv = d.d_nope + d.d_rope, d.d_v
    flops = 2.0 * b * d.n_h * (s * s / 2) * (dq + dv)
    nbytes = 2.0 * b * s * d.n_h * (dq + dq + dv + dv)
    return flops, nbytes


def _gmm_work(config, traffic):
    """One of the layer's three calls (gate, up, down): the
    ``T * k * held / E`` assignments routed to the held experts, not the
    ``held * C`` capacity rows the static-capacity kernel computes:
    ``2 * rows * h * f`` FLOPs and, in bf16, the rows read and written
    once and every held expert's matrix read once."""
    d = dims_of(config)
    T = int(traffic["global_batch"]) // int(traffic["n_micro"]) \
        * int(traffic["seq_len"])
    rows, f = T * d.top_k * d.held / d.experts, d.expert_ff
    flops = 2.0 * rows * d.h * f
    nbytes = 2.0 * (rows * d.h + d.held * d.h * f + rows * f)
    return flops, nbytes


_WORK = {"_flash_attention_jit": _flash_work, "_gmm_jit": _gmm_work}


def call_work(kernel: str, config, traffic):
    """(FLOPs, bytes) of one call of ``kernel`` in this cell."""
    return _WORK[kernel](config, traffic)
