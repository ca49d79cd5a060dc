"""Model dimensions from a configuration file, and weights from the seed.

The weights are the benchmark's own: a fixed layout (the program's
parameter tree, which ``program.py`` checks against the program's own
``init``) filled from ``--seed``.  The program and the reference are
handed the same values; the reference never sees what the program made.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp


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


def dims_of(config: Dict[str, Any]) -> Dims:
    """Read a ``bench/configs`` file (Hugging Face key names)."""
    c = config
    moe = "num_experts" in c
    return Dims(
        h=c["hidden_size"], n_h=c["num_attention_heads"],
        n_kv=c["num_key_value_heads"],
        d_head=c["hidden_size"] // c["num_attention_heads"],
        ff=0 if moe else c["intermediate_size"], vocab=c["vocab_size"],
        layers=c["num_hidden_layers"], tied=bool(c["tie_word_embeddings"]),
        qkv_bias=bool(c.get("qkv_bias", False)),
        rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        experts=c.get("num_experts", 0), top_k=c.get("num_experts_per_tok", 0),
        expert_ff=c["intermediate_size"] if moe else 0,
        capacity_factor=float(c["training"].get("capacity_factor", 1.25)),
        aux_coef=float(c.get("router_aux_loss_coef", 0.0)))


# Leaves are (shape, dtype, rule); rule: "one" 1 + 0.1·N, "bias" 0.02·N,
# "in" N·fan_in^-½ over the second-to-last dim, "h" N·h^-½.

def layout(d: Dims) -> Dict[str, Any]:
    """The parameter tree, as ``(shape, dtype, rule)`` leaves."""
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


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[2], str)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


def make(d: Dims, key: jax.Array, dtype: Optional[Any] = None):
    """The weights of ``layout(d)`` from ``key``; traceable, so one jitted
    call makes them all on the device.  ``dtype`` overrides every leaf's
    type (the reference takes them in float32)."""
    tree = layout(d)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_leaf)
    out = []
    for i, (shape, dt, rule) in enumerate(leaves):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if rule == "one":
            v = 1.0 + 0.1 * z
        elif rule == "bias":
            v = 0.02 * z
        elif rule == "h":
            v = z * d.h ** -0.5
        else:
            v = z * shape[-2] ** -0.5
        # every value is a bfloat16 one, the type the program's working
        # copy holds after a step (the float32 router included), so that
        # a float32 copy holds exactly the values the program is given
        v = v.astype(jnp.bfloat16).astype(dt)
        out.append(v if dtype is None else v.astype(dtype))
    return jax.tree.unflatten(treedef, out)


def abstract(d: Dims):
    """``jax.ShapeDtypeStruct`` leaves of ``layout(d)``."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x[0], x[1]),
                        layout(d), is_leaf=_is_leaf)
