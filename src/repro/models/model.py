"""The public model API: build_model(spec) → Model.

Model bundles: parameter init (stacked layer pytrees), training forward +
loss (next-token CE + MoE aux), and single-token decode with the
family-appropriate cache (GQA KV / MLA latent / SSM state / enc-dec cross).
All functions are pure and pjit-compatible; sharding is expressed through
logical-axis annotations (repro.parallel.axes).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.notation import AttentionKind, FamilyKind, ModelSpec
from repro.parallel.axes import logical_constraint
from . import attention as A
from . import mla as M
from . import ssm as S
from .layers import (Params, embed_apply, embed_init, head_apply, head_init,
                     rmsnorm, rmsnorm_init)
from .transformer import ModelOptions, block_apply, stack_apply, stack_init

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Model:
    spec: ModelSpec
    opts: ModelOptions

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def init(self, rng: jax.Array, dtype=jnp.bfloat16) -> PyTree:
        spec = self.spec
        k_emb, k_dense, k_moe, k_head, k_enc = jax.random.split(rng, 5)
        n_moe = spec.n_moe_layers()
        n_dense = spec.n_layers - n_moe
        cross = spec.encoder is not None
        params: Dict[str, Any] = {
            "embed": embed_init(k_emb, spec.vocab, spec.h, dtype),
            "dense_layers": stack_init(k_dense, spec, n_dense, False, dtype,
                                       cross_attn=cross),
            "moe_layers": stack_init(k_moe, spec, n_moe, True, dtype),
            "final_norm": rmsnorm_init(spec.h, dtype),
        }
        if not spec.tie_embeddings:
            params["head"] = head_init(k_head, spec.h, spec.vocab, dtype)
        if spec.encoder is not None:
            ks = jax.random.split(k_enc, 2)
            params["encoder"] = {
                "layers": stack_init(ks[0], spec, spec.encoder.n_layers,
                                     False, dtype),
                "final_norm": rmsnorm_init(spec.h, dtype),
            }
        return params

    def abstract_params(self, dtype=jnp.bfloat16) -> PyTree:
        """Shape/dtype skeleton without allocation (dry-run path)."""
        return jax.eval_shape(lambda k: self.init(k, dtype),
                              jax.ShapeDtypeStruct((2,), jnp.uint32))

    # ------------------------------------------------------------------
    # training forward / loss
    # ------------------------------------------------------------------

    def _backbone(self, params: PyTree, x: jnp.ndarray,
                  positions: jnp.ndarray,
                  enc_out: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        spec = self.spec
        x = logical_constraint(x, ("batch", "seq", "embed"))
        window = spec.sliding_window
        x, aux1 = stack_apply(params["dense_layers"], spec, self.opts, x,
                              positions, False, enc_out=enc_out, window=window)
        x, aux2 = stack_apply(params["moe_layers"], spec, self.opts, x,
                              positions, True, window=window)
        return x, aux1 + aux2

    def forward(self, params: PyTree, batch: Dict[str, jnp.ndarray]
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """batch: tokens (b,s) int32 [+ vision_embeds | audio_embeds].
        Returns (logits (b,s,v) bf16, aux_loss)."""
        spec = self.spec
        tokens = batch["tokens"]
        b, s_len = tokens.shape
        x = embed_apply(params["embed"], tokens,
                        scale_by_dim=spec.name.startswith("gemma"), h=spec.h)

        if spec.family == FamilyKind.VLM and "vision_embeds" in batch:
            # stubbed ViT frontend: patch embeddings occupy the first
            # n_patch positions of the interleaved sequence (DESIGN.md §4)
            ve = batch["vision_embeds"].astype(x.dtype)
            n_p = ve.shape[1]
            x = x.at[:, :n_p, :].add(ve)

        positions = jnp.broadcast_to(jnp.arange(s_len)[None], (b, s_len))

        enc_out = None
        if spec.encoder is not None:
            enc_out = self._encode(params, batch["audio_embeds"])

        x, aux = self._backbone(params, x, positions, enc_out=enc_out)
        x = rmsnorm(params["final_norm"], x, spec.norm_eps,
                    gemma_style=spec.name.startswith("gemma"))
        if spec.tie_embeddings:
            logits = x @ params["embed"]["w"].T
        else:
            logits = x @ params["head"]["w"]
        logits = logical_constraint(logits, ("batch", "seq", "vocab"))
        return logits, aux

    def _encode(self, params: PyTree, audio_embeds: jnp.ndarray) -> jnp.ndarray:
        """Whisper encoder over stubbed mel/conv frame embeddings."""
        spec = self.spec
        b, s_len, _ = audio_embeds.shape
        pos = jnp.broadcast_to(jnp.arange(s_len)[None], (b, s_len))
        x = audio_embeds.astype(jnp.bfloat16)
        x, _ = stack_apply(params["encoder"]["layers"], spec, self.opts, x,
                           pos, False, causal=False)
        return rmsnorm(params["encoder"]["final_norm"], x, spec.norm_eps)

    def loss(self, params: PyTree, batch: Dict[str, jnp.ndarray]
             ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        logits, aux = self.forward(params, batch)
        tokens = batch["tokens"]
        targets = tokens[:, 1:]
        lg = logits[:, :-1].astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
        mask = batch.get("mask", jnp.ones_like(targets, jnp.float32))
        if mask.shape == tokens.shape:
            mask = mask[:, 1:]
        ce = jnp.sum((logz - gold) * mask) / jnp.maximum(mask.sum(), 1.0)
        total = ce + self.spec.aux_coef * aux
        return total, {"ce": ce, "aux": aux, "loss": total}

    # ------------------------------------------------------------------
    # decode (serving)
    # ------------------------------------------------------------------

    def init_cache(self, b: int, cache_len: int,
                   enc_out: Optional[jnp.ndarray] = None,
                   dtype=jnp.bfloat16) -> PyTree:
        spec = self.spec
        n_moe = spec.n_moe_layers()
        n_dense = spec.n_layers - n_moe
        window = spec.sliding_window
        eff = min(cache_len, window) if window else cache_len
        cache: Dict[str, Any] = {"index": jnp.zeros((), jnp.int32)}
        if spec.attention == AttentionKind.MLA:
            m = spec.mla
            cache["mla"] = {
                "c": jnp.zeros((spec.n_layers, b, eff, m.d_c), dtype),
                "r": jnp.zeros((spec.n_layers, b, eff, m.d_hr), dtype)}
        elif spec.attention != AttentionKind.NONE:
            cache["kv"] = {
                "k": jnp.zeros((spec.n_layers, b, eff, spec.n_kv,
                                spec.d_head), dtype),
                "v": jnp.zeros((spec.n_layers, b, eff, spec.n_kv,
                                spec.d_head), dtype)}
        if spec.ssm is not None:
            st = S.init_ssm_state(spec, spec.n_layers, b)
            cache["ssm"] = {"s": st.s, "x_prev": st.x_prev}
        if spec.encoder is not None:
            assert enc_out is not None, "enc-dec decode needs encoder output"
            cache["enc_out"] = enc_out
        return cache

    def decode_step(self, params: PyTree, cache: PyTree,
                    tokens: jnp.ndarray) -> Tuple[jnp.ndarray, PyTree]:
        """One token for every sequence: tokens (b, 1) → (logits (b,1,v), cache)."""
        spec, opts = self.spec, self.opts
        b = tokens.shape[0]
        idx = cache["index"]
        x = embed_apply(params["embed"], tokens,
                        scale_by_dim=spec.name.startswith("gemma"), h=spec.h)
        x = logical_constraint(x, ("batch", None, "embed"))
        enc_out = cache.get("enc_out")

        n_dense = spec.n_layers - spec.n_moe_layers()

        def layer_decode(x, layer_p, layer_cache, is_moe):
            aux = {}
            h = rmsnorm(layer_p["ln1"], x, spec.norm_eps,
                        gemma_style=spec.name.startswith("gemma"))
            mix = None
            new_cache = dict(layer_cache)
            if spec.attention == AttentionKind.MLA:
                mix, c, r = M.mla_decode(layer_p["attn"], spec, h,
                                         layer_cache["c"], layer_cache["r"], idx)
                new_cache.update(c=c, r=r)
            elif spec.attention != AttentionKind.NONE:
                mix, k, v = A.gqa_decode(layer_p["attn"], spec, h,
                                         layer_cache["k"], layer_cache["v"],
                                         idx, window=spec.sliding_window)
                new_cache.update(k=k, v=v)
            if spec.ssm is not None:
                so, s_new, xp = S.rwkv6_decode(layer_p["ssm"], spec, h,
                                               layer_cache["s"],
                                               layer_cache["x_prev"])
                new_cache.update(s=s_new, x_prev=xp)
                if spec.family == FamilyKind.HYBRID and mix is not None:
                    mn = rmsnorm(layer_p["merge_norm"], so, spec.norm_eps)
                    mix = 0.5 * (mix + mn)
                else:
                    mix = so
            x = x + mix
            if enc_out is not None:
                hx = rmsnorm(layer_p["ln_x"], x, spec.norm_eps)
                q = (hx @ layer_p["xattn"]["wq"]).reshape(b, 1, spec.n_h,
                                                          spec.d_head)
                ek = layer_cache["enc_k"]
                ev = layer_cache["enc_v"]
                sc = jnp.einsum("bqhd,bkhd->bhqk", q, ek).astype(jnp.float32)
                pr = jax.nn.softmax(sc * spec.d_head ** -0.5, -1).astype(x.dtype)
                ctx = jnp.einsum("bhqk,bkhd->bqhd", pr, ev)
                x = x + ctx.reshape(b, 1, spec.n_h * spec.d_head) \
                    @ layer_p["xattn"]["wo"]
            h2 = rmsnorm(layer_p["ln2"], x, spec.norm_eps,
                         gemma_style=spec.name.startswith("gemma"))
            if is_moe:
                from .moe import moe_forward
                out = moe_forward(layer_p["moe"], spec, h2,
                                  capacity_factor=opts.capacity_factor,
                                  router_impl=opts.router_impl)
                x = x + out.y
            elif spec.h_ff:
                from .layers import mlp_apply
                x = x + mlp_apply(layer_p["mlp"], spec, h2)
            return x, new_cache

        def scan_group(x, group_params, group_cache, is_moe):
            if not group_params:
                return x, group_cache

            def body(xc, inp):
                lp, lc = inp
                xc, nc = layer_decode(xc, lp, lc, is_moe)
                return xc, nc

            if opts.scan_layers:
                x, new_cache = jax.lax.scan(body, x,
                                            (group_params, group_cache))
            else:
                n = jax.tree.leaves(group_params)[0].shape[0]
                outs = []
                for i in range(n):
                    lp = jax.tree.map(lambda a: a[i], group_params)
                    lc = jax.tree.map(lambda a: a[i], group_cache)
                    x, nc = layer_decode(x, lp, lc, is_moe)
                    outs.append(nc)
                new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
            return x, new_cache

        # split stacked caches between the dense and moe layer groups
        def split_cache(tree, lo, hi):
            return jax.tree.map(lambda a: a[lo:hi], tree)

        per_layer_cache: Dict[str, Any] = {}
        if "mla" in cache:
            per_layer_cache.update(c=cache["mla"]["c"], r=cache["mla"]["r"])
        if "kv" in cache:
            per_layer_cache.update(k=cache["kv"]["k"], v=cache["kv"]["v"])
        if "ssm" in cache:
            per_layer_cache.update(s=cache["ssm"]["s"],
                                   x_prev=cache["ssm"]["x_prev"])
        if enc_out is not None:
            # precomputed cross K/V would normally live in the cache; compute
            # per step from enc_out to keep the cache small (enc ctx is short)
            dense_p = params["dense_layers"]
            ek = jnp.einsum("bsh,lhd->lbsd", enc_out, dense_p["xattn"]["wk"]) \
                .reshape(spec.n_layers, b, -1, spec.n_kv, spec.d_head)
            ev = jnp.einsum("bsh,lhd->lbsd", enc_out, dense_p["xattn"]["wv"]) \
                .reshape(spec.n_layers, b, -1, spec.n_kv, spec.d_head)
            ek = A._repeat_kv(ek.reshape(spec.n_layers * b, -1, spec.n_kv,
                                         spec.d_head),
                              spec.n_h // spec.n_kv).reshape(
                spec.n_layers, b, -1, spec.n_h, spec.d_head)
            ev = A._repeat_kv(ev.reshape(spec.n_layers * b, -1, spec.n_kv,
                                         spec.d_head),
                              spec.n_h // spec.n_kv).reshape(
                spec.n_layers, b, -1, spec.n_h, spec.d_head)
            per_layer_cache.update(enc_k=ek, enc_v=ev)

        dense_cache = split_cache(per_layer_cache, 0, n_dense)
        moe_cache = split_cache(per_layer_cache, n_dense, spec.n_layers)

        x, new_dense_cache = scan_group(x, params["dense_layers"],
                                        dense_cache, False)
        x, new_moe_cache = scan_group(x, params["moe_layers"], moe_cache, True)

        x = rmsnorm(params["final_norm"], x, spec.norm_eps,
                    gemma_style=spec.name.startswith("gemma"))
        if spec.tie_embeddings:
            logits = x @ params["embed"]["w"].T
        else:
            logits = x @ params["head"]["w"]
        logits = logical_constraint(logits, ("batch", None, "vocab"))

        # stitch caches back together
        def join(a, b_):
            if a is None:
                return b_
            if b_ is None:
                return a
            return jnp.concatenate([a, b_], axis=0)

        new_cache = dict(cache)
        new_cache["index"] = idx + 1

        def merged(field):
            d = new_dense_cache.get(field) if new_dense_cache else None
            m_ = new_moe_cache.get(field) if new_moe_cache else None
            return join(d, m_)

        if "mla" in cache:
            new_cache["mla"] = {"c": merged("c"), "r": merged("r")}
        if "kv" in cache:
            new_cache["kv"] = {"k": merged("k"), "v": merged("v")}
        if "ssm" in cache:
            new_cache["ssm"] = {"s": merged("s"), "x_prev": merged("x_prev")}
        return logits, new_cache


def build_model(spec: ModelSpec, opts: Optional[ModelOptions] = None) -> Model:
    return Model(spec=spec, opts=opts or ModelOptions())
