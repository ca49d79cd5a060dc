import os
if "XLA_FLAGS" not in os.environ and os.environ.get("REPRO_FAKE_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_FAKE_DEVICES"])

"""Serving launcher: batched decode for ``--arch <id>``.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --smoke \
      --batch 4 --prompt-len 8 --new-tokens 32
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_spec
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.models.transformer import ModelOptions
from repro.serving import ServeConfig, serve_requests


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    enable_compile_cache()

    spec = get_spec(args.arch, smoke=args.smoke)
    model = build_model(spec, ModelOptions())
    params = model.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, spec.vocab)
    enc_out = None
    if spec.encoder is not None:
        frames = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, spec.encoder.n_ctx, spec.h), jnp.bfloat16) * 0.02
        enc_out = model._encode(params, frames)

    t0 = time.perf_counter()
    out = serve_requests(model, params, prompts,
                         ServeConfig(max_new_tokens=args.new_tokens,
                                     temperature=args.temperature),
                         cache_len=args.cache_len, enc_out=enc_out)
    dt = time.perf_counter() - t0
    total = args.batch * args.new_tokens
    print(f"arch={spec.name} generated {total} tokens in {dt:.1f}s "
          f"({total/dt:.1f} tok/s incl. prefill+compile)")
    for i in range(min(args.batch, 2)):
        print(f"  seq{i}: {out[i].tolist()}")


if __name__ == "__main__":
    main()
