import os
if "XLA_FLAGS" not in os.environ and os.environ.get("REPRO_FAKE_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_FAKE_DEVICES"])

"""Training launcher: ``--arch <id>`` on the local device set (or a debug
mesh), with the paper's knobs exposed.

  PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b --smoke \
      --steps 50 --batch 8 --seq 256 --zero os+g --recompute full

On a real TPU pod this process runs once per host; jax.distributed picks up
the cluster topology and ``make_production_mesh`` lays the global mesh.
Here (CPU container) it drives the same code on small meshes; set
REPRO_FAKE_DEVICES=8 to exercise multi-device sharding paths.
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_spec
from repro.core.parallel_config import RecomputePolicy, ZeROStage
from repro.data.synthetic import config_for, make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.specs import batch_shardings
from repro.models import build_model
from repro.models.transformer import ModelOptions
from repro.optim.adamw import AdamWConfig, init_train_state
from repro.parallel.axes import axis_rules
from repro.parallel.compat import make_mesh
from repro.parallel.sharding import state_shardings
from repro.train.loop import TrainConfig, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--zero", default="os+g",
                    choices=[z.value for z in ZeROStage])
    ap.add_argument("--recompute", default="none",
                    choices=[r.value for r in RecomputePolicy])
    ap.add_argument("--attn", default="naive", choices=["naive", "chunked"])
    ap.add_argument("--data-axis", type=int, default=0,
                    help="data-axis size (0 = all devices)")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    spec = get_spec(args.arch, smoke=args.smoke)
    opts = ModelOptions(attn_impl=args.attn,
                        recompute=RecomputePolicy(args.recompute))
    model = build_model(spec, opts)

    n_dev = jax.device_count()
    data_ax = args.data_axis or (n_dev // args.model_axis)
    mesh = make_mesh((data_ax, args.model_axis), ("data", "model"))
    print(f"arch={spec.name} devices={n_dev} mesh=({data_ax},{args.model_axis}) "
          f"zero={args.zero} ac={args.recompute}")

    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.1f}M "
          f"(analytic {spec.total_params()/1e6:.1f}M)")
    state = init_train_state(params)
    abstract_state = jax.eval_shape(lambda: state)
    st_sh = state_shardings(abstract_state, mesh, ZeROStage(args.zero))
    step_fn = make_train_step(model, TrainConfig(
        n_micro=args.n_micro, adamw=AdamWConfig(lr=args.lr)))

    data_cfg = config_for(spec, args.batch, args.seq)
    b0 = make_batch(data_cfg, 0)
    b_sh = batch_shardings(jax.eval_shape(lambda: b0), mesh)

    with axis_rules(mesh):
        fn = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, None), donate_argnums=0)
        state = jax.device_put(state, st_sh)
        t0 = time.perf_counter()
        for i in range(args.steps):
            batch = jax.device_put(make_batch(data_cfg, i), b_sh)
            state, metrics = fn(state, batch)
            if i % args.log_every == 0 or i == args.steps - 1:
                print(f"step {i:>5}  loss {float(metrics['loss']):.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.2f}  "
                      f"{time.perf_counter()-t0:.0f}s")
    print("done")


if __name__ == "__main__":
    main()
