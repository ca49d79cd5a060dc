#!/usr/bin/env python3
"""Compile a cell's step, and the reference's gradient, for a described
TPU v5e, without the chip.

  JAX_PLATFORMS=cpu python3 bench/rehearse.py <cell> [<cell> ...]

Prints each program's ``memory_analysis()`` (arguments, outputs, aliased,
temporaries, in GiB) and how many Pallas kernels the step holds.  The
TPU's compiler refuses here what it would refuse on the chip: a program
that does not fit 16 GB, a kernel that cannot lower.  A compile count,
not a measurement.  Only one process at a time may load the TPU's
library.
"""

from __future__ import annotations

import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

GIB = 2 ** 30


def _footprint(compiled) -> str:
    from bench.run import hbm_bytes
    ma = compiled.memory_analysis()
    total = hbm_bytes(compiled)
    return (f"args {ma.argument_size_in_bytes / GIB:.3f} out "
            f"{ma.output_size_in_bytes / GIB:.3f} alias "
            f"{ma.alias_size_in_bytes / GIB:.3f} temp "
            f"{ma.temp_size_in_bytes / GIB:.3f} total {total / GIB:.3f} GiB")


def main(cells) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    import repro.kernels.ops as K
    from bench import arch
    from bench import weights as W
    from bench.cells import resolve
    from bench.program import Program

    jax.config.update("jax_enable_compilation_cache", False)
    # lower the kernels with Mosaic although this process runs on the CPU
    K.default_interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in cells:
        cell = resolve(name)
        prog = Program(cell.config, cell.traffic, topo.devices)
        t = time.perf_counter()
        state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            prog.abstract_state, prog.state_sharding)
        shape = (int(cell.traffic["global_batch"]),
                 int(cell.traffic["seq_len"]))
        batch = {"tokens": jax.ShapeDtypeStruct(
            shape, jnp.int32, sharding=prog.batch_sharding)}
        c = jax.jit(prog.step_fn, donate_argnums=0).lower(
            state, batch).compile()
        print(f"{name} step ({time.perf_counter() - t:.0f} s): "
              f"{_footprint(c)}; "
              f"{c.as_text().count('tpu_custom_call')} kernels", flush=True)
        est = prog.estimate()
        print(f"{name} estimate_memory {est.total / GIB:.3f} GiB", flush=True)
        # the reference's gradient of one microbatch, on one chip
        desc = arch.of(cell.config)
        d = desc.dims_of(cell.config)
        one = jax.sharding.SingleDeviceSharding(topo.devices[0])
        w = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.float32, sharding=one), W.abstract(desc, d))
        mb = shape[0] // int(cell.traffic["n_micro"])
        tok = jax.ShapeDtypeStruct((mb, shape[1]), jnp.int32, sharding=one)
        wt = jax.ShapeDtypeStruct((mb, shape[1]), jnp.float32, sharding=one)
        for precision in ("float32", "int8", "fp8"):
            with jax.default_matmul_precision("highest"):
                g = jax.jit(jax.value_and_grad(
                    lambda w_, t_, k_: desc.micro_loss(
                        w_, t_, k_, d, precision))).lower(w, tok, wt).compile()
            print(f"{name} reference {precision} gradient: {_footprint(g)}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
