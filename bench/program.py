"""The system under test: the 3D executor's jitted train step for a cell.

Builds, from a configuration file and a traffic mix, the program's model
(``repro.models``, with the spec its architecture description gives),
mesh, ``make_pipeline_train_step`` step and the sharded ``TrainState``
layout it keeps resident.  Everything else the benchmark does (weights,
batches, timing, the reference) is its own.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from bench import arch
from bench import weights as W

AXES = ("pipe", "data", "model")


def _bf16(tree):
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16), tree)


@dataclasses.dataclass
class Program:
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    devices: Sequence[Any]

    def __post_init__(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.parallel_config import RecomputePolicy, ZeROStage
        from repro.models import build_model
        from repro.models.transformer import ModelOptions
        from repro.optim.adamw import AdamWConfig, init_train_state
        from repro.parallel.compat import make_mesh
        from repro.parallel.sharding import state_shardings
        from repro.train import pipeline_loop as PL
        from repro.train.loop import TrainConfig

        tr, par = self.config["training"], self.config["parallel"]
        self.arch = arch.of(self.config)
        self.dims = self.arch.dims_of(self.config)
        self.spec, fixed = self.arch.spec_of(self.config)
        self.model = build_model(self.spec, ModelOptions(
            backend=tr["backend"], attn_impl=tr["attn_impl"],
            recompute=RecomputePolicy(tr["recompute"]), **fixed))
        shape = tuple(par["mesh"])
        n = math.prod(shape)
        if len(self.devices) < n:
            raise SystemExit(f"the mesh {shape} needs {n} devices, "
                             f"found {len(self.devices)}")
        self.mesh = make_mesh(shape, AXES, devices=list(self.devices)[:n])
        self.zero = ZeROStage(par["zero"])
        self.ep = int(par["ep"])
        opt = {k: v for k, v in tr["optimizer"].items() if k != "name"}
        self.adamw = AdamWConfig(**opt)
        self.n_micro = int(self.traffic["n_micro"])
        self.step_fn = PL.make_pipeline_train_step(
            self.model, TrainConfig(n_micro=self.n_micro, adamw=self.adamw),
            self.mesh, schedule=par["schedule"], zero=self.zero,
            sp=bool(par["sp"]), ep=self.ep)
        # the description's layout must be the program's parameter tree
        mine = W.abstract(self.arch, self.dims)
        theirs = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        if jax.tree.structure(mine) != jax.tree.structure(theirs) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(mine), jax.tree.leaves(theirs))):
            raise ValueError(f"the layout of bench/archs/"
                             f"{self.config['arch']}.py is not the "
                             "program's parameter tree")
        # the working copy in bfloat16 in every leaf, as the program's step
        # leaves it (the router is float32 only before the first step), so
        # that every step runs one executable
        self.abstract_state = jax.eval_shape(
            lambda p: init_train_state(_bf16(p)), mine)
        rules = PL._EXEC_EP_RULES if self.ep > 1 else PL._EXEC_TP_RULES
        self.state_sharding = state_shardings(self.abstract_state, self.mesh,
                                              self.zero, rules=rules)
        self.batch_sharding = NamedSharding(self.mesh, P())
        self._init = init_train_state

    @property
    def tokens_per_step(self) -> int:
        return int(self.traffic["global_batch"]) * int(self.traffic["seq_len"])

    def make_state(self, key):
        """The TrainState from ``key``, made on the device in one call."""
        a, d, init = self.arch, self.dims, self._init
        return jax.jit(lambda k: init(_bf16(W.make(a, d, k))),
                       out_shardings=self.state_sharding)(key)

    def place(self, tokens):
        return jax.device_put({"tokens": jnp.asarray(tokens)},
                              self.batch_sharding)

    def estimate(self):
        """``estimate_memory`` for this configuration (per device)."""
        from repro.core.memory_model import estimate_memory
        from repro.core.parallel_config import ParallelConfig
        shape = self.config["parallel"]["mesh"]
        cfg = ParallelConfig(
            pp=shape[0], dp=shape[1], tp=shape[2], ep=self.ep,
            sp=bool(self.config["parallel"]["sp"]), zero=self.zero,
            micro_batch=int(self.traffic["global_batch"])
            // self.n_micro // shape[1],
            seq_len=int(self.traffic["seq_len"]), attn_impl="flash")
        return estimate_memory(self.spec, cfg, n_micro=self.n_micro)
