"""Weights from the seed.

The weights are the benchmark's own: the layout of the configuration's
architecture description (``bench/archs``; the program's parameter tree,
which ``program.py`` checks against the program's own ``init``) filled
from ``--seed``.  The program and the reference are handed the same
values; the reference never sees what the program made.

A leaf of a layout is ``(shape, dtype, rule)``; rule: "one" 1 + 0.1·N,
"bias" 0.02·N, "in" N·fan_in^-½ over the second-to-last dim, "h" N·h^-½.
"""

from __future__ import annotations

from types import ModuleType
from typing import Any, Optional

import jax
import jax.numpy as jnp


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[2], str)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


def make(arch: ModuleType, d: Any, key: jax.Array,
         dtype: Optional[Any] = None):
    """The weights of ``arch.layout(d)`` from ``key``; traceable, so one
    jitted call makes them all on the device.  ``dtype`` overrides every
    leaf's type (the reference takes them in float32)."""
    tree = arch.layout(d)
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


def abstract(arch: ModuleType, d: Any):
    """``jax.ShapeDtypeStruct`` leaves of ``arch.layout(d)``."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x[0], x[1]),
                        arch.layout(d), is_leaf=_is_leaf)
