"""A configuration's architecture description, found by its ``arch`` key.

Every reader of the model's shape in the harness goes through the module
``bench/archs/<config["arch"]>.py``, loaded by path as a metric is.  A
description provides:

* ``dims_of(config)``: the dims it reads from the file, ``h`` and the
  ``vocab`` the batches draw ids from among them; it refuses a file with
  a key it does not read;
* ``layout(dims)``: the parameter tree as ``(shape, dtype, rule)`` leaves,
  which ``weights.make`` fills from the seed;
* ``spec_of(config)``: the program's ``ModelSpec`` and the
  ``ModelOptions`` fields the description fixes;
* ``micro_loss(w, tokens, weight, dims, precision, dp, ep)``: the plain
  reference's loss of one microbatch;
* ``flops_per_token(config, seq_len)``: the model FLOPs ``step_mfu``
  counts;
* ``call_work(kernel, config, traffic)``: FLOPs and bytes of one call of
  a kernel (``_flash_attention_jit``, ``_gmm_jit``), for its roofline.
"""

from __future__ import annotations

import importlib.util
import pathlib
import sys
from types import ModuleType
from typing import Any, Dict, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]


def path_of(name: str, root: Optional[pathlib.Path] = None) -> pathlib.Path:
    return (ROOT if root is None else root) / "bench" / "archs" / f"{name}.py"


def load(path: pathlib.Path) -> ModuleType:
    """The description module at ``path``, imported once per process (in
    ``sys.modules``, as a dataclass in it needs)."""
    path = pathlib.Path(path).resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no architecture description {path}")
    name = "bench_arch_" + path.stem.replace(".", "_").replace("-", "_")
    mod = sys.modules.get(name)
    if mod is not None and mod.__file__ == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def of(config: Dict[str, Any], root: Optional[pathlib.Path] = None
       ) -> ModuleType:
    """The description that ``config["arch"]`` names; a file without the
    key, or one that names no description, is an error."""
    if "arch" not in config:
        raise KeyError(f"the configuration {config.get('name')!r} names no "
                       "architecture description (its 'arch' key)")
    return load(path_of(config["arch"], root))
