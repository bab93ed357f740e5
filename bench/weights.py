"""Random weights from the seed, made on the device in one jitted call.

The leaves follow the program's parameter layout (``model_zoo.
param_table``: the names and shapes it serves from); their values are the
benchmark's own, so the reference can use them without taking anything
the program made.  Every matrix is normal with variance 1/fan-in over its
contraction axes, which the family module's ``FAN_IN`` names by index;
the embedding is unit normal, norm scales are
``1 + 0.1 N(0, 1)`` and every bias, norm bias included, is ``0.1 N(0,
1)``, so the bias and norm paths carry weight in the comparison.
"""

from __future__ import annotations

import math
import zlib
from typing import Dict, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def jax_seed(seed: int) -> int:
    """A 31-bit key seed drawn from any whole-number ``--seed``."""
    return int(np.random.default_rng(seed).integers(0, 2 ** 31 - 1))


def _leaf_rule(path: str, shape: Tuple[int, ...],
               fan_in: Mapping[str, Sequence[int]]) -> Tuple[str, float]:
    name = path[len("layers/"):] if path.startswith("layers/") else path
    dims = shape[1:] if path.startswith("layers/") else shape
    if name == "embed":
        return "normal", 1.0
    if name in fan_in:
        return "normal", 1.0 / math.sqrt(math.prod(dims[a]
                                                   for a in fan_in[name]))
    if name.endswith("/scale"):
        return "scale", 0.1
    if name.endswith("/bias") or name.split("/")[-1] in ("bq", "bk", "bv"):
        return "normal", 0.1
    raise KeyError(f"no initialisation rule for parameter {path!r}")


def make_params(shapes: Dict[str, Tuple[int, ...]], seed: int,
                fan_in: Mapping[str, Sequence[int]],
                dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """``{path: array}`` of ``dtype`` on the default device, one jitted
    call; ``fan_in`` is the family's ``FAN_IN``.  Each leaf's stream is
    keyed by its name, so the values of a leaf do not depend on which
    other leaves exist."""
    rules = {p: _leaf_rule(p, s, fan_in) for p, s in shapes.items()}

    def build(key):
        out = {}
        for p, shape in sorted(shapes.items()):
            kind, scale = rules[p]
            k = jax.random.fold_in(key, zlib.crc32(p.encode()) & 0x7FFFFFFF)
            z = jax.random.normal(k, shape, dtype)
            out[p] = (1 + z * scale if kind == "scale" else z * scale
                      ).astype(dtype)
        return out

    return jax.jit(build)(jax.random.PRNGKey(jax_seed(seed)))
