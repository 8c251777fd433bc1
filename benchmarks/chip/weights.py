"""Random weights from the seed, made on the device in one jitted call.

The tree's layout and dtypes are the program's (``jax.eval_shape`` of its
init, which never runs); the values come from the rules in the
configuration's ``init`` list, matched by the last component(s) of a leaf's
dotted path.  A leaf of two or more dimensions with no rule is drawn normal
with scale 1/sqrt(fan_in), fan_in being its next-to-last dimension; any other
leaf with no rule is an error.  Draws are float32, cast to the leaf's dtype
inside the same program, so no float32 copy of the weights is ever stored.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from a seed of any size (numpy's SeedSequence folds
    every bit of it), so seeds past 32 bits give distinct keys."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def leaf_name(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _rule_for(name: str, ndim: int, rules: Sequence[Tuple[str, Dict]]):
    for pattern, rule in rules:
        if name == pattern or name.endswith("." + pattern):
            return rule
    if ndim >= 2:
        return {"fan_in": True}
    raise ValueError(f"no init rule for leaf {name!r} of rank {ndim}")


def _draw(key, shape, dtype, rule) -> jax.Array:
    if "const" in rule:
        return jnp.full(shape, rule["const"], dtype)
    scale = rule["normal"] if "normal" in rule else 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def make_init(shapes, rules) -> Callable[[jax.Array], object]:
    """A jitted function key -> weight tree shaped like ``shapes``."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    plan: List = [(_rule_for(leaf_name(p), len(s.shape), rules), s.shape,
                   s.dtype) for p, s in leaves]

    @jax.jit
    def init(key):
        return jax.tree_util.tree_unflatten(tree, [
            _draw(jax.random.fold_in(key, i), shape, dtype, rule)
            for i, (rule, shape, dtype) in enumerate(plan)])

    return init
