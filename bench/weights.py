"""Weights drawn from the run's seed, made on the device by the benchmark.

Every leaf of the program's parameter tree (its names and shapes come from
``jax.eval_shape`` of the program's own ``init_params``) is filled here from
the seed alone, so the plain reference can draw the very same values again,
one layer at a time, without taking anything the program made:

* norm gains: ``1 + 0.1 * N(0, 1)`` (not all ones, so a gain that a path
  skips shows in the comparison);
* the token embedding: ``0.02 * N(0, 1)``;
* every other matrix: ``N(0, 1) / sqrt(fan_in)``, fan-in the second-to-last
  axis.

A configuration file's ``weights`` object covers the leaves these rules do
not, such as per-layer gains or bias vectors of another architecture, by
leaf name: ``"gain"`` draws ``1 + 0.1 * N(0, 1)``, a number ``s`` draws
``s * N(0, 1)``. A rule comes before the defaults; a leaf of fewer than two
axes that neither covers is an error that names its path.

Leaves under ``segments`` carry a leading layer axis; layer ``l`` of a leaf
is drawn from ``fold_in(leaf_key, l)``, so one layer can be drawn alone.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

NORM_LEAVES = ("ln", "final_norm", "q_norm", "k_norm")


def base_key(seed: int) -> jax.Array:
    """A key from a seed of up to 64 bits (``jax.random.key`` keeps 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def leaf_path(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def layered(path: str) -> bool:
    return path.startswith("segments/")


def draw(key: jax.Array, path: str, shape: Tuple[int, ...], dtype,
         rules: Optional[dict] = None) -> jax.Array:
    """One leaf (or one layer of a layered leaf), in the served dtype;
    ``rules`` are the configuration's ``weights``."""
    name = path.rsplit("/", 1)[-1]
    rule = (rules or {}).get(name)
    x = jax.random.normal(key, shape, jnp.float32)
    if rule == "gain" or (rule is None and name in NORM_LEAVES):
        x = 1.0 + 0.1 * x
    elif isinstance(rule, (int, float)) and not isinstance(rule, bool):
        x = rule * x
    elif rule is not None:
        raise ValueError(f"weight rule {rule!r} for {path}: "
                         f"'gain' or a scale")
    elif name == "embed":
        x = 0.02 * x
    elif len(shape) >= 2:
        x = x / jnp.sqrt(jnp.float32(shape[-2]))
    else:
        raise ValueError(f"no weight rule covers {path} of shape {shape}: "
                         f"give it one under the configuration's 'weights'")
    return x.astype(dtype)


def leaf_key(seed_key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(seed_key, zlib.crc32(path.encode()))


def draw_layer(seed_key: jax.Array, path: str, layer: int,
               shape: Tuple[int, ...], dtype,
               rules: Optional[dict] = None) -> jax.Array:
    """Layer ``layer`` of a layered leaf whose per-layer shape is ``shape``."""
    return draw(jax.random.fold_in(leaf_key(seed_key, path), layer), path,
                shape, dtype, rules)


def leaf_specs(shapes) -> List[Tuple[str, Tuple[int, ...], Any]]:
    """(path, shape, dtype) of every leaf of a shape tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return [(leaf_path(p), tuple(a.shape), a.dtype) for p, a in flat]


def make_params(shapes, seed: int,
                rules: Optional[dict] = None) -> Dict[str, Any]:
    """The whole tree on the default device, in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(leaf_path(p), tuple(a.shape), a.dtype) for p, a in flat]

    def build(k):
        out = []
        for path, shape, dtype in specs:
            if layered(path):
                lk = leaf_key(k, path)
                out.append(jax.lax.map(
                    lambda l, lk=lk, path=path, shape=shape, dtype=dtype:
                    draw(jax.random.fold_in(lk, l), path, shape[1:], dtype,
                         rules),
                    jnp.arange(shape[0])))
            else:
                out.append(draw(leaf_key(k, path), path, shape, dtype, rules))
        return out

    leaves = jax.jit(build)(base_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)
