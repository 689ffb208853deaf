"""Weights drawn from the seed, on the device, in one jitted call.

The harness, not the program, makes the weights: the program is handed
them, and the plain reference draws the same ones again from the same
seed. So a fault in how the program builds its own weights cannot hide
behind a reference that shares them.

Leaves are named as the program lays its parameters out (``embed``,
``segments/0/attn/q/w``, ...); the rule for each leaf depends only on its
name and shape:

- ``embed``: N(0, 0.02²), the usual token-embedding scale;
- a linear ``w`` of shape (..., fan_in, fan_out): N(0, 1/fan_in);
- a linear bias ``b``, a norm ``bias``: N(0, 0.02²);
- a norm ``scale``: 1 + N(0, 0.05²).

Biases and norm scales are not left at 0 and 1, so that a reference that
forgot one would disagree with the program.
"""
from __future__ import annotations

import functools
import zlib
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return tuple(out)


def _leaf(key, names: Sequence[str], shape, dtype):
    last = names[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if last == "embed":
        x = 0.02 * z
    elif last == "w":
        x = z / jnp.sqrt(jnp.float32(shape[-2]))
    elif last in ("b", "bias"):
        x = 0.02 * z
    elif last == "scale":
        x = 1.0 + 0.05 * z
    else:
        raise ValueError(f"no weight rule for leaf {'/'.join(names)}")
    return x.astype(dtype)


def leaf_key(key, names: Sequence[str]):
    """The key of one leaf: folded from its path, so a leaf's values do not
    depend on which other leaves the tree holds or on their order."""
    return jax.random.fold_in(key, zlib.crc32("/".join(names).encode()) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("specs", "dtype"))
def _draw(key, specs, dtype):
    return [_leaf(leaf_key(key, names), names, shape, dtype)
            for names, shape in specs]


def draw_tree(shapes: Any, seed: int, stream: int, dtype=jnp.float32) -> Any:
    """A tree shaped like ``shapes`` (a tree of ShapeDtypeStructs or
    arrays) with every leaf drawn by its rule from ``(seed, stream)`` and
    its path, made on the default device in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = tuple((_path_names(p), tuple(s.shape)) for p, s in flat)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), stream)
    return jax.tree_util.tree_unflatten(treedef, _draw(key, specs, dtype))
