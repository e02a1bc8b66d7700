"""The benchmark's weights: made on the device from ``--seed`` in one jitted
call, leaf by leaf from each configuration's ``leaf_specs`` (shape, stored
dtype and initial distribution), in the dtype the program trains them in.
The reference and the program start from these same arrays; neither the
program's own initialiser nor anything else it makes is used.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Spec = Tuple[tuple, str, tuple]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits
    included), through numpy's seed sequence."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _leaf(key, shape, dtype, init):
    kind = init[0]
    if kind == "normal":
        a = jax.random.normal(key, shape, jnp.float32) * init[1]
    elif kind == "uniform":
        a = jax.random.uniform(key, shape, jnp.float32, init[1], init[2])
    elif kind == "const":
        a = jnp.full(shape, init[1], jnp.float32)
    else:
        raise ValueError(f"unknown initialiser {init!r}")
    return a.astype(jnp.dtype(dtype))


def make_weights(specs: Dict[str, Spec], seed: int) -> Dict[str, jax.Array]:
    """path -> leaf, every leaf drawn from its own fold of the seed's key."""
    paths = sorted(specs)

    @jax.jit
    def init(key):
        return {p: _leaf(jax.random.fold_in(key, i), *specs[p])
                for i, p in enumerate(paths)}

    return init(seed_key(seed))


def leaf_path(path) -> str:
    """'layers/attn/wq' for a pytree key path of dict keys."""
    return "/".join(str(getattr(k, "key", k)) for k in path)


def check_layout(shapes, specs: Dict[str, Spec]):
    """The program's parameter pytree (``jax.eval_shape`` of its
    initialiser) against the configuration's leaf specs: the same paths,
    shapes and dtypes. Returns (treedef, paths in the program's order)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    got = {leaf_path(p): (tuple(s.shape), jnp.dtype(s.dtype).name)
           for p, s in flat}
    want = {p: (tuple(s), jnp.dtype(d).name) for p, (s, d, _) in specs.items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"program parameters differ from the configuration's "
                         f"leaf specs: {diff[:8]}")
    return treedef, [leaf_path(p) for p, _ in flat]
