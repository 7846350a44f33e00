"""Seeded random weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights: leaf ``i`` of the
parameter tree (in the order of its flattened paths) is drawn from
``fold_in(key(seed), i)`` with a scale set by the leaf's name, in the type the
model is served in. The reference draws the same leaves again by the same
rule once the program's state is gone, so it takes nothing the program made.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def key_of(seed: int) -> jax.Array:
    """A threefry key from any whole seed (64 bits and more)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def path_name(path) -> str:
    parts = []
    for p in path:
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
    return "/".join(parts)


def scale_of(name: str, shape: tuple, cfg) -> float:
    """The standard deviation of a leaf, by its name: norm scales (applied as
    1 + scale) small, the expert down projection by its input width, every
    other matrix by d_model; so activations and logits keep unit scale."""
    leaf = name.rsplit("/", 1)[-1]
    if "norm" in leaf:
        return 0.1
    if leaf == "w_down":
        return cfg.moe_d_ff ** -0.5 if cfg.moe_experts else cfg.d_ff ** -0.5
    return cfg.d_model ** -0.5


def layout(shapes) -> list[tuple[str, tuple, str]]:
    """(name, shape, dtype) of every leaf, in flattening order."""
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    return [(path_name(p), tuple(s.shape), str(s.dtype)) for p, s in flat]


def make(seed: int, leaves: list[tuple[str, tuple, str]], cfg,
         shardings=None) -> dict:
    """{name: array} for every leaf, all made in one jitted call."""
    names = [n for n, _, _ in leaves]

    def build(key):
        out = []
        for i, (name, shape, dtype) in enumerate(leaves):
            x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            out.append((x * scale_of(name, shape, cfg)).astype(dtype))
        return out

    fn = jax.jit(build, out_shardings=shardings)
    return dict(zip(names, fn(key_of(seed))))


def program_params(seed: int, cfg, init_params):
    """The parameter tree the program expects (its structure from
    ``jax.eval_shape`` of the program's own initialiser), filled from the
    seed."""
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    leaves = layout(shapes)
    flat = make(seed, leaves, cfg)
    treedef = jax.tree_util.tree_structure(shapes)
    return jax.tree_util.tree_unflatten(treedef, [flat[n] for n, _, _ in leaves]), leaves
