"""Random weights made by the benchmark, from the seed, on the device.

The tree has the layout the serving program takes (``embed``,
``final_norm``, one scanned ``decoder`` segment of stacked layers, an
``lm_head`` when the embedding is untied), in bfloat16: the type a
published Qwen3 checkpoint is served in.  Norm gains are stored as
``gain - 1``, the convention of that tree; ``reference.py`` reads them
the same way.  ``check_layout`` holds the tree against the program's
own ``eval_shape`` of its initialiser, so a change of layout fails
loudly instead of serving the wrong weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16
NORM_SPREAD = 0.1      # gains are 1 + 0.1 * N(0, 1), so a dropped norm shows


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes(dims: dict) -> dict:
    """Leaf shapes of the served tree for a Qwen3 stack."""
    L, d, f, v = dims["layers"], dims["d_model"], dims["d_ff"], dims["vocab"]
    qw = dims["n_heads"] * dims["head_dim"]
    kw = dims["n_kv_heads"] * dims["head_dim"]
    hd = dims["head_dim"]
    layer = {
        "ln1": (L, d),
        "attn": {"wq": (L, d, qw), "wk": (L, d, kw), "wv": (L, d, kw),
                 "wo": (L, qw, d), "q_norm": (L, hd), "k_norm": (L, hd)},
        "ln2": (L, d),
        "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f), "w_out": (L, f, d)},
    }
    tree = {"embed": (v, d), "final_norm": (d,),
            "decoder": [{"slot0": layer}]}
    if not dims["tied"]:
        tree["lm_head"] = (d, v)
    return tree


def _leaf(key, path: str, shape) -> jax.Array:
    name = path.rsplit("/", 1)[-1]
    if name in ("ln1", "ln2", "final_norm", "q_norm", "k_norm"):
        return (NORM_SPREAD * jax.random.normal(key, shape)).astype(DTYPE)
    fan_in = shape[-2]
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(DTYPE)


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _paths(tree[k], f"{prefix}/{k}")
    elif isinstance(tree, list):
        for i, t in enumerate(tree):
            yield from _paths(t, f"{prefix}/{i}")
    else:
        yield prefix, tree


def _build(tree, key, prefix=""):
    if isinstance(tree, dict):
        return {k: _build(v, key, f"{prefix}/{k}") for k, v in tree.items()}
    if isinstance(tree, list):
        return [_build(t, key, f"{prefix}/{i}") for i, t in enumerate(tree)]
    salt = sum(ord(c) * 31 ** i for i, c in enumerate(prefix)) & 0x7FFFFFFF
    return _leaf(jax.random.fold_in(key, salt), prefix, tree)


def make(dims: dict, seed: int, shardings=None):
    """The whole tree in one jitted call, placed by ``shardings`` (a tree
    of shardings matching ``shapes(dims)``, or None for the default
    device)."""
    tree = shapes(dims)
    fn = jax.jit(lambda key: _build(tree, key), out_shardings=shardings)
    return fn(seed_key(seed))


def check_layout(params_shape, dims: dict) -> None:
    """Raise unless ``params_shape`` (the program's ``eval_shape`` of its
    own initialiser) has exactly the leaves and shapes made here."""
    ours = dict(_paths(shapes(dims)))
    theirs = {p: tuple(s.shape) for p, s in _paths(params_shape)}
    if ours != {p: tuple(s) for p, s in theirs.items()}:
        missing = sorted(set(theirs) ^ set(ours))
        differ = sorted(p for p in set(ours) & set(theirs)
                        if tuple(ours[p]) != theirs[p])
        raise ValueError(f"served weight tree changed: leaves only on one "
                         f"side {missing}, shapes differ at {differ}")
