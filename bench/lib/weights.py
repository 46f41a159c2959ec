"""Random weights from the seed, made by the benchmark and not the program.

A model family (``bench/models/<family>.py``) says which leaves it has
and in what layout the program takes them (its ``program_params``).
Every leaf is drawn from its own key, ``fold_in(fold_in(seed key, leaf
id), layer)``, so the whole stack (one jitted call on the device, for
the program) and one layer at a time (for the reference, after the
program's state is freed) give the same numbers.

:func:`check_layout` holds the program's own parameter shapes against
the family's, so a change of layout fails loudly instead of running
other weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_STD = 0.1


def seed_words(seed: int) -> jnp.ndarray:
    """A seed of any size as two uint32 words (jit argument: one compile
    serves every seed)."""
    return jnp.array([seed % 2**31, seed // 2**31], jnp.uint32)


def base_key(words):
    key = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(key, words[0]), words[1])


def draw(key, shape, kind, dtype):
    """A ``norm`` scale 1 + N(0, NORM_STD^2), or a ``matrix`` N(0,
    1/fan_in) with the fan-in first."""
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        return (1.0 + NORM_STD * z).astype(dtype)
    return (z * shape[0] ** -0.5).astype(dtype)


def nest(flat: dict) -> dict:
    """``{"a/b": x}`` as ``{"a": {"b": x}}``."""
    out: dict = {}
    for path, a in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = a
    return out


def make_on_device(seed: int, family, m, device) -> dict:
    """The family's ``program_params`` as one jitted call, placed on
    ``device``."""
    fn = jax.jit(family.program_params, static_argnums=1,
                 out_shardings=jax.sharding.SingleDeviceSharding(device))
    return jax.block_until_ready(fn(seed_words(seed), m))


def check_layout(want_specs, family, m) -> None:
    """Raise unless the program's parameter shapes are the family's."""
    got = jax.eval_shape(lambda w: family.program_params(w, m),
                         seed_words(0))
    a, b = (jax.tree_util.tree_structure(t) for t in (want_specs, got))
    if a != b:
        raise ValueError(f"the program's parameter tree {a} is not the "
                         f"benchmark's {b}")
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_specs),
                            jax.tree_util.tree_leaves(got), strict=True):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"{jax.tree_util.keystr(path)}: the program "
                             f"wants {w.shape} {w.dtype}, the benchmark "
                             f"makes {g.shape} {g.dtype}")
