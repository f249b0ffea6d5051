"""Seeded weights for the program under test, made by the benchmark.

The benchmark, not the program, makes the weights, so the reference can
take them without taking anything the program made. The leaves follow the
program's parameter tree (read from ``jax.eval_shape`` of its ``lm.init``:
names, shapes and dtypes); each leaf is drawn by the rule that the
configuration's reference module declares for its name (the rules below,
or its own), and a leaf with no rule stops the run. One jitted call draws
every leaf on the device in the dtype it is served in.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import spec


def fan_in(axis: int):
    """Rule of a weight matrix: normal, scaled by the inverse square root
    of its size along ``axis`` (its fan-in)."""
    def draw(k, shape, dt, w):
        return jax.random.normal(k, shape, dt) * jnp.asarray(
            shape[axis] ** -0.5, dt)
    return draw


def bias(k, shape, dt, w):
    """Rule of a bias: normal with the configuration's ``bias_std``."""
    return (float(w["bias_std"]) * jax.random.normal(k, shape)).astype(dt)


def norm_scale(k, shape, dt, w):
    """Rule of a norm's gain: 1 plus normal with ``norm_scale_std``."""
    return (1.0 + float(w["norm_scale_std"])
            * jax.random.normal(k, shape)).astype(dt)


def identity(k, shape, dt, w):
    """Rule of the PCA projections until they are calibrated."""
    return jnp.broadcast_to(jnp.eye(shape[-1], dtype=dt), shape)


def _leaf_specs(shapes) -> Tuple[tuple, ...]:
    """(path, shape, dtype) of every leaf of the program's tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for path, leaf in flat:
        names = tuple(getattr(k, "key", getattr(k, "idx", None))
                      for k in path)
        out.append((names, tuple(leaf.shape), jnp.dtype(leaf.dtype).name))
    return tuple(out)


def make_params(cfg: dict, prog_cfg):
    """The program's parameter tree for configuration file ``cfg``, drawn
    from its weight seed on the default device, each leaf by the rule
    that the configuration's reference module gives its name
    (``LEAVES``: leaf name -> ``rule(key, shape, dtype, weights)``)."""
    from repro.models import lm
    shapes = jax.eval_shape(lambda k: lm.init(k, prog_cfg),
                            jax.random.PRNGKey(0))
    _, treedef = jax.tree_util.tree_flatten(shapes)
    specs = _leaf_specs(shapes)
    rules = spec.reference(cfg).LEAVES
    for names, _, _ in specs:
        if names[-1] not in rules:
            raise ValueError(f"no rule to draw parameter {names}")
    w = cfg["weights"]

    @jax.jit
    def draw(key):
        return [rules[names[-1]](jax.random.fold_in(key, i), shape,
                                 jnp.dtype(dtype), w)
                for i, (names, shape, dtype) in enumerate(specs)]
    leaves = draw(jax.random.PRNGKey(np.uint32(w["seed"] % 2 ** 32)))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def with_projections(params, proj):
    """``params`` with every layer's ``pca`` leaf set to ``proj``
    (L, Hkv, D, D); the other leaves are shared, not copied."""
    layers = dict(params["layers"])
    attn = dict(layers["attn"])
    attn["pca"] = jnp.asarray(proj, attn["pca"].dtype)
    layers["attn"] = attn
    return {**params, "layers": layers}
