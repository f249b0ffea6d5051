"""Seeded weights for the program under test, made by the benchmark.

The benchmark, not the program, makes the weights, so the reference can
take them without taking anything the program made. The leaves follow the
program's parameter tree (read from ``jax.eval_shape`` of its ``lm.init``:
names, shapes and dtypes); each leaf is drawn by what its name says it is,
and a leaf whose name is unknown here stops the run. One jitted call draws
every leaf on the device in the dtype it is served in.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

MATRICES = ("wq", "wk", "wv", "wo", "w_in", "w_out")
BIASES = ("bq", "bk", "bv")


def _leaf_specs(shapes) -> Tuple[tuple, ...]:
    """(path, shape, dtype) of every leaf of the program's tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for path, leaf in flat:
        names = tuple(getattr(k, "key", getattr(k, "idx", None))
                      for k in path)
        out.append((names, tuple(leaf.shape), jnp.dtype(leaf.dtype).name))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, specs, bias_std: float, scale_std: float):
    leaves = []
    for i, (names, shape, dtype) in enumerate(specs):
        k = jax.random.fold_in(key, i)
        name = names[-1]
        dt = jnp.dtype(dtype)
        if name == "table":                       # (vocab, d_model)
            w = jax.random.normal(k, shape, dt) * jnp.asarray(
                shape[-1] ** -0.5, dt)
        elif name in MATRICES:                    # (L, fan_in, fan_out)
            w = jax.random.normal(k, shape, dt) * jnp.asarray(
                shape[-2] ** -0.5, dt)
        elif name in BIASES:
            w = (bias_std * jax.random.normal(k, shape)).astype(dt)
        elif name == "scale":                     # RMSNorm gains
            w = (1.0 + scale_std * jax.random.normal(k, shape)).astype(dt)
        elif name == "pca":                       # identity until calibrated
            w = jnp.broadcast_to(jnp.eye(shape[-1], dtype=dt), shape)
        else:
            raise ValueError(f"no rule to draw parameter {names}")
        leaves.append(w)
    return leaves


def make_params(cfg: dict, prog_cfg):
    """The program's parameter tree for configuration file ``cfg``, drawn
    from its weight seed on the default device."""
    from repro.models import lm
    shapes = jax.eval_shape(lambda k: lm.init(k, prog_cfg),
                            jax.random.PRNGKey(0))
    _, treedef = jax.tree_util.tree_flatten(shapes)
    w = cfg["weights"]
    leaves = _draw(jax.random.PRNGKey(np.uint32(w["seed"] % 2 ** 32)),
                   _leaf_specs(shapes), float(w["bias_std"]),
                   float(w["norm_scale_std"]))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def with_projections(params, proj):
    """``params`` with every layer's ``pca`` leaf set to ``proj``
    (L, Hkv, D, D); the other leaves are shared, not copied."""
    layers = dict(params["layers"])
    attn = dict(layers["attn"])
    attn["pca"] = jnp.asarray(proj, attn["pca"].dtype)
    layers["attn"] = attn
    return {**params, "layers": layers}
