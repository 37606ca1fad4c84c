"""VGG19 (configuration E): its weights from the seed, its graph for
``repro.compile``, and its plain reference.

The reference is ``jax.lax`` convolutions, pools and matrix products in
float32 at the precision it is given; it imports nothing of the system
under test and takes only the weights this file makes.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def layers(cfg: dict) -> List[Tuple[str, str, tuple]]:
    """``(kind, name, weight shape)`` of every layer in order; pools
    are ``("pool", name, ())``."""
    h, w, c = cfg["input"]
    k = cfg["kernel"]
    out, n = [], 0
    for width, convs in cfg["blocks"]:
        for _ in range(convs):
            n += 1
            out.append(("conv", f"conv{n}", (k, k, c, width)))
            c = width
        out.append(("pool", f"pool{n}", ()))
        h, w = h // 2, w // 2
    fan = h * w * c
    for i, units in enumerate(cfg["dense"]):
        out.append(("dense", f"fc{i + 1}", (fan, units)))
        fan = units
    return out


def make_weights(cfg: dict, key) -> Dict[str, jax.Array]:
    """He-normal kernels and small normal biases, float32, made on the
    device in one call."""
    shapes = [(name, shape) for kind, name, shape in layers(cfg)
              if kind != "pool"]

    def build(key):
        keys = jax.random.split(key, 2 * len(shapes))
        out = {}
        for i, (name, shape) in enumerate(shapes):
            fan_in = int(np.prod(shape[:-1]))
            out[f"{name}/kernel"] = jax.random.normal(
                keys[2 * i], shape, jnp.float32) * np.sqrt(2.0 / fan_in)
            out[f"{name}/bias"] = 0.01 * jax.random.normal(
                keys[2 * i + 1], shape[-1:], jnp.float32)
        return out

    return jax.jit(build)(key)


def build_graph(cfg: dict, weights: Dict[str, np.ndarray]):
    """The network as ``repro.compile`` takes it: a ``repro.core.Graph``
    whose parameters are ``weights`` (host arrays)."""
    from repro.core import Graph
    g = Graph()
    x = g.add_input("input", tuple(cfg["input"]), "float32")
    act = cfg["activation"]
    dense = [name for kind, name, _ in layers(cfg) if kind == "dense"]
    for kind, name, _ in layers(cfg):
        if kind == "pool":
            x = g.add_node("maxpool2d", name, [x], attrs={
                "pool_size": (2, 2), "strides": (2, 2), "padding": "valid"})
            continue
        params = {p: g.add_param(f"{name}/{p}", weights[f"{name}/{p}"])
                  for p in ("kernel", "bias")}
        if kind == "conv":
            x = g.add_node("conv2d", name, [x], attrs={
                "strides": (1, 1), "padding": "same"}, params=params)
        else:
            if name == dense[0]:
                x = g.add_node("flatten", "flatten", [x])
            x = g.add_node("dense", name, [x], params=params)
        if name != dense[-1]:
            x = g.add_node("activation", f"{name}_{act}", [x],
                           attrs={"fn": act})
    x = g.add_node("softmax", "softmax", [x], attrs={"axis": -1})
    g.set_outputs([x])
    return g


def reference(cfg: dict, weights: Dict[str, jax.Array], images: jax.Array,
              precision: str = "highest") -> jax.Array:
    """Class probabilities of ``images`` (N, H, W, C): 3x3 'same'
    convolutions with ReLU, 2x2 max pools, dense layers with ReLU but
    the last, softmax; every product in float32 at ``precision``."""
    prec = {"highest": jax.lax.Precision.HIGHEST,
            "high": jax.lax.Precision.HIGH,
            "default": jax.lax.Precision.DEFAULT}[precision]
    dense = [name for kind, name, _ in layers(cfg) if kind == "dense"]
    h = images.astype(jnp.float32)
    for kind, name, _ in layers(cfg):
        if kind == "pool":
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            continue
        k, b = weights[f"{name}/kernel"], weights[f"{name}/bias"]
        if kind == "conv":
            h = jax.lax.conv_general_dilated(
                h, k, (1, 1), "SAME", precision=prec,
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
        else:
            h = jnp.dot(h.reshape(h.shape[0], -1), k, precision=prec) + b
        if name != dense[-1]:
            h = jnp.maximum(h, 0.0)
    return jax.nn.softmax(h, axis=-1)


def input_shape(cfg: dict) -> tuple:
    return tuple(cfg["input"])
