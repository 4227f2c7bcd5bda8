"""Plain reference of the system's spec-sized MLP (``ScenarioSpec`` with
``model="mlp"``): one ReLU layer of ``width`` units over ``dim`` flat
features, a linear head over ``n_classes``, mean cross-entropy.

Written apart from the system's code: straightforward ``jax.numpy`` in the
dtype it is given.  The initialisation follows the system's recipe and key
split, so one seed gives both the same weights.  A model of matmuls alone,
whose check replays on the chip: the tests and chip runs of that path use
it (``tiny.add_cell`` copies it beside the benchmark's models).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, cfg):
    m = cfg["model"]
    dim, width, n_classes = m["dim"], m["width"], m["n_classes"]
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (dim, width), jnp.float32) * dim**-0.5,
        "b1": jnp.zeros((width,), jnp.float32),
        "w2": jax.random.normal(k2, (width, n_classes), jnp.float32) * width**-0.5,
        "b2": jnp.zeros((n_classes,), jnp.float32),
    }


def loss(params, batch):
    """Mean cross-entropy of a batch ``{"inputs", "labels"}``, taken in the
    dtype of the parameters."""
    x = batch["inputs"].astype(params["w1"].dtype)
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    lg = h @ params["w2"] + params["b2"]
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def flops_per_example(cfg) -> int:
    """Forward and backward operations for one example: 2 per MAC forward,
    2 for the weight gradient, 2 for the input gradient, except the first
    layer's, whose input needs none."""
    m = cfg["model"]
    first, head = m["dim"] * m["width"], m["width"] * m["n_classes"]
    return 6 * (first + head) - 2 * first
