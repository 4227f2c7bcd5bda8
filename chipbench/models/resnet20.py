"""Plain reference of ResNet-20 for CIFAR (He et al., arXiv:1512.03385
§4.2): a 3x3 stem, three stages of three basic blocks at 16/32/64
channels, global average pooling and a linear head.  GroupNorm with 8
groups stands in for BatchNorm, as in the system's model (stateless, so
clients' parameters average cleanly).

Written from the paper, apart from the system's code: straightforward
``jax.numpy`` in the dtype it is given.  The initialisation follows the
same recipe and key splits as the system's, so one seed gives both the
same weights: He-normal convolutions, unit GroupNorm scales, zero biases,
a normal head scaled by 64**-0.5.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WIDTHS = (16, 32, 64)
BLOCKS = 3
GROUPS = 8


def _he(key, kh, kw, cin, cout):
    std = (2.0 / (kh * kw * cin)) ** 0.5
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * std


def _gn_params(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def init(key, cfg):
    n_classes = cfg["model"]["n_classes"]
    # the stem, nine blocks, the head and one spare: the system splits 12
    ks = jax.random.split(key, 12)
    params = {"stem": _he(ks[0], 3, 3, 3, WIDTHS[0]), "gn0": _gn_params(WIDTHS[0])}
    cin, i = WIDTHS[0], 1
    for s, w in enumerate(WIDTHS):
        for b in range(BLOCKS):
            kb = jax.random.split(ks[i], 3)
            block = {
                "conv1": _he(kb[0], 3, 3, cin, w),
                "gn1": _gn_params(w),
                "conv2": _he(kb[1], 3, 3, w, w),
                "gn2": _gn_params(w),
            }
            if cin != w:
                block["proj"] = _he(kb[2], 1, 1, cin, w)
            params[f"s{s}b{b}"] = block
            cin, i = w, i + 1
    params["fc"] = {
        "w": jax.random.normal(ks[i], (WIDTHS[-1], n_classes), jnp.float32)
        * WIDTHS[-1] ** -0.5,
        "b": jnp.zeros((n_classes,), jnp.float32),
    }
    return params


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


def _group_norm(p, x, eps=1e-5):
    b, h, w, c = x.shape
    g = min(GROUPS, c)
    xg = x.reshape(b, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + eps)
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


def logits(params, images):
    x = jax.nn.relu(_group_norm(params["gn0"], _conv(images, params["stem"], 1)))
    for s in range(len(WIDTHS)):
        for b in range(BLOCKS):
            p = params[f"s{s}b{b}"]
            stride = 2 if s > 0 and b == 0 else 1
            h = jax.nn.relu(_group_norm(p["gn1"], _conv(x, p["conv1"], stride)))
            h = _group_norm(p["gn2"], _conv(h, p["conv2"], 1))
            shortcut = _conv(x, p["proj"], stride) if "proj" in p else x
            x = jax.nn.relu(h + shortcut)
    x = x.mean(axis=(1, 2))
    return x @ params["fc"]["w"] + params["fc"]["b"]


def loss(params, batch):
    """Mean cross-entropy of a batch ``{"images", "labels"}``, taken in the
    dtype of the parameters."""
    dtype = params["fc"]["w"].dtype
    lg = logits(params, batch["images"].astype(dtype))
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def conv_macs(cfg) -> list[tuple[str, int]]:
    """Multiply-accumulates of every convolution and of the head, for one
    32x32 image: output pixels x kernel area x input x output channels."""
    macs = [("stem", 32 * 32 * 9 * 3 * WIDTHS[0])]
    size, cin = 32, WIDTHS[0]
    for s, w in enumerate(WIDTHS):
        for b in range(BLOCKS):
            stride = 2 if s > 0 and b == 0 else 1
            size //= stride
            macs.append((f"s{s}b{b}.conv1", size * size * 9 * cin * w))
            macs.append((f"s{s}b{b}.conv2", size * size * 9 * w * w))
            if cin != w:
                macs.append((f"s{s}b{b}.proj", size * size * cin * w))
            cin = w
    macs.append(("fc", WIDTHS[-1] * cfg["model"]["n_classes"]))
    return macs


def flops_per_example(cfg) -> int:
    """Operations the forward and backward passes require for one image:
    2 per MAC forward, 2 for the weight gradient and 2 for the input
    gradient, except the stem, whose input (the image) needs no gradient.
    Normalisation and activations are left out (under 1% of the total)."""
    macs = conv_macs(cfg)
    return 6 * sum(m for _, m in macs) - 2 * macs[0][1]
