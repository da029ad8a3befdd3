"""The paper's CNN (Table 6: C(3,32)-R-M-C(32,32)-R-M-L(...)-R-L(10)) on a
synthetic image task, as a model kind of the benchmark.

The generators are copies of the program's own, kept beside the benchmark
so that the yardstick stays fixed when the program's versions change:
``image_task`` draws the Gaussian-prototype images of
``repro.data.synthetic.make_image_classification`` (the same
distribution) on the device in one jitted call, and ``init`` the weights
of ``repro.models.cnn.init_cnn``.  ``cnn_forward`` and ``xent`` are
the reference's plain forward pass and loss.  A sample's group is its
class label.
"""
from __future__ import annotations

import numpy as np

CLIENT_BLOCK = 32   # clients per reference local-SGD call: one compiled shape


# -- traffic ------------------------------------------------------------------

def groups(rng: np.random.Generator, model: dict, n: int) -> np.ndarray:
    """Each sample's class label, uniform over the classes."""
    return rng.integers(0, model["n_classes"], n).astype(np.int32)


def image_task(key, labels, *, n_classes, shape, margin, noise):
    """Images ``[n, *shape]`` f32 for the given labels: a Gaussian prototype
    per class (scale ``margin``) plus per-sample noise (scale ``noise``)."""
    import jax
    import jax.numpy as jnp

    d = int(np.prod(shape))

    @jax.jit
    def gen(key, labels):
        kp, kx = jax.random.split(key)
        protos = margin * jax.random.normal(kp, (n_classes, d))
        x = protos[labels] + noise * jax.random.normal(kx, (labels.shape[0], d))
        return x.reshape((labels.shape[0],) + tuple(shape))

    return gen(key, jnp.asarray(labels))


def arrays(key, groups, model: dict, data: dict) -> dict:
    """``images`` on the device and ``labels`` (the groups) on the host."""
    images = image_task(key, groups, n_classes=model["n_classes"],
                        shape=tuple(model["input_shape"]),
                        margin=data["margin"], noise=data["noise"])
    return dict(images=images, labels=groups)


# -- weights and the program's loss -------------------------------------------

def init(key, model):
    """The CNN's weights, made on the device in one jitted call, in the
    program's parameter layout (``conv<j>``/``fc<j>``/``head`` with ``w``
    and ``b``): He-style normal weights scaled by fan-in**-0.5, zero
    biases."""
    import jax
    import jax.numpy as jnp

    H, W, C = model["input_shape"]
    channels, hidden = model["channels"], model["hidden"]
    n_classes = model["n_classes"]

    @jax.jit
    def init(key):
        ks = jax.random.split(key, len(channels) + len(hidden) + 1)
        params, cin, i, h, w = {}, C, 0, H, W
        for j, cout in enumerate(channels):
            params[f"conv{j}"] = dict(
                w=jax.random.normal(ks[i], (3, 3, cin, cout))
                * (9 * cin) ** -0.5,
                b=jnp.zeros((cout,)))
            cin, h, w, i = cout, h // 2, w // 2, i + 1
        din = h * w * cin
        for j, dout in enumerate(hidden):
            params[f"fc{j}"] = dict(
                w=jax.random.normal(ks[i], (din, dout)) * din ** -0.5,
                b=jnp.zeros((dout,)))
            din, i = dout, i + 1
        params["head"] = dict(
            w=jax.random.normal(ks[i], (din, n_classes)) * din ** -0.5,
            b=jnp.zeros((n_classes,)))
        return params

    return init(key)


def split(params, model):
    """Every weight trains; there is no frozen base."""
    return params, {}


def loss_fn(model):
    from repro.models import cnn

    return cnn.make_image_loss_fn(cnn.cnn_apply)


# -- the reference ------------------------------------------------------------

def cnn_forward(params, x, precision):
    """C(3,c0)-R-M-...-L(hidden)-R-L(classes): 3x3 'SAME' convolutions with
    bias and ReLU, each followed by a 2x2 max-pool, then ReLU dense layers
    and a linear head.  x: [B, H, W, C] -> logits [B, classes]."""
    import jax
    import jax.numpy as jnp

    h = x
    j = 0
    while f"conv{j}" in params:
        p = params[f"conv{j}"]
        h = jax.lax.conv_general_dilated(
            h, p["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision) + p["b"]
        h = jax.nn.relu(h)
        h = jax.lax.reduce_window(h, -jnp.inf,
                                  jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1),
                                  "VALID")
        j += 1
    h = h.reshape(h.shape[0], -1)
    j = 0
    while f"fc{j}" in params:
        p = params[f"fc{j}"]
        h = jax.nn.relu(jnp.dot(h, p["w"], precision=precision) + p["b"])
        j += 1
    p = params["head"]
    return jnp.dot(h, p["w"], precision=precision) + p["b"]


def xent(logits, labels):
    import jax
    import jax.numpy as jnp

    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def reference_loss(model, params, frozen, batch, precision):
    return xent(cnn_forward(params, batch["images"], precision),
                batch["labels"])


# -- work counted from shapes -------------------------------------------------

def layer_macs(model: dict) -> list:
    """Multiply-accumulates of one sample's forward pass, per layer in
    order: 3x3 'SAME' convolutions (each followed by a 2x2 max-pool), then
    the dense layers and the head."""
    H, W, C = model["input_shape"]
    macs, cin, h, w = [], C, H, W
    for cout in model["channels"]:
        macs.append(h * w * cout * 9 * cin)
        cin, h, w = cout, h // 2, w // 2
    din = h * w * cin
    for dout in list(model["hidden"]) + [model["n_classes"]]:
        macs.append(din * dout)
        din = dout
    return macs


def train_flops_per_sample(model: dict) -> int:
    """FLOPs of one sample's forward and backward pass: 2 per MAC forward,
    2 for the weight gradient, and 2 for the input gradient of every layer
    but the first (whose input is data).  Bias, activation and pooling
    work is not counted.  19,275,264 for the paper's CNN at 32x32x3."""
    macs = layer_macs(model)
    return 6 * sum(macs) - 2 * macs[0]


def param_count(model: dict) -> int:
    """Trainable parameters N of the CNN (273,706 at 32x32x3)."""
    H, W, C = model["input_shape"]
    n, cin, h, w = 0, C, H, W
    for cout in model["channels"]:
        n += 9 * cin * cout + cout
        cin, h, w = cout, h // 2, w // 2
    din = h * w * cin
    for dout in list(model["hidden"]) + [model["n_classes"]]:
        n += din * dout + dout
        din = dout
    return n


def tiny(model: dict):
    """CPU test sizes.  The dense layer keeps thousands of weights so that
    a bf16 step, as at full size, loses most of its small updates."""
    model.update(input_shape=[8, 8, 3], channels=[4, 4], hidden=[4096])
