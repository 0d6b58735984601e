"""Plain reference for the ``resnet50`` configuration: the forward pass of
the reference's ResNet v2 symbol (arXiv:1603.05027; ``example/
image-classification/symbols/resnet.py``), in inference mode (batch-norm
by the moving statistics) or in training mode (by the batch's own), in
straightforward ``jax.numpy``/``lax`` and float32 under
``jax.default_matmul_precision("highest")``. Independent of ``mxnet_tpu``:
it takes the parameters and moving statistics as a dict of arrays by the
symbol's names and the images as NCHW, and returns the softmax
probabilities.

The structure is read from the names that are present (``_conv3`` marks a
bottleneck unit, ``_sc`` a projection shortcut, ``bn0`` the ImageNet stem),
so the same code serves the tiny rehearsal network.
"""
import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 2e-5       # the symbol's eps, every BatchNorm


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _bn(p, x, name, fix_gamma=False):
    """Inference mode: the moving statistics. Training mode
    (``p["__batch_stats__"]``): the batch's mean and biased variance."""
    if p.get("__batch_stats__"):
        mean = jnp.mean(x, axis=(0, 2, 3))
        var = jnp.mean(jnp.square(x - mean[None, :, None, None]),
                       axis=(0, 2, 3))
    else:
        mean = p[name + "_moving_mean"]
        var = p[name + "_moving_var"]
    gamma = jnp.ones_like(mean) if fix_gamma else p[name + "_gamma"]
    scale = gamma / jnp.sqrt(var + BN_EPS)
    shift = p[name + "_beta"] - mean * scale
    return x * scale[None, :, None, None] + shift[None, :, None, None]


def _unit(p, x, name, stride):
    """Pre-activation residual unit."""
    act1 = jax.nn.relu(_bn(p, x, name + "_bn1"))
    if name + "_conv3_weight" in p:         # bottleneck: 1x1, 3x3, 1x1
        y = _conv(act1, p[name + "_conv1_weight"], 1, 0)
        y = jax.nn.relu(_bn(p, y, name + "_bn2"))
        y = _conv(y, p[name + "_conv2_weight"], stride, 1)
        y = jax.nn.relu(_bn(p, y, name + "_bn3"))
        y = _conv(y, p[name + "_conv3_weight"], 1, 0)
    else:                                   # basic: 3x3, 3x3
        y = _conv(act1, p[name + "_conv1_weight"], stride, 1)
        y = jax.nn.relu(_bn(p, y, name + "_bn2"))
        y = _conv(y, p[name + "_conv2_weight"], 1, 1)
    if name + "_sc_weight" in p:
        shortcut = _conv(act1, p[name + "_sc_weight"], stride, 0)
    else:
        shortcut = x
    return y + shortcut


def forward(params, images, batch_stats=False):
    """``params``: name -> array (weights and moving statistics);
    ``images``: (n, 3, h, w). Returns (n, classes) probabilities.
    ``batch_stats``: normalise by the batch's statistics, as a training
    step's forward does."""
    with jax.default_matmul_precision("highest"):
        p = dict((k, jnp.asarray(v, jnp.float32)) for k, v in params.items())
        p["__batch_stats__"] = bool(batch_stats)
        x = jnp.asarray(images, jnp.float32)
        x = _bn(p, x, "bn_data", fix_gamma=True)
        if "bn0_gamma" in p:                # ImageNet stem
            x = _conv(x, p["conv0_weight"], 2, 3)
            x = jax.nn.relu(_bn(p, x, "bn0"))
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                                  (1, 1, 2, 2),
                                  ((0, 0), (0, 0), (1, 1), (1, 1)))
        else:                               # 32x32-and-under stem
            x = _conv(x, p["conv0_weight"], 1, 1)
        stage = 1
        while "stage%d_unit1_bn1_gamma" % stage in p:
            unit = 1
            while "stage%d_unit%d_bn1_gamma" % (stage, unit) in p:
                stride = 2 if unit == 1 and stage > 1 else 1
                x = _unit(p, x, "stage%d_unit%d" % (stage, unit), stride)
                unit += 1
            stage += 1
        x = jax.nn.relu(_bn(p, x, "bn1"))
        x = jnp.mean(x, axis=(2, 3))        # global average pool
        logits = x @ p["fc1_weight"].T + p["fc1_bias"]
        return jax.nn.softmax(logits, axis=-1)
