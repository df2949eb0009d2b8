"""InceptionV3 (Szegedy et al. 2015, "Rethinking the Inception Architecture"), in
the layer plan of keras.applications.inception_v3: stem, 3 blocks at 35x35, a
reduction, 4 blocks at 17x17, a reduction, 2 blocks at 8x8, global average pool
(2,048 features), dense to the classes. uint8 pixels in, scaled to [-1, 1]."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference.nn import Net, avg_pool_same, global_avg_pool, max_pool


def _block_a(net: Net, x, pool_features):
    with net.scope("InceptionBlockA"):
        b1 = net.conv_bn(x, 64, (1, 1))
        b5 = net.conv_bn(x, 48, (1, 1))
        b5 = net.conv_bn(b5, 64, (5, 5))
        b3 = net.conv_bn(x, 64, (1, 1))
        b3 = net.conv_bn(b3, 96, (3, 3))
        b3 = net.conv_bn(b3, 96, (3, 3))
        bp = net.conv_bn(avg_pool_same(x), pool_features, (1, 1))
        return jnp.concatenate([b1, b5, b3, bp], axis=-1)


def _reduction_a(net: Net, x):
    with net.scope("ReductionA"):
        b3 = net.conv_bn(x, 384, (3, 3), strides=(2, 2), padding="VALID")
        bd = net.conv_bn(x, 64, (1, 1))
        bd = net.conv_bn(bd, 96, (3, 3))
        bd = net.conv_bn(bd, 96, (3, 3), strides=(2, 2), padding="VALID")
        return jnp.concatenate([b3, bd, max_pool(x)], axis=-1)


def _block_b(net: Net, x, c7):
    with net.scope("InceptionBlockB"):
        b1 = net.conv_bn(x, 192, (1, 1))
        b7 = net.conv_bn(x, c7, (1, 1))
        b7 = net.conv_bn(b7, c7, (1, 7))
        b7 = net.conv_bn(b7, 192, (7, 1))
        bd = net.conv_bn(x, c7, (1, 1))
        bd = net.conv_bn(bd, c7, (7, 1))
        bd = net.conv_bn(bd, c7, (1, 7))
        bd = net.conv_bn(bd, c7, (7, 1))
        bd = net.conv_bn(bd, 192, (1, 7))
        bp = net.conv_bn(avg_pool_same(x), 192, (1, 1))
        return jnp.concatenate([b1, b7, bd, bp], axis=-1)


def _reduction_b(net: Net, x):
    with net.scope("ReductionB"):
        b3 = net.conv_bn(x, 192, (1, 1))
        b3 = net.conv_bn(b3, 320, (3, 3), strides=(2, 2), padding="VALID")
        b7 = net.conv_bn(x, 192, (1, 1))
        b7 = net.conv_bn(b7, 192, (1, 7))
        b7 = net.conv_bn(b7, 192, (7, 1))
        b7 = net.conv_bn(b7, 192, (3, 3), strides=(2, 2), padding="VALID")
        return jnp.concatenate([b3, b7, max_pool(x)], axis=-1)


def _block_c(net: Net, x):
    with net.scope("InceptionBlockC"):
        b1 = net.conv_bn(x, 320, (1, 1))
        b3 = net.conv_bn(x, 384, (1, 1))
        b3 = jnp.concatenate([net.conv_bn(b3, 384, (1, 3)),
                              net.conv_bn(b3, 384, (3, 1))], axis=-1)
        bd = net.conv_bn(x, 448, (1, 1))
        bd = net.conv_bn(bd, 384, (3, 3))
        bd = jnp.concatenate([net.conv_bn(bd, 384, (1, 3)),
                              net.conv_bn(bd, 384, (3, 1))], axis=-1)
        bp = net.conv_bn(avg_pool_same(x), 192, (1, 1))
        return jnp.concatenate([b1, b3, bd, bp], axis=-1)


def forward(net: Net, images, config: dict):
    """uint8 [N, 299, 299, 3] -> {"features": [N, 2048]} or, where the
    configuration's ``head`` is ``predictions``, softmax probabilities."""
    x = images.astype(jnp.float32) * (1.0 / 127.5) - 1.0
    x = net.conv_bn(x, 32, (3, 3), strides=(2, 2), padding="VALID")
    x = net.conv_bn(x, 32, (3, 3), padding="VALID")
    x = net.conv_bn(x, 64, (3, 3))
    x = max_pool(x)
    x = net.conv_bn(x, 80, (1, 1), padding="VALID")
    x = net.conv_bn(x, 192, (3, 3), padding="VALID")
    x = max_pool(x)
    for pool_features in (32, 64, 64):
        x = _block_a(net, x, pool_features)
    x = _reduction_a(net, x)
    for c7 in (128, 160, 160, 192):
        x = _block_b(net, x, c7)
    x = _reduction_b(net, x)
    x = _block_c(net, x)
    x = _block_c(net, x)
    feats = global_avg_pool(x)
    # the dense head's parameters exist in the program's tree whichever head is served
    logits = net.dense(feats, config["num_classes"], gain=config["assumed"]["dense_gain"])
    if config["head"] == "features":
        return {"features": feats}
    import jax
    return {"predictions": jax.nn.softmax(logits, axis=-1)}
