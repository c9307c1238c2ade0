"""Plain reference for the `vgg16-idc` configuration: Keras
`VGG16(include_top=False)` on 50x50x3 patches, global average pooling,
Dense(1) logits, binary cross-entropy from logits
(dist_model_tf_vgg.py:119-131). Float32 throughout at
`default_matmul_precision("highest")`, written from the published
architecture and independent of `idc_models_tpu/models`; it only reads
the parameter tree's names (`backbone/block<b>_conv<c>/{kernel,bias}`,
`head/{kernel,bias}`)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BLOCKS = ((1, 2), (2, 2), (3, 3), (4, 3), (5, 3))   # (block, convolutions)


def forward(params, images):
    """images [B, H, W, 3] float32 in [0, 1] -> logits [B, 1]."""
    with jax.default_matmul_precision("highest"):
        h = images.astype(jnp.float32)
        for block, n_convs in BLOCKS:
            for conv in range(1, n_convs + 1):
                p = params["backbone"][f"block{block}_conv{conv}"]
                h = lax.conv_general_dilated(
                    h, p["kernel"].astype(jnp.float32), (1, 1), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
                h = jnp.maximum(h + p["bias"], 0.0)
            h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        pooled = h.mean(axis=(1, 2))
        return pooled @ params["head"]["kernel"] + params["head"]["bias"]


def loss(params, images, labels):
    """Mean binary cross-entropy from logits, in its stable form."""
    z = forward(params, images).reshape(-1)
    y = labels.reshape(-1).astype(jnp.float32)
    return jnp.mean(jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def loss_and_head_grad(params, images, labels):
    """The loss and its gradient with respect to the head alone."""
    def of_head(head):
        return loss({"backbone": params["backbone"], "head": head},
                    images, labels)

    return jax.value_and_grad(of_head)(params["head"])
