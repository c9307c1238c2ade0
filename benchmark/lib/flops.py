"""Operations the algorithm needs, from shapes alone. Recomputation the
compiler may add is never counted: these are the numerators of every
utilisation and roofline share the benchmark reports."""

from __future__ import annotations

# (block, filters, convolutions) of VGG16 without its top.
VGG16_BLOCKS = ((1, 64, 2), (2, 128, 2), (3, 256, 3), (4, 512, 3), (5, 512, 3))


def vgg16_keras_index() -> dict[str, int]:
    """Keras layer index of every convolution of VGG16(include_top=False):
    index 0 is the input layer, and each block's pooling layer takes one."""
    out, i = {}, 1
    for block, _, n_convs in VGG16_BLOCKS:
        for conv in range(1, n_convs + 1):
            out[f"block{block}_conv{conv}"] = i
            i += 1
        i += 1
    return out


def vgg16_step(image_size: int = 50, fine_tune_at: int = 15) -> float:
    """FLOPs per patch of one fine-tune step: the whole forward pass plus
    the backward pass of the live layers only (Keras index >=
    `fine_tune_at`, and the head). A live convolution's backward is two
    convolutions of its forward's cost (dX and dW)."""
    s, c_in = image_size, 3
    fwd = {}
    for block, filters, n_convs in VGG16_BLOCKS:
        for conv in range(1, n_convs + 1):
            fwd[f"block{block}_conv{conv}"] = 2.0 * 9 * c_in * filters * s * s
            c_in = filters
        s //= 2
    head = 2.0 * 512 * 1
    live = [n for n, i in vgg16_keras_index().items() if i >= fine_tune_at]
    bwd = 2.0 * sum(fwd[n] for n in live) + 2.0 * head
    return sum(fwd.values()) + head + bwd


def lm_matmul_params(model: dict) -> int:
    """Parameters of an attention LM that a token multiplies against:
    q/k/v/o and the two MLP matrices of every block, and the output head."""
    d, mlp = model["embed_dim"], model["mlp_dim"]
    return model["num_blocks"] * (4 * d * d + 2 * d * mlp) + d * model["vocab_size"]


def lm_prefill_chunk(model: dict, chunk: int, context: int) -> float:
    """FLOPs of one prefill chunk of `chunk` tokens whose last token sees
    `context` cached positions: the matmuls of every token (the head only
    for the last one) plus causal attention against the context."""
    d = model["embed_dim"]
    body = model["num_blocks"] * (4 * d * d + 2 * d * model["mlp_dim"])
    matmul = 2.0 * chunk * body + 2.0 * d * model["vocab_size"]
    mean_ctx = max(context - (chunk - 1) / 2.0, 1.0)
    attn = 4.0 * model["num_blocks"] * d * chunk * mean_ctx
    return matmul + attn
