"""What is left of the pre-round benchmark script: one hand count.
`benchmark/tests/test_flops_bytes.py` imports `analytic_vgg16_step_flops` from
this module to hold `benchmark/lib/flops.py` to the program's own count."""


def analytic_vgg16_step_flops(image_size: int = 50,
                              fine_tune_at: int = 15) -> float:
    """Per-patch FLOPs of the fine-tune train step: full forward + the
    live backward (only layers with Keras index >= fine_tune_at get
    gradients; XLA dead-code-eliminates the rest — the explicit analogue
    of the reference's frozen layers, dist_model_tf_vgg.py:146)."""
    from idc_models_tpu.models.vgg import _CFG, KERAS_LAYER_INDEX

    s, c_in = image_size, 3
    fwd: dict[str, float] = {}
    for block, filters, n_convs in _CFG:
        for conv in range(1, n_convs + 1):
            fwd[f"block{block}_conv{conv}"] = 2.0 * 9 * c_in * filters * s * s
            c_in = filters
        s //= 2
    head = 2.0 * 512 * 1
    live = [n for n, i in KERAS_LAYER_INDEX.items() if i >= fine_tune_at]
    # backward: dX + dW per live conv layer, each ~= its forward cost
    bwd = 2.0 * sum(fwd[n] for n in live) + 2.0 * head
    return sum(fwd.values()) + head + bwd
