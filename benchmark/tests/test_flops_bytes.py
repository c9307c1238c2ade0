import json
import sys
from pathlib import Path

import pytest

from benchmark.lib import bytes as nbytes
from benchmark.lib import flops, peaks

ROOT = Path(__file__).resolve().parent.parent.parent


def test_vgg16_step_equals_the_programs_own_count():
    sys.path.insert(0, str(ROOT))
    import bench

    assert flops.vgg16_step(50, 15) == bench.analytic_vgg16_step_flops(50, 15)
    assert flops.vgg16_step(50, 0) > flops.vgg16_step(50, 15)


def test_vgg16_keras_index_matches_the_model():
    from idc_models_tpu.models.vgg import KERAS_LAYER_INDEX

    assert flops.vgg16_keras_index() == KERAS_LAYER_INDEX


def test_decode_window_bytes_by_hand():
    model = {"embed_dim": 4, "mlp_dim": 8, "num_blocks": 2, "vocab_size": 10,
             "param_dtype": "float32"}
    engine = {"cache_dtype": "bfloat16", "window": 3}
    # weights: 2 blocks x (4*16 + 2*32) + 4*10 = 296 parameters x 4 B
    assert flops.lm_matmul_params(model) == 296
    # a cached position: k and v, 2 blocks, width 4, 2 B each = 32 B
    assert nbytes.kv_bytes_per_token(model, engine) == 32
    # a step: 1184 B of weights + 100 positions read + 5 written
    assert nbytes.decode_window(model, engine, 100, 5) == 3 * (1184 + 3200 + 160)


def test_gpt2_large_counts():
    model = json.loads((ROOT / "benchmark/configs/gpt2-large.json").read_text())["model"]
    assert model["embed_dim"] == model["num_heads"] * model["head_dim"]
    assert model["mlp_dim"] == 4 * model["embed_dim"]
    assert flops.lm_matmul_params(model) == 36 * 12 * 1280 ** 2 + 1280 * 50257
    one = flops.lm_prefill_chunk(model, 128, 128)
    assert one == pytest.approx(2 * 128 * 36 * 12 * 1280 ** 2, rel=0.05)


def test_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
