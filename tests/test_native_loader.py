"""Native (C++/libpng) loader: build, decode correctness vs PIL, resize,
robustness, and integration with load_directory."""

import numpy as np
import pytest
from PIL import Image

from idc_models_tpu.data import native
from idc_models_tpu.data.idc import load_directory

pytestmark = pytest.mark.skipif(
    not native.available(),
    reason=f"native loader unavailable: {native.build_error()}")


def _write_pngs(root, n_per_class=4, size=50, seed=0, mode="RGB"):
    rng = np.random.default_rng(seed)
    for label in ("0", "1"):
        d = root / label
        d.mkdir(parents=True, exist_ok=True)
        for i in range(n_per_class):
            arr = rng.integers(0, 256, (size, size, 3), np.uint8)
            img = Image.fromarray(arr, "RGB").convert(mode)
            img.save(d / f"p{i}.png")


def test_decode_matches_pil_no_resize(tmp_path):
    _write_pngs(tmp_path, size=50)
    files = sorted(str(p) for p in tmp_path.glob("*/*.png"))
    got = native.decode_batch(files, 50)
    assert got.shape == (len(files), 50, 50, 3) and got.dtype == np.float32
    for i, f in enumerate(files):
        ref = np.asarray(Image.open(f).convert("RGB"), np.float32) / 255.0
        np.testing.assert_array_equal(got[i], ref)


def test_decode_grayscale_and_palette(tmp_path):
    _write_pngs(tmp_path, n_per_class=2, size=20, mode="L")
    files = sorted(str(p) for p in tmp_path.glob("*/*.png"))
    got = native.decode_batch(files, 20)
    for i, f in enumerate(files):
        ref = np.asarray(Image.open(f).convert("RGB"), np.float32) / 255.0
        np.testing.assert_allclose(got[i], ref, atol=1 / 255.0)


def test_resize_matches_python_backend(tmp_path):
    """Native resize implements the same naive-bilinear/half-pixel math as
    the Python fallback (both mirroring tf.image.resize defaults,
    dist_model_tf_vgg.py:42) — backends must be interchangeable."""
    from idc_models_tpu.data.idc import _decode_one

    _write_pngs(tmp_path, n_per_class=2, size=50)
    files = sorted(str(p) for p in tmp_path.glob("*/*.png"))
    got = native.decode_batch(files, 10)
    assert got.shape[1:] == (10, 10, 3)
    for i, f in enumerate(files):
        ref = _decode_one(f, 10)
        np.testing.assert_allclose(got[i], ref, atol=1e-5)


def test_bad_file_raises_naming_the_file(tmp_path):
    """Default is loud, like the PIL backend: backend='auto' must not
    silently train on zero images carrying real labels."""
    _write_pngs(tmp_path, n_per_class=1, size=10)
    bad = tmp_path / "0" / "bad.png"
    bad.write_bytes(b"not a png")
    files = sorted(str(p) for p in tmp_path.glob("*/*.png"))
    with pytest.raises(ValueError, match="bad.png"):
        native.decode_batch(files, 10)


def test_bad_file_zeroed_when_opted_in(tmp_path):
    _write_pngs(tmp_path, n_per_class=1, size=10)
    bad = tmp_path / "0" / "bad.png"
    bad.write_bytes(b"not a png")
    files = sorted(str(p) for p in tmp_path.glob("*/*.png"))
    with pytest.warns(UserWarning, match="failed to decode"):
        got = native.decode_batch(files, 10, on_error="zero")
    i_bad = files.index(str(bad))
    np.testing.assert_array_equal(got[i_bad], 0.0)
    assert got[(i_bad + 1) % len(files)].max() > 0


def test_all_bad_raises(tmp_path):
    bad = tmp_path / "b.png"
    bad.write_bytes(b"nope")
    with pytest.raises(ValueError):
        native.decode_batch([str(bad)], 10)
    # all-failed raises even in lenient mode
    with pytest.raises(ValueError, match="failed to decode"):
        native.decode_batch([str(bad)], 10, on_error="zero")
    with pytest.raises(ValueError, match="on_error"):
        native.decode_batch([str(bad)], 10, on_error="ignore")


def test_stale_abi_binary_triggers_rebuild(tmp_path, monkeypatch):
    """A wrong-ABI .so sitting under the right name must be rebuilt
    from source, not cached as a permanent failure."""
    import shutil
    import subprocess

    import idc_models_tpu.data.native as nat

    src = tmp_path / "loader.cpp"
    shutil.copy(nat._SRC, src)
    monkeypatch.setattr(nat, "_DIR", tmp_path)
    monkeypatch.setattr(nat, "_SRC", src)
    monkeypatch.setattr(nat, "_lib", None)
    monkeypatch.setattr(nat, "_build_error", None)
    stub = tmp_path / "stub.cpp"
    stub.write_text('extern "C" int idc_loader_abi_version() { return 0; }')
    subprocess.run(["g++", "-O3", "-shared", "-fPIC", str(stub),
                    "-o", str(nat._so_path())], check=True)
    assert nat.available(), nat.build_error()
    assert nat._lib.idc_loader_abi_version() == nat._ABI


def test_binary_name_follows_the_source(tmp_path, monkeypatch):
    """A binary built from another loader.cpp is never loaded: the name
    carries the source's digest (file times do not survive a copy)."""
    import idc_models_tpu.data.native as nat

    before = nat._so_path()
    src = tmp_path / "loader.cpp"
    src.write_bytes(nat._SRC.read_bytes() + b"\n// edited\n")
    monkeypatch.setattr(nat, "_SRC", src)
    assert nat._so_path() != before
    assert nat._so_path().parent == before.parent


def test_load_directory_native_equals_pil(tmp_path):
    _write_pngs(tmp_path, n_per_class=3, size=12)
    ds_nat = load_directory(tmp_path, image_size=12, seed=7,
                            backend="native")
    ds_pil = load_directory(tmp_path, image_size=12, seed=7, backend="pil")
    np.testing.assert_array_equal(ds_nat.labels, ds_pil.labels)
    np.testing.assert_array_equal(ds_nat.images, ds_pil.images)
