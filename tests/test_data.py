"""Data layer tests: loader determinism, splits, sharding, prefetch."""

import numpy as np
import pytest

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.data import (
    ArrayDataset, Loader, cifar10, partition, pipeline, synthetic,
)
from idc_models_tpu.data.idc import load_directory, train_val_test_split


@pytest.fixture(scope="module")
def png_tree(tmp_path_factory):
    """A tiny <root>/<label>/*.png tree with recoverable labels."""
    from PIL import Image

    root = tmp_path_factory.mktemp("idc")
    rng = np.random.default_rng(0)
    for label in (0, 1):
        d = root / str(label)
        d.mkdir()
        for i in range(12):
            arr = (rng.random((50, 50, 3)) * 100 + label * 120).astype(np.uint8)
            Image.fromarray(arr).save(d / f"p{i}.png")
    return root


def test_load_directory_labels_and_range(png_tree):
    ds = load_directory(png_tree, image_size=50, seed=3)
    assert len(ds) == 24
    assert ds.images.dtype == np.float32
    assert 0.0 <= ds.images.min() and ds.images.max() <= 1.0
    assert set(np.unique(ds.labels)) == {0, 1}
    # label is recoverable from brightness (class 1 is brighter)
    bright = ds.images.mean(axis=(1, 2, 3))
    assert bright[ds.labels == 1].mean() > bright[ds.labels == 0].mean()


def test_load_directory_deterministic(png_tree):
    a = load_directory(png_tree, seed=7)
    b = load_directory(png_tree, seed=7)
    np.testing.assert_array_equal(a.labels, b.labels)
    np.testing.assert_array_equal(a.images, b.images)
    c = load_directory(png_tree, seed=8)
    assert not np.array_equal(a.labels, c.labels) or not np.array_equal(
        a.images, c.images)


def test_load_directory_resize(png_tree):
    ds = load_directory(png_tree, image_size=10)
    assert ds.images.shape[1:] == (10, 10, 3)


def test_split_is_materialized_and_disjoint():
    imgs, labels = synthetic.make_idc_like(100, size=8, seed=0)
    # tag each image with a unique corner value to detect overlap
    imgs[:, 0, 0, 0] = np.arange(100) / 100.0
    ds = ArrayDataset(imgs, labels)
    tr, va, te = train_val_test_split(ds, (0.8, 0.1, 0.1), seed=5)
    assert (len(tr), len(va), len(te)) == (80, 10, 10)
    ids = np.concatenate([d.images[:, 0, 0, 0] for d in (tr, va, te)])
    assert len(np.unique(ids)) == 100  # disjoint, covers everything


def test_loader_epochs_and_drop_remainder():
    imgs, labels = synthetic.make_idc_like(70, size=8, seed=0)
    ld = Loader(ArrayDataset(imgs, labels), 32, seed=1)
    assert len(ld) == 2
    b0 = list(ld.epoch(0))
    b1 = list(ld.epoch(1))
    assert all(x.shape[0] == 32 for x, _ in b0)
    # different epoch -> different order
    assert not np.array_equal(b0[0][0], b1[0][0])
    # same epoch replayed -> identical
    b0r = list(ld.epoch(0))
    np.testing.assert_array_equal(b0[0][0], b0r[0][0])


def test_loader_repeat_two_passes():
    # the dense preset's repeat(2) (dist_model_tf_dense.py:122-123): each
    # epoch covers the set twice, each pass freshly shuffled
    imgs, labels = synthetic.make_idc_like(64, size=8, seed=0)
    labels = np.arange(64, dtype=np.int32)
    ds = ArrayDataset(imgs, labels)
    ld = Loader(ds, 16, seed=1, repeat=2)
    assert len(ld) == 8
    batches = list(ld.epoch(0))
    assert len(batches) == 8
    first_pass = np.concatenate([y for _, y in batches[:4]])
    second_pass = np.concatenate([y for _, y in batches[4:]])
    # each pass is a full permutation; the two passes are ordered differently
    assert set(first_pass) == set(range(64)) == set(second_pass)
    assert not np.array_equal(first_pass, second_pass)
    # repeat=1 stream is unchanged by the feature (pass 0 seeds the same)
    np.testing.assert_array_equal(
        np.concatenate([y for _, y in Loader(ds, 16, seed=1).epoch(0)]),
        first_pass)
    with pytest.raises(ValueError, match="repeat"):
        Loader(ds, 16, repeat=0)


def test_prefetch_to_mesh_shards(devices):
    mesh = meshlib.data_mesh(8)
    imgs, labels = synthetic.make_idc_like(64, size=8, seed=0)
    ld = Loader(ArrayDataset(imgs, labels), 16, shuffle=False)
    out = list(pipeline.prefetch_to_mesh(iter(ld), mesh))
    assert len(out) == 4
    x, y = out[0]
    assert x.shape == (16, 8, 8, 3)
    assert len(x.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(y), labels[:16])


def test_prefetch_propagates_errors(devices):
    mesh = meshlib.data_mesh(8)

    def bad():
        yield (np.zeros((8, 4, 4, 3), np.float32), np.zeros(8, np.int32))
        raise RuntimeError("decode failed")

    it = pipeline.prefetch_to_mesh(bad(), mesh)
    next(it)
    with pytest.raises(RuntimeError, match="decode failed"):
        list(it)


def test_pad_to_multiple():
    x = np.ones((10, 4, 4, 3), np.float32)
    y = np.ones(10, np.int32)
    px, py, mask = pipeline.pad_to_multiple(x, y, 8)
    assert px.shape[0] == 16 and mask.sum() == 10
    px2, _, mask2 = pipeline.pad_to_multiple(x[:8], y[:8], 8)
    assert px2.shape[0] == 8 and mask2.all()


def test_partition_iid_vs_noniid():
    imgs, labels = synthetic.make_idc_like(400, size=8, seed=0,
                                           pos_fraction=0.5)
    ds = ArrayDataset(imgs, labels)
    ci, cl = partition.partition_clients(ds, 8, iid=True, seed=0)
    assert ci.shape == (8, 50, 8, 8, 3) and cl.shape == (8, 50)
    iid_skew = np.abs(cl.mean(axis=1) - labels.mean()).max()
    _, cl_n = partition.partition_clients(ds, 8, iid=False, seed=0)
    # non-IID: most clients are single-class
    frac = cl_n.mean(axis=1)
    assert np.sum((frac > 0.99) | (frac < 0.01)) >= 6
    assert iid_skew < 0.2


def test_partition_deterministic():
    imgs, labels = synthetic.make_idc_like(64, size=8, seed=0)
    ds = ArrayDataset(imgs, labels)
    a = partition.partition_clients(ds, 4, iid=False, seed=3)
    b = partition.partition_clients(ds, 4, iid=False, seed=3)
    np.testing.assert_array_equal(a[1], b[1])


def test_train_test_client_split():
    tr, te = partition.train_test_client_split(10, 0.2, seed=0)
    assert len(tr) == 8 and len(te) == 2
    assert set(tr) | set(te) == set(range(10))


def test_strided_shard():
    imgs, labels = synthetic.make_idc_like(20, size=8, seed=0)
    labels = np.arange(20, dtype=np.int32)
    ds = ArrayDataset(imgs, labels)
    s = ds.shard(4, 1)
    np.testing.assert_array_equal(s.labels, [1, 5, 9, 13, 17])


def test_cifar10_synthetic_fallback():
    with pytest.warns(UserWarning, match="synthetic stand-in"):
        ds = cifar10.load_cifar10(None, synthetic_size=128)
    assert ds.images.shape == (128, 32, 32, 3)
    assert ds.labels.max() < 10


def test_cifar10_npz(tmp_path):
    x = (np.random.default_rng(0).random((8, 32, 32, 3)) * 255).astype(np.uint8)
    y = np.arange(8) % 10
    np.savez(tmp_path / "cifar10.npz", x_train=x, y_train=y,
             x_test=x[:4], y_test=y[:4])
    ds = cifar10.load_cifar10(str(tmp_path), split="train")
    assert len(ds) == 8
    np.testing.assert_allclose(ds.images, x.astype(np.float32) / 255.0)


def test_filestream_matches_materialized_loader(png_tree):
    """Streaming a directory and training on its materialized
    ArrayDataset (same pair order) must produce identical batch streams
    — FileStream duck-types Loader bit-for-bit."""
    from idc_models_tpu.data.idc import decode_pairs, list_labeled_files

    pairs = list_labeled_files(png_tree)
    stream = pipeline.FileStream(pairs, 50, 8, seed=3)
    labels = np.asarray([l for _, l in pairs], np.int32)
    ds = ArrayDataset(decode_pairs(pairs, 50), labels)
    ld = Loader(ds, 8, seed=3)
    assert len(stream) == len(ld) == 3
    for (sx, sy), (lx, ly) in zip(stream.epoch(1), ld.epoch(1)):
        np.testing.assert_array_equal(sx, lx)
        np.testing.assert_array_equal(sy, ly)
    # repeat passes mirror Loader's seeding too
    s2 = pipeline.FileStream(pairs, 50, 8, seed=3, repeat=2)
    assert len(s2) == 6
    ys = [y for _, y in s2.epoch(0)]
    assert len(ys) == 6
    with pytest.raises(ValueError, match="non-empty"):
        pipeline.FileStream([], 50, 8)
    # replace() re-validates, so fit's schedule path fails as loudly as
    # the constructor would
    with pytest.raises(ValueError, match="repeat"):
        stream.replace(repeat=0)
    with pytest.raises(ValueError, match="batch_size"):
        stream.replace(batch_size=0)
    with pytest.raises(AttributeError):
        stream.replace(nope=1)
    stream.close()  # idempotent even when the pool was never created
    stream.close()


def test_filestream_decode_workers_bit_identical(png_tree):
    """Multi-process decode fan-out (--decode-workers): round-robin
    whole batches over 2 spawned worker processes must yield a stream
    BIT-IDENTICAL to the single-process one, across epochs and repeat
    passes — the parallelism changes throughput, never the data."""
    from idc_models_tpu.data.idc import list_labeled_files

    pairs = list_labeled_files(png_tree)
    base = pipeline.FileStream(pairs, 50, 8, seed=3, repeat=2)
    fanout = pipeline.FileStream(pairs, 50, 8, seed=3, repeat=2,
                                 decode_workers=2)
    try:
        assert len(fanout) == len(base) == 6
        for ep in (0, 1):
            for (sx, sy), (fx, fy) in zip(base.epoch(ep),
                                          fanout.epoch(ep),
                                          strict=True):
                np.testing.assert_array_equal(fx, sx)
                np.testing.assert_array_equal(fy, sy)
        # replace() copies share the worker pool and stay identical
        half = fanout.replace(batch_size=4)
        halfb = base.replace(batch_size=4)
        for (sx, sy), (fx, fy) in zip(halfb.epoch(0), half.epoch(0),
                                      strict=True):
            np.testing.assert_array_equal(fx, sx)
            np.testing.assert_array_equal(fy, sy)
        assert half._proc_box is fanout._proc_box
    finally:
        fanout.close()
        fanout.close()  # idempotent, terminates worker processes once


def test_fit_on_filestream_equals_materialized(png_tree, devices):
    """End-to-end: training from the stream lands on exactly the state
    the materialized path produces."""
    import jax

    from idc_models_tpu.data.idc import decode_pairs, list_labeled_files
    from idc_models_tpu.models import small_cnn
    from idc_models_tpu.train import create_train_state, fit, rmsprop
    from idc_models_tpu.train.losses import binary_cross_entropy

    pairs = list_labeled_files(png_tree)
    labels = np.asarray([l for _, l in pairs], np.int32)
    ds = ArrayDataset(decode_pairs(pairs, 10), labels)
    mesh = meshlib.data_mesh(8)
    model = small_cnn(10, 3, 1)

    def run(train_source):
        opt = rmsprop(1e-3)
        state = create_train_state(model, opt, jax.random.key(0))
        state, hist = fit(model, opt, binary_cross_entropy, state,
                          train_source, None, mesh, epochs=2,
                          batch_size=8, seed=5, verbose=False)
        return jax.device_get(state.params), hist["loss"]

    p_mat, l_mat = run(ds)
    # stream built with a DIFFERENT seed: fit reseeds the schedule to its
    # own (seed=5), so phase seeds apply identically to both paths
    p_str, l_str = run(pipeline.FileStream(pairs, 10, 8, seed=0))
    np.testing.assert_allclose(l_str, l_mat, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p_str), jax.tree.leaves(p_mat)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_cifar10_pickle_batches(tmp_path):
    """The cifar-10-batches-py branch: 5 train batches concatenated, CHW
    row-major 3072-vectors transposed to NHWC, /255 scaling."""
    import pickle

    d = tmp_path / "cifar-10-batches-py"
    d.mkdir()

    def make_batch(path, n, label_base):
        # per-image planes: channel c filled with a recoverable constant
        data = np.zeros((n, 3072), np.uint8)
        for i in range(n):
            planes = np.stack([np.full((32, 32), 10 * (c + 1) + i, np.uint8)
                               for c in range(3)])
            data[i] = planes.reshape(-1)
        with open(path, "wb") as f:
            pickle.dump({b"data": data,
                         b"labels": [(label_base + i) % 10 for i in range(n)]},
                        f)

    for b in range(1, 6):
        make_batch(d / f"data_batch_{b}", 4, b)
    make_batch(d / "test_batch", 6, 0)

    train = cifar10.load_cifar10(str(tmp_path), split="train")
    test = cifar10.load_cifar10(str(tmp_path), split="test")
    assert train.images.shape == (20, 32, 32, 3)
    assert test.images.shape == (6, 32, 32, 3)
    assert train.images.dtype == np.float32
    # image 0 of batch 1: channel c == (10*(c+1) + 0)/255 everywhere
    for c in range(3):
        np.testing.assert_allclose(train.images[0, :, :, c],
                                   (10 * (c + 1)) / 255.0)
    # batches concatenate in order: image 4 is batch 2's image 0
    np.testing.assert_allclose(train.images[4, :, :, 0], 10 / 255.0)
    np.testing.assert_array_equal(train.labels[:4], [1, 2, 3, 4])
    np.testing.assert_array_equal(train.labels[4:8], [2, 3, 4, 5])
    np.testing.assert_array_equal(test.labels, np.arange(6) % 10)


def test_prefetch_abandoned_iterator_stops_producer(devices):
    import threading
    mesh = meshlib.data_mesh(8)
    imgs, labels = synthetic.make_idc_like(64, size=8, seed=0)
    ld = Loader(ArrayDataset(imgs, labels), 8, shuffle=False)
    n_before = threading.active_count()
    it = pipeline.prefetch_to_mesh(iter(ld), mesh, prefetch=1)
    next(it)
    it.close()  # abandon early
    import time
    for _ in range(50):
        if threading.active_count() <= n_before:
            break
        time.sleep(0.1)
    assert threading.active_count() <= n_before


def test_cifar10_synthetic_splits_differ():
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("ignore")
        tr = cifar10.load_cifar10(None, split="train", synthetic_size=64)
        te = cifar10.load_cifar10(None, split="test", synthetic_size=64)
    assert not np.array_equal(tr.images, te.images)


def test_patchify_token_mapping():
    """patchify: raster-order tokens, each the row-major flatten of one
    sub-patch with channels innermost; patch_size=1 is the per-pixel
    sequence; the token count/width match sequence_shape."""
    from idc_models_tpu.data import sequences

    rng = np.random.default_rng(0)
    imgs = rng.random((2, 6, 6, 3)).astype(np.float32)
    toks = sequences.patchify(imgs, 3)
    assert toks.shape == (2, 4, 27)
    assert toks.shape[1:] == sequences.sequence_shape(6, 3)
    # token 1 = sub-patch at (row 0, col 1); feature order (py, px, c)
    np.testing.assert_array_equal(
        toks[0, 1].reshape(3, 3, 3), imgs[0, 0:3, 3:6, :])
    # token 2 = sub-patch at (row 1, col 0)
    np.testing.assert_array_equal(
        toks[1, 2].reshape(3, 3, 3), imgs[1, 3:6, 0:3, :])
    # per-pixel degenerate case
    pix = sequences.patchify(imgs, 1)
    assert pix.shape == (2, 36, 3)
    np.testing.assert_array_equal(pix[0, 7], imgs[0, 1, 1, :])


def test_patchify_rejections():
    from idc_models_tpu.data import sequences

    with pytest.raises(ValueError, match="divisible"):
        sequences.patchify(np.zeros((1, 6, 6, 3), np.float32), 4)
    with pytest.raises(ValueError, match="N, S, S, C"):
        sequences.patchify(np.zeros((6, 6, 3), np.float32), 2)
    with pytest.raises(ValueError, match=">= 1"):
        sequences.sequence_shape(6, 0)


# -- the loader's staging ring (recycled host batches under prefetch) ------


def _tagged_loader(n, batch, **kw):
    """Every row carries its own index, so a wrong or torn row shows."""
    imgs = synthetic.make_idc_like(n, size=6, seed=0)[0].astype(np.float32)
    imgs[:, 0, 0, 0] = np.arange(n)
    return Loader(ArrayDataset(imgs, np.arange(n, dtype=np.int32)), batch, **kw)


def _host(batch):
    return tuple(np.asarray(a) for a in batch)


@pytest.mark.parametrize("kw", [
    dict(shuffle=True, repeat=2, seed=3),
    dict(shuffle=True, seed=1, drop_remainder=False),   # final batch of 8
    dict(shuffle=False),
], ids=["shuffle_repeat2", "partial_final_batch", "in_order"])
def test_staged_stream_equals_direct_epoch(devices, kw):
    """Handed to the prefetcher, a loader's epochs come out of its ring
    bit for bit as `Loader.epoch` yields them, slots reused across
    epochs."""
    mesh = meshlib.data_mesh(8)
    ld = _tagged_loader(72, 16, **kw)
    for epoch in range(4):
        got = [_host(b) for b in
               pipeline.prefetch_to_mesh(ld.epoch(epoch), mesh)]
        want = list(ld.epoch(epoch))
        assert len(got) == len(want) == len(ld)
        for (x, y), (wx, wy) in zip(got, want):
            assert x.dtype == wx.dtype and y.dtype == wy.dtype
            np.testing.assert_array_equal(x, wx)
            np.testing.assert_array_equal(y, wy)
    ring = ld._ring
    assert ring is not None and len(ring.slots) == 4    # prefetch 2 + 2
    assert all(s.placed is not None for s in ring.slots)


def test_staged_batches_do_not_change_after_hand_over(devices):
    """The ring (3 slots) is shallower than the epoch (12 batches): every
    slot is refilled three times while the consumer still holds every
    device array it was given."""
    mesh = meshlib.data_mesh(8)
    ld = _tagged_loader(96, 8, shuffle=True, seed=5)
    held = list(pipeline.prefetch_to_mesh(ld.epoch(0), mesh, prefetch=1))
    assert len(ld._ring.slots) == 3 and len(held) == 12
    for got, (wx, wy) in zip(held, ld.epoch(0)):
        x, y = _host(got)
        np.testing.assert_array_equal(x, wx)
        np.testing.assert_array_equal(y, wy)


def test_put_with_sharding_may_alias_false_copies_an_aligned_buffer(devices):
    """jax's CPU client keeps a 64-byte-aligned numpy buffer as the
    array's memory; a staging slot is written again, so its placement
    must not."""
    sh = meshlib.sharding(meshlib.data_mesh(8), meshlib.DATA_AXIS)
    raw = np.empty(8 * 64 * 4 + 128, np.uint8)
    start = (-raw.ctypes.data) % 64
    a = raw[start:start + 8 * 64 * 4].view(np.float32).reshape(8, 64)
    a[...] = 1.0
    placed = meshlib.put_with_sharding(a, sh, may_alias=False)
    placed.block_until_ready()
    a[...] = 2.0
    np.testing.assert_array_equal(np.asarray(placed), 1.0)


def test_abandoned_epoch_then_new_epoch_on_the_same_loader(devices):
    """The abandoned epoch's producer may still be filling a slot, and
    its transfers are in flight: the next epoch takes the ring over only
    once it is gone, and waits for the slots' arrays."""
    mesh = meshlib.data_mesh(8)
    ld = _tagged_loader(96, 8, shuffle=True, seed=2)
    for epoch in range(3):
        it = pipeline.prefetch_to_mesh(ld.epoch(epoch), mesh, prefetch=1)
        next(it)
        it.close()
        got = [_host(b) for b in pipeline.prefetch_to_mesh(
            ld.epoch(epoch + 10), mesh, prefetch=1)]
        for (x, y), (wx, wy) in zip(got, ld.epoch(epoch + 10), strict=True):
            np.testing.assert_array_equal(x, wx)
            np.testing.assert_array_equal(y, wy)
    assert ld._ring._lock.acquire(timeout=5)    # the last producer let go
    ld._ring._lock.release()


def test_two_live_epochs_of_one_loader_do_not_share_the_ring(devices):
    """A second epoch started while the first still runs gathers the
    plain way; neither blocks, both are right."""
    mesh = meshlib.data_mesh(8)
    ld = _tagged_loader(64, 8, shuffle=True, seed=4)
    a = pipeline.prefetch_to_mesh(ld.epoch(0), mesh)
    b = pipeline.prefetch_to_mesh(ld.epoch(1), mesh)
    for ga, gb, wa, wb in zip(a, b, ld.epoch(0), ld.epoch(1), strict=True):
        np.testing.assert_array_equal(np.asarray(ga[0]), wa[0])
        np.testing.assert_array_equal(np.asarray(gb[0]), wb[0])


def test_loader_iterated_directly_yields_arrays_the_caller_owns(devices):
    mesh = meshlib.data_mesh(8)
    ld = _tagged_loader(64, 8, shuffle=True, seed=6)
    assert iter(ld.epoch(0)) is not iter(ld.epoch(0))
    before = list(ld.epoch(0))
    assert ld._ring is None             # no hand-over, no ring
    copies = [(x.copy(), y.copy()) for x, y in before]
    list(pipeline.prefetch_to_mesh(ld.epoch(1), mesh))      # uses the ring
    after = list(ld)                    # __iter__ is epoch 0
    slots = [s.images for s in ld._ring.slots]
    for i, ((x, y), (cx, cy), (ax, ay)) in enumerate(
            zip(before, copies, after, strict=True)):
        np.testing.assert_array_equal(x, cx)     # untouched by the ring
        np.testing.assert_array_equal(ax, cx)
        np.testing.assert_array_equal(ay, cy)
        assert x.flags.owndata and ax.flags.owndata
        for other in [b[0] for b in before[:i]] + slots:
            assert not np.shares_memory(x, other)
            assert not np.shares_memory(ax, other)


def test_replaced_loader_has_a_ring_of_its_own(devices):
    mesh = meshlib.data_mesh(8)
    ld = _tagged_loader(64, 8, shuffle=True, seed=6)
    list(pipeline.prefetch_to_mesh(ld.epoch(0), mesh))
    big = ld.replace(batch_size=16, seed=7)
    assert big._ring is None
    got = [_host(b) for b in pipeline.prefetch_to_mesh(big.epoch(0), mesh)]
    for (x, y), (wx, wy) in zip(got, big.epoch(0), strict=True):
        np.testing.assert_array_equal(x, wx)
    assert big._ring is not ld._ring
    assert big._ring.slots[0].images.shape[0] == 16


def test_prefetch_eval_batches_pads_the_final_partial_batch(devices):
    """The eval pipeline stays on the plain path (its padded batches are
    built by a generator of its own): 21 rows in batches of 8 over 8
    devices end in 5 rows padded to 8."""
    mesh = meshlib.data_mesh(8)
    ld = _tagged_loader(21, 8, shuffle=False)
    got = list(pipeline.prefetch_eval_batches(ld.ds, mesh, 8))
    assert [size for _, _, size in got] == [8, 8, 5]
    rows = np.concatenate([np.asarray(x)[:size] for x, _, size in got])
    np.testing.assert_array_equal(rows, ld.ds.images)
    x, y, _ = got[-1]
    assert x.shape[0] == 8
    np.testing.assert_array_equal(np.asarray(x)[5:], 0.0)
    np.testing.assert_array_equal(np.asarray(y)[:5], ld.ds.labels[16:])


def test_sliced_fill_equals_single_thread_fill(monkeypatch):
    """A batch of several slices is filled by the ring's pool, row slice
    by row slice; the pool's threads live and die with the ring."""
    import os
    import sys
    import threading

    ld = _tagged_loader(512, 96, shuffle=True, seed=9)
    row_bytes = ld.ds.images[0].nbytes
    monkeypatch.setattr(pipeline, "_FILL_SLICE_BYTES", 8 * row_bytes)
    monkeypatch.setattr(os, "cpu_count", lambda: 16)
    ring = pipeline._StagingRing(2, 96, ld.ds)
    assert ring.width == 4
    one = pipeline._StagingRing(1, 4, ld.ds)            # under one slice
    assert one.width == 1 and one._pool is None
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for i, idx in enumerate(list(ld._index_batches(0)) * 20):
            idx = idx[:len(idx) - i % 7]                # ragged ends too
            x, y = ring.fill(ring.slots[i % 2], ld.ds, idx)
            np.testing.assert_array_equal(x, ld.ds.images[idx])
            np.testing.assert_array_equal(y, ld.ds.labels[idx])
            x1, _ = one.fill(one.slots[0], ld.ds, idx[:4])
            np.testing.assert_array_equal(x1, x[:4])
    finally:
        sys.setswitchinterval(old)
    assert any(t.name.startswith("idc-stage") for t in threading.enumerate())
    ring._pool.shutdown(wait=True)
    assert not any(t.name.startswith("idc-stage")
                   for t in threading.enumerate())
