"""Native C++ prefetcher tests."""
import os

import numpy as np
import pytest

from bigdl_tpu import native


pytestmark = pytest.mark.skipif(not native.available(),
                                reason="no native toolchain")


def test_prefetcher_batches_match_python():
    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 255, size=(50, 3, 8, 8)).astype(np.uint8)
    labels = rng.randint(1, 11, size=(50,)).astype(np.int64)
    mean, std = [10.0, 20.0, 30.0], [2.0, 3.0, 4.0]
    pf = native.NativePrefetcher(imgs, labels, mean, std, batch_size=16,
                                 n_workers=2)
    batches = list(pf.data(train=False))
    assert sum(b.size() for b in batches) == 50
    # deterministic order for train=False: reconstruct and compare
    x0 = batches[0].get_input()
    ref = (imgs[:16].astype(np.float32) -
           np.asarray(mean, np.float32)[:, None, None]) / \
        np.asarray(std, np.float32)[:, None, None]
    assert np.allclose(x0, ref, atol=1e-5)
    assert np.allclose(batches[0].get_target(), labels[:16])


def test_prefetcher_shuffled_epoch_covers_all():
    imgs = np.arange(40, dtype=np.uint8).reshape(40, 1, 1, 1)
    labels = np.arange(1, 41, dtype=np.int64)
    pf = native.NativePrefetcher(imgs, labels, [0.0], [1.0], batch_size=8)
    seen = []
    for b in pf.data(train=True):
        seen.extend(np.asarray(b.get_target()).astype(int).tolist())
    assert sorted(seen) == list(range(1, 41))


def test_prefetcher_looped_epochs_cover_all_without_restart():
    """loop_epochs=k yields k full (independently permuted) epochs from ONE
    worker run — the no-queue-refill-stall path the realdata bench uses."""
    imgs = np.arange(40, dtype=np.uint8).reshape(40, 1, 1, 1)
    labels = np.arange(1, 41, dtype=np.int64)
    pf = native.NativePrefetcher(imgs, labels, [0.0], [1.0], batch_size=8)
    seen = []
    for b in pf.data(train=True, loop_epochs=3):
        seen.extend(np.asarray(b.get_target()).astype(int).tolist())
    assert len(seen) == 120
    # every epoch's worth of labels appears exactly 3 times overall
    assert sorted(seen) == sorted(list(range(1, 41)) * 3)
    # non-divisible n: each epoch drops its partial batch so no minibatch
    # spans an epoch boundary (which could repeat a sample within a batch)
    pf2 = native.NativePrefetcher(imgs, labels, [0.0], [1.0], batch_size=16)
    batches = [np.asarray(b.get_target()).astype(int)
               for b in pf2.data(train=True, loop_epochs=2)]
    assert [len(b) for b in batches] == [16, 16, 16, 16]  # 2 * (40 // 16)
    for b in batches:
        assert len(set(b.tolist())) == len(b), "duplicate sample in batch"


def test_prefetcher_trains_lenet():
    from bigdl_tpu import nn
    from bigdl_tpu.models import LeNet5
    from bigdl_tpu.optim import LocalOptimizer, SGD, max_iteration
    from bigdl_tpu.dataset import mnist
    imgs, labels = mnist.load(n_synthetic=256)
    pf = native.NativePrefetcher(imgs[:, None], labels,
                                 [mnist.TRAIN_MEAN], [mnist.TRAIN_STD],
                                 batch_size=64)
    opt = LocalOptimizer(LeNet5(10), pf, nn.ClassNLLCriterion(),
                         SGD(learningrate=0.05), max_iteration(8), 64)
    opt.optimize()
    assert opt.optim_method.state["loss"] < 2.5


# ---- native JPEG decode -----------------------------------------------------

def _make_jpeg(tmp_path, w=64, h=48, q=95, name="img.jpg"):
    from PIL import Image
    rng = np.random.RandomState(0)
    # smooth gradient (JPEG-friendly so decode comparison is tight)
    yy, xx = np.mgrid[0:h, 0:w]
    arr = np.stack([(xx * 255 / w), (yy * 255 / h),
                    ((xx + yy) * 127 / (w + h))], -1).astype(np.uint8)
    path = str(tmp_path / name)
    Image.fromarray(arr).save(path, quality=q)
    return path, arr


def test_native_jpeg_decode_matches_pil(tmp_path):
    from bigdl_tpu import native
    if not native.jpeg_available():
        import pytest
        pytest.skip("libjpeg not available")
    from PIL import Image
    path, _ = _make_jpeg(tmp_path)
    ours = native.decode_jpeg(path)
    ref = np.asarray(Image.open(path).convert("RGB"))
    assert ours.shape == ref.shape
    # same bitstream, independent decoders: allow small IDCT rounding diffs
    assert np.mean(np.abs(ours.astype(int) - ref.astype(int))) < 2.0
    assert np.max(np.abs(ours.astype(int) - ref.astype(int))) <= 24


def test_native_jpeg_decode_resize_norm(tmp_path):
    from bigdl_tpu import native
    if not native.jpeg_available():
        import pytest
        pytest.skip("libjpeg not available")
    path, _ = _make_jpeg(tmp_path, w=100, h=80)
    mean, std = [10.0, 20.0, 30.0], [2.0, 3.0, 4.0]
    out = native.decode_jpeg_resize_norm(path, 32, 32, mean, std)
    assert out.shape == (3, 32, 32)
    # un-normalize and compare against python bilinear of the full decode
    full = native.decode_jpeg(path).astype(np.float32)
    back = out * np.array(std, np.float32)[:, None, None] + \
        np.array(mean, np.float32)[:, None, None]
    assert back.min() >= -1 and back.max() <= 256
    # centers should track the gradient: monotone along x for channel 0
    row = back[0, 16]
    assert np.all(np.diff(row) > -3)


def test_native_jpeg_folder_prefetcher(tmp_path):
    from bigdl_tpu import native
    if not native.jpeg_available():
        import pytest
        pytest.skip("libjpeg not available")
    paths, labels = [], []
    for i in range(8):
        p, _ = _make_jpeg(tmp_path, w=40 + i, h=30 + i, name=f"im{i}.jpg")
        paths.append(p)
        labels.append(i % 4 + 1)
    # n_workers=1: batches are pushed in completion order, so only a single
    # worker guarantees index order for the exact-label assertion below
    pf = native.JpegFolderPrefetcher(paths, labels, 24, 24, 0.0, 255.0,
                                     batch_size=3, n_workers=1)
    assert pf.size() == 8
    seen, ys = 0, []
    for mb in pf.data(train=False):
        assert mb.input.shape[1:] == (3, 24, 24)
        assert np.isfinite(mb.input).all()
        assert mb.input.max() <= 1.0
        seen += mb.input.shape[0]
        ys += list(mb.target)
    assert seen == 8
    assert ys == [float(l) for l in labels]  # single worker: order preserved
    assert pf.decode_failures == 0
    # multi-worker: same multiset of samples, any batch order
    pf2 = native.JpegFolderPrefetcher(paths, labels, 24, 24, 0.0, 255.0,
                                      batch_size=3, n_workers=3)
    ys2 = sorted(y for mb in pf2.data(train=False) for y in mb.target)
    assert ys2 == sorted(float(l) for l in labels)


def test_native_jpeg_prefetcher_bf16_nhwc_output(tmp_path):
    """out="bf16_nhwc" emits accelerator-ready batches: same pixels as the
    f32 CHW path within bf16 rounding, transposed to NHWC, dtype bf16.
    n_workers=1 so both instances deliver batches in cursor order."""
    import ml_dtypes
    if not native.jpeg_available():
        import pytest
        pytest.skip("libjpeg not available")
    paths, labels = [], []
    for i in range(8):
        p, _ = _make_jpeg(tmp_path, w=48, h=48, name=f"bf{i}.jpg")
        paths.append(p)
        labels.append(i % 4 + 1)
    kw = dict(mean=(124.0, 117.0, 104.0), std=(59.0, 57.0, 57.0),
              batch_size=4, n_workers=1, queue_capacity=2)
    pf32 = native.JpegFolderPrefetcher(paths, labels, 32, 32, **kw)
    pf16 = native.JpegFolderPrefetcher(paths, labels, 32, 32,
                                       out="bf16_nhwc", **kw)
    b32 = next(pf32.data(train=False))
    b16 = next(pf16.data(train=False))
    x16 = np.asarray(b16.get_input())
    assert x16.dtype == ml_dtypes.bfloat16
    assert x16.shape == (4, 32, 32, 3)
    x32 = np.transpose(np.asarray(b32.get_input()), (0, 2, 3, 1))
    assert np.max(np.abs(x32 - x16.astype(np.float32))) < 0.02
    assert np.allclose(np.asarray(b32.get_target()),
                       np.asarray(b16.get_target()))
    # non-JPEG prefetchers reject the format rather than crash
    imgs = np.zeros((8, 1, 8, 8), np.uint8)
    pf = native.NativePrefetcher(imgs, np.arange(1, 9, dtype=np.int64),
                                 [0.0], [1.0], batch_size=4)
    assert pf.lib.pf_set_format(pf.handle, 1) != 0


def test_native_jpeg_prefetcher_augmentation(tmp_path):
    """Worker-side RandomResizedCrop + hflip: deterministic per seed,
    different across seeds, different from the un-augmented decode, and
    statistically centered (mean within the un-augmented image's range)."""
    if not native.jpeg_available():
        import pytest
        pytest.skip("libjpeg not available")
    paths, labels = [], []
    for i in range(8):
        p, _ = _make_jpeg(tmp_path, w=64, h=48, name=f"aug{i}.jpg")
        paths.append(p)
        labels.append(i % 4 + 1)
    kw = dict(mean=(124.0, 117.0, 104.0), std=(59.0, 57.0, 57.0),
              batch_size=8, n_workers=1, queue_capacity=2)
    plain = np.asarray(next(native.JpegFolderPrefetcher(
        paths, labels, 32, 32, **kw).data(train=False)).get_input())
    a1 = np.asarray(next(native.JpegFolderPrefetcher(
        paths, labels, 32, 32, augment=True, seed=7,
        **kw).data(train=False)).get_input())
    a1b = np.asarray(next(native.JpegFolderPrefetcher(
        paths, labels, 32, 32, augment=True, seed=7,
        **kw).data(train=False)).get_input())
    a2 = np.asarray(next(native.JpegFolderPrefetcher(
        paths, labels, 32, 32, augment=True, seed=8,
        **kw).data(train=False)).get_input())
    assert np.array_equal(a1, a1b)          # same seed → same crops
    assert not np.array_equal(a1, a2)       # different seed → different
    assert not np.array_equal(a1, plain)    # augmented ≠ plain decode
    assert np.isfinite(a1).all()
    # crops sample real pixels: values stay within the plain image's
    # normalized range (bilinear cannot extrapolate)
    assert a1.min() >= plain.min() - 0.1 and a1.max() <= plain.max() + 0.1
    # non-JPEG prefetchers reject augmentation rather than crash
    imgs = np.zeros((8, 1, 8, 8), np.uint8)
    pf = native.NativePrefetcher(imgs, np.arange(1, 9, dtype=np.int64),
                                 [0.0], [1.0], batch_size=4)
    assert pf.lib.pf_set_augment(pf.handle, 1, 3) != 0


def test_native_jpeg_augmentation_worker_count_invariant(tmp_path):
    """Crops hash per (seed, epoch position), not per worker: the multiset
    of augmented images is identical for 1 vs 3 decode workers (batch
    ORDER may differ — completion order — but contents may not)."""
    if not native.jpeg_available():
        import pytest
        pytest.skip("libjpeg not available")
    paths, labels = [], []
    for i in range(12):
        p, _ = _make_jpeg(tmp_path, w=40, h=40, name=f"wi{i}.jpg")
        paths.append(p)
        labels.append(i % 3 + 1)

    def collect(n_workers):
        pf = native.JpegFolderPrefetcher(
            paths, labels, 24, 24, mean=(124.0, 117.0, 104.0),
            std=(59.0, 57.0, 57.0), batch_size=4, n_workers=n_workers,
            queue_capacity=2, augment=True, seed=5)
        out = []
        for mb in pf.data(train=False):
            for img in np.asarray(mb.get_input()):
                out.append(img.tobytes())
        return sorted(out)

    assert collect(1) == collect(3)


def test_native_jpeg_prefetcher_counts_bad_files(tmp_path):
    from bigdl_tpu import native
    if not native.jpeg_available():
        import pytest
        pytest.skip("libjpeg not available")
    good, _ = _make_jpeg(tmp_path, name="good.jpg")
    bad = str(tmp_path / "bad.jpg")
    with open(bad, "wb") as f:
        f.write(b"\xff\xd8 garbage that is not a jpeg")
    pf = native.JpegFolderPrefetcher([good, bad], [1, 2], 16, 16, 0.0, 255.0,
                                     batch_size=2, n_workers=1)
    batches = list(pf.data(train=False))
    assert pf.decode_failures == 1
    # the bad sample decoded to a zero image, the good one did not
    xs = np.concatenate([mb.input for mb in batches])
    zero_mask = [bool(np.all(x == 0)) for x in xs]
    assert sorted(zero_mask) == [False, True]


def test_native_jpeg_corrupt_input(tmp_path):
    from bigdl_tpu import native
    if not native.jpeg_available():
        import pytest
        pytest.skip("libjpeg not available")
    import pytest
    with pytest.raises(ValueError):
        native.decode_jpeg(b"not a jpeg at all" * 10)


def test_native_tfrecord_reader_matches_python(tmp_path):
    """C++ tfr_* reader == pure-python reader; corrupt crc raises in both."""
    from bigdl_tpu.native import read_tfrecords_native, available
    from bigdl_tpu.dataset.tfrecord import read_tfrecords, write_tfrecords
    if not available():
        import pytest
        pytest.skip("no native toolchain")

    path = str(tmp_path / "data.tfrecord")
    rng = np.random.RandomState(0)
    records = [rng.bytes(int(n)) for n in rng.randint(1, 2000, size=20)]
    records.append(b"")  # zero-length record edge case
    write_tfrecords(path, records)

    native = read_tfrecords_native(path)
    python = list(read_tfrecords(path, use_native=False))
    assert native == python == records

    # the public reader routes through the native path transparently
    assert list(read_tfrecords(path)) == records

    # corruption: flip a payload byte -> both readers raise
    blob = bytearray(open(path, "rb").read())
    blob[30] ^= 0xFF
    bad = str(tmp_path / "bad.tfrecord")
    open(bad, "wb").write(bytes(blob))
    import pytest
    with pytest.raises(IOError):
        read_tfrecords_native(bad)
    with pytest.raises(IOError):
        list(read_tfrecords(bad, use_native=False))


def test_tfrecord_interop_with_real_tensorflow(tmp_path):
    """Files we write are readable by REAL TensorFlow and vice versa (the
    masked-crc delta bug would fail this: 'corrupted record at 0')."""
    import pytest
    tf = pytest.importorskip("tensorflow")
    from bigdl_tpu.dataset.tfrecord import write_tfrecords, read_tfrecords

    ours = str(tmp_path / "ours.tfrecord")
    write_tfrecords(ours, [b"hello", b"\x00" * 100, b"world"])
    got = [r.numpy() for r in tf.data.TFRecordDataset(ours)]
    assert got == [b"hello", b"\x00" * 100, b"world"]

    theirs = str(tmp_path / "theirs.tfrecord")
    with tf.io.TFRecordWriter(theirs) as w:
        w.write(b"alpha")
        w.write(b"beta")
    assert list(read_tfrecords(theirs)) == [b"alpha", b"beta"]
    assert list(read_tfrecords(theirs, use_native=False)) == \
        [b"alpha", b"beta"]


def test_native_jpeg_encode_roundtrip():
    """je_encode inverse of jd_decode (smooth image: JPEG-friendly)."""
    import pytest
    from bigdl_tpu import native
    if not native.jpeg_available():
        pytest.skip("no libjpeg")
    h, w = 24, 30
    yy, xx = np.mgrid[0:h, 0:w]
    img = np.stack([yy * 255 // h, xx * 255 // w,
                    (yy + xx) * 255 // (h + w)], axis=-1).astype(np.uint8)
    back = native.decode_jpeg(native.encode_jpeg(img, quality=95))
    assert back.shape == img.shape
    assert np.abs(back.astype(int) - img.astype(int)).mean() < 3.0


def test_rebuild_decision_follows_source_contents_not_mtimes(tmp_path,
                                                             monkeypatch):
    """A checkout or a copy of the tree does not preserve mtimes, so the
    loader rebuilds when the stamp beside the .so does not hold the hash
    of the committed source — however new the binary looks."""
    from bigdl_tpu import native
    so, src = tmp_path / "lib.so", tmp_path / "prefetcher.cpp"
    monkeypatch.setattr(native, "_SO", str(so))
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_STAMP", str(so) + ".sha256")
    src.write_text("int v1;")
    assert native._needs_build()                      # no binary at all
    so.write_bytes(b"stale binary, newer mtime than the source")
    assert native._needs_build()                      # binary, no stamp
    (tmp_path / "lib.so.sha256").write_text(native._src_hash())
    assert not native._needs_build()                  # stamp matches
    src.write_text("int v2;")
    os.utime(src, (1, 1))                             # source looks OLD
    assert native._needs_build()                      # contents changed
