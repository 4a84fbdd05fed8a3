"""libmxtpu native component tests: parity with the Python codecs."""
import os

import numpy as onp
import pytest

import mxtpu as mx
from mxtpu import native, recordio
from mxtpu import io as mio

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="libmxtpu build unavailable")


def test_library_is_built_from_the_source_git_holds(monkeypatch):
    """The library's name is a hash of libmxtpu.cc, so a binary built
    from other source is never loaded; a failed build raises where a
    native component is asked for by name and answers False where the
    Python path is the alternative."""
    import hashlib
    with open(os.path.join(native._SRC_DIR, "libmxtpu.cc"), "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:16]
    assert os.path.basename(native._build()) == f"libmxtpu-{tag}.so"

    def no_compiler():
        raise OSError("g++: not found")

    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_LIB_ERROR", None)
    monkeypatch.setattr(native, "_build", no_compiler)
    assert native.available() is False
    with pytest.raises(RuntimeError, match="g\\+\\+: not found"):
        native.NativeRecordReader("x.rec")


@pytest.fixture(scope="module")
def rec_file(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("native")
    path = str(tmp / "data.rec")
    from mxtpu import image as mimg
    rng = onp.random.default_rng(0)
    w = recordio.MXRecordIO(path, "w")
    imgs = []
    for i in range(10):
        img = rng.integers(0, 255, (20, 24, 3), dtype=onp.uint8)
        imgs.append(img)
        w.write(recordio.pack_img(
            recordio.IRHeader(0, float(i % 3), i, 0), img, quality=95))
    w.close()
    return path, imgs


def test_native_record_reader_matches_python(rec_file):
    path, _ = rec_file
    r = native.NativeRecordReader(path)
    assert len(r) == 10
    pyr = recordio.MXRecordIO(path, "r")
    for i in range(10):
        assert r.read(i) == pyr.read()
    # random access out of order
    b7 = r.read(7)
    b2 = r.read(2)
    pyr.reset()
    expected = [pyr.read() for _ in range(10)]
    assert b7 == expected[7] and b2 == expected[2]


def test_native_multipart_record(tmp_path):
    import struct
    path = str(tmp_path / "mp.rec")
    magic = struct.pack("<I", 0xced7230a)
    with open(path, "wb") as f:
        def chunk(cflag, payload):
            f.write(struct.pack("<II", 0xced7230a,
                                (cflag << 29) | len(payload)))
            f.write(payload)
            f.write(b"\x00" * ((-len(payload)) % 4))
        chunk(1, b"abcd")
        chunk(3, b"efgh")
        chunk(0, b"tail")
    r = native.NativeRecordReader(path)
    assert len(r) == 2
    assert r.read(0) == b"abcd" + magic + b"efgh"
    assert r.read(1) == b"tail"


def test_native_jpeg_decode_close_to_tf(rec_file):
    path, imgs = rec_file
    r = native.NativeRecordReader(path)
    header, buf = recordio.unpack(r.read(0))
    from mxtpu.image import imdecode
    tf_img = imdecode(buf, as_numpy=True)
    native_img = native.jpeg_decode(bytes(buf))
    assert native_img.shape == tf_img.shape
    # libjpeg (islow) vs TF's libjpeg-turbo differ by a few LSBs per
    # pixel — worst on random-noise content; compare statistically
    diff = onp.abs(native_img.astype(int) - tf_img.astype(int))
    assert diff.mean() < 2.0, diff.mean()
    assert diff.max() <= 16, diff.max()


def test_native_pipeline_and_iter(rec_file):
    path, _ = rec_file
    it = mio.NativeImageRecordIter(path_imgrec=path, data_shape=(3, 16, 16),
                                   batch_size=4, preprocess_threads=2)
    seen = 0
    labels = []
    for batch in it:
        assert batch.data[0].shape == (4, 3, 16, 16)
        n_valid = 4 - (batch.pad or 0)
        labels.extend(batch.label[0].asnumpy()[:n_valid].tolist())
        seen += n_valid
    assert seen == 10
    assert set(labels) == {0.0, 1.0, 2.0}
    it.reset()
    total2 = sum(4 - (b.pad or 0) for b in it)
    assert total2 == 10


def test_native_pipeline_shuffle_differs_across_epochs(rec_file):
    path, _ = rec_file
    it = mio.NativeImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                                   batch_size=10, shuffle=True, seed=1)
    l1 = next(it).label[0].asnumpy().tolist()
    it.reset()
    l2 = next(it).label[0].asnumpy().tolist()
    assert sorted(l1) == sorted(l2)
    # epochs reshuffle (seed+epoch): identical 10-permutations would be
    # a 1-in-10! coincidence
    assert l1 != l2


def test_native_order_deterministic_without_shuffle(rec_file):
    path, _ = rec_file
    it = mio.NativeImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                                   batch_size=10, preprocess_threads=3)
    labels = next(it).label[0].asnumpy().tolist()
    # file order: labels are i % 3 for i in 0..9
    assert labels == [i % 3 for i in range(10)]


def test_native_center_crop_matches_python(rec_file):
    # same pixels as the Python CenterCropAug path (crop then resize)
    path, imgs = rec_file
    from mxtpu import image as mimg
    it = mio.NativeImageRecordIter(path_imgrec=path, data_shape=(3, 16, 16),
                                   batch_size=1, preprocess_threads=1)
    native = next(it).data[0].asnumpy()[0].transpose(1, 2, 0)
    from mxtpu.recordio import MXRecordIO, unpack
    r = MXRecordIO(path, "r")
    _, buf = unpack(r.read())
    dec = mimg.imdecode(buf, as_numpy=True).astype(onp.float32)
    cropped, _ = mimg.center_crop(mx.nd.array(dec), (16, 16))
    ref = cropped.asnumpy()
    # decoder LSB differences + interpolation edge handling
    assert onp.abs(native - ref).mean() < 6.0


def test_native_u8_device_pipeline_matches_f32_host_path(rec_file):
    """The r5 fast path (uint8 handover + on-device convert/normalize/
    transpose) must reproduce the all-host f32 path to within the 0.5
    LSB the worker-side rounding costs."""
    path, _ = rec_file
    kw = dict(path_imgrec=path, data_shape=(3, 16, 16), batch_size=4,
              preprocess_threads=1, mean=[10.0, 20.0, 30.0],
              std=[2.0, 3.0, 4.0])
    it_dev = mio.NativeImageRecordIter(device_pipeline=True, **kw)
    it_host = mio.NativeImageRecordIter(device_pipeline=False, **kw)
    n = 0
    for bd, bh in zip(it_dev, it_host):
        d, h = bd.data[0].asnumpy(), bh.data[0].asnumpy()
        assert d.shape == h.shape == (4, 3, 16, 16)
        assert d.dtype == onp.float32
        # 0.5 raw-pixel rounding / smallest std 2.0 = 0.25
        assert onp.abs(d - h).max() <= 0.26, onp.abs(d - h).max()
        onp.testing.assert_allclose(bd.label[0].asnumpy(),
                                    bh.label[0].asnumpy())
        n += 1
    assert n == 3                    # 10 imgs / batch 4, incl. pad
    it_dev.close()
    it_host.close()


def test_imagerecorditer_routes_python_for_unsupported_kwargs(rec_file):
    path, _ = rec_file
    it = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 16, 16),
                             batch_size=2, rand_mirror=True)
    assert isinstance(it, mio.PrefetchingIter)     # python path
    it2 = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 16, 16),
                              batch_size=2)
    assert isinstance(it2, mio.NativeImageRecordIter)
