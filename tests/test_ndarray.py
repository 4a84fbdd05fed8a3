"""NDArray basics — rebuild of tests/python/unittest/test_ndarray.py themes."""
import numpy as np
import pytest

import mxtpu as mx
from mxtpu.test_utils import assert_almost_equal, with_seed


def test_creation():
    a = mx.nd.array([[1, 2], [3, 4]])
    assert a.shape == (2, 2)
    assert a.dtype == np.float32
    assert a.size == 4
    b = mx.nd.zeros((3, 4))
    assert (b.asnumpy() == 0).all()
    c = mx.nd.ones((2,), dtype="int32")
    assert c.dtype == np.int32
    d = mx.nd.full((2, 2), 7.0)
    assert (d.asnumpy() == 7).all()
    e = mx.nd.arange(0, 10, 2)
    assert_almost_equal(e, np.arange(0, 10, 2, dtype=np.float32))


@with_seed(0)
def test_arithmetic():
    a = mx.nd.random.uniform(shape=(3, 4))
    b = mx.nd.random.uniform(shape=(3, 4))
    an, bn = a.asnumpy(), b.asnumpy()
    assert_almost_equal(a + b, an + bn)
    assert_almost_equal(a - b, an - bn)
    assert_almost_equal(a * b, an * bn)
    assert_almost_equal(a / (b + 1), an / (bn + 1))
    assert_almost_equal(a ** 2, an ** 2)
    assert_almost_equal(-a, -an)
    assert_almost_equal(2 - a, 2 - an)
    assert_almost_equal(2 / (a + 1), 2 / (an + 1))
    assert_almost_equal(a.T, an.T)


def test_inplace_ops():
    a = mx.nd.ones((2, 3))
    a += 2
    assert (a.asnumpy() == 3).all()
    a *= 2
    assert (a.asnumpy() == 6).all()
    a -= 1
    assert (a.asnumpy() == 5).all()
    a /= 5
    assert (a.asnumpy() == 1).all()


def test_setitem_getitem():
    a = mx.nd.zeros((3, 4))
    a[1] = 5.0
    assert (a.asnumpy()[1] == 5).all()
    a[0, 2] = 1.5
    assert a.asnumpy()[0, 2] == 1.5
    a[:] = 2.0
    assert (a.asnumpy() == 2).all()
    b = a[1:3]
    assert b.shape == (2, 4)
    a[:] = np.arange(12).reshape(3, 4)
    assert a.asnumpy()[2, 3] == 11


def test_reshape_magic():
    a = mx.nd.zeros((2, 3, 4))
    assert a.reshape(6, 4).shape == (6, 4)
    assert a.reshape(-1).shape == (24,)
    assert a.reshape((0, 12)).shape == (2, 12)
    assert a.flatten().shape == (2, 12)
    assert a.expand_dims(0).shape == (1, 2, 3, 4)
    assert a.expand_dims(0).squeeze(0).shape == (2, 3, 4)


@with_seed()
def test_reductions():
    a = mx.nd.random.uniform(shape=(2, 3, 4))
    an = a.asnumpy()
    assert_almost_equal(a.sum(), an.sum())
    assert_almost_equal(a.sum(axis=1), an.sum(axis=1))
    assert_almost_equal(a.mean(axis=(0, 2)), an.mean(axis=(0, 2)))
    assert_almost_equal(a.max(axis=2), an.max(axis=2))
    assert_almost_equal(a.min(), an.min())
    assert_almost_equal(mx.nd.sum(a, axis=1, keepdims=True),
                        an.sum(axis=1, keepdims=True))
    assert_almost_equal(mx.nd.sum(a, axis=1, exclude=True),
                        an.sum(axis=(0, 2)))
    assert_almost_equal(a.argmax(axis=1),
                        an.argmax(axis=1).astype(np.float32))


@with_seed()
def test_dot():
    a = mx.nd.random.uniform(shape=(3, 4))
    b = mx.nd.random.uniform(shape=(4, 5))
    assert_almost_equal(mx.nd.dot(a, b), a.asnumpy() @ b.asnumpy())
    c = mx.nd.random.uniform(shape=(2, 3, 4))
    d = mx.nd.random.uniform(shape=(2, 4, 5))
    assert_almost_equal(mx.nd.batch_dot(c, d),
                        np.matmul(c.asnumpy(), d.asnumpy()))
    assert_almost_equal(mx.nd.dot(a, a, transpose_b=True),
                        a.asnumpy() @ a.asnumpy().T)


def test_concat_stack_split():
    a = mx.nd.ones((2, 3))
    b = mx.nd.zeros((2, 3))
    c = mx.nd.concat(a, b, dim=1)
    assert c.shape == (2, 6)
    s = mx.nd.stack(a, b, axis=0)
    assert s.shape == (2, 2, 3)
    parts = mx.nd.split(c, 2, axis=1)
    assert len(parts) == 2 and parts[0].shape == (2, 3)
    assert_almost_equal(parts[0], a.asnumpy())


def test_accelerator_context_past_last_chip_raises(monkeypatch):
    """mx.tpu(i) names chip i or nothing: an id past the last chip
    must not quietly become chip 0. cpu ids stay nominal."""
    import jax
    from mxtpu import context
    one = jax.devices()[:1]
    monkeypatch.setattr(context, "_devices_of_type", lambda t: one)
    assert mx.tpu(0).jax_device() is one[0]
    with pytest.raises(RuntimeError, match="past the last"):
        mx.tpu(1).jax_device()
    assert mx.cpu(3).jax_device() is one[0]


def test_astype_context():
    a = mx.nd.ones((2, 2))
    b = a.astype("float16")
    assert b.dtype == np.float16
    c = a.astype(np.int32)
    assert c.dtype == np.int32
    cpu_a = a.as_in_context(mx.cpu())
    assert cpu_a.context.device_type == "cpu"


def test_copyto_copy():
    a = mx.nd.ones((2, 2))
    b = mx.nd.zeros((2, 2))
    a.copyto(b)
    assert (b.asnumpy() == 1).all()
    c = a.copy()
    c[:] = 5
    assert (a.asnumpy() == 1).all()


def test_scalar_conversions():
    a = mx.nd.array([3.5])
    assert float(a) == 3.5
    assert a.asscalar() == 3.5
    b = mx.nd.array([2], dtype="int32")
    assert int(b) == 2
    with pytest.raises(ValueError):
        mx.nd.ones((2, 2)).asscalar()


def test_save_load(tmp_path):
    fname = str(tmp_path / "test.params")
    d = {"w": mx.nd.random.normal(shape=(3, 4)),
         "b": mx.nd.ones((4,), dtype="int64")}
    mx.nd.save(fname, d)
    loaded = mx.nd.load(fname)
    assert set(loaded) == {"w", "b"}
    assert_almost_equal(loaded["w"], d["w"].asnumpy())
    assert loaded["b"].dtype == np.int64
    lst = [mx.nd.ones((2,)), mx.nd.zeros((3,))]
    mx.nd.save(fname, lst)
    loaded = mx.nd.load(fname)
    assert isinstance(loaded, list) and len(loaded) == 2


def test_waitall_and_engine():
    a = mx.nd.ones((100, 100))
    for _ in range(10):
        a = a * 1.01
    a.wait_to_read()
    mx.nd.waitall()
    assert a.asnumpy().shape == (100, 100)
