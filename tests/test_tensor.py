"""Tensor shell tests (DenseTensor/eager-Tensor parity surface)."""
import numpy as np
import pytest

import paddle_tpu as paddle


def test_to_tensor_dtypes():
    t = paddle.to_tensor([1.0, 2.0, 3.0])
    assert t.dtype == np.float32
    assert t.shape == [3]
    t64 = paddle.to_tensor(np.array([1.0, 2.0]))  # numpy dtype preserved (paddle parity)
    assert t64.dtype == np.float64
    ti = paddle.to_tensor([1, 2, 3])
    assert ti.dtype == np.int64
    tb = paddle.to_tensor([True, False])
    assert tb.dtype == np.bool_
    tf16 = paddle.to_tensor([1.0], dtype="bfloat16")
    assert tf16.dtype == paddle.bfloat16


def test_numpy_roundtrip_and_item():
    arr = np.arange(6, dtype=np.float32).reshape(2, 3)
    t = paddle.to_tensor(arr)
    np.testing.assert_array_equal(t.numpy(), arr)
    assert paddle.to_tensor(3.5).item() == pytest.approx(3.5)
    assert len(t) == 2
    assert t.size == 6
    assert t.ndim == 2


def test_astype_cast():
    t = paddle.to_tensor([1.5, 2.5])
    ti = t.astype("int32")
    assert ti.dtype == np.int32
    np.testing.assert_array_equal(ti.numpy(), [1, 2])


def test_indexing():
    t = paddle.to_tensor(np.arange(12, dtype=np.float32).reshape(3, 4))
    assert t[0].shape == [4]
    assert t[0, 1].item() == 1.0
    assert t[1:, :2].shape == [2, 2]
    idx = paddle.to_tensor([0, 2])
    np.testing.assert_array_equal(t[idx].numpy(), t.numpy()[[0, 2]])


def test_setitem():
    t = paddle.to_tensor(np.zeros((3, 3), np.float32))
    t[1] = 5.0
    assert t.numpy()[1].tolist() == [5.0, 5.0, 5.0]
    t[0, 0] = paddle.to_tensor(2.0)
    assert t[0, 0].item() == 2.0


def test_arithmetic_dunders():
    a = paddle.to_tensor([1.0, 2.0])
    b = paddle.to_tensor([3.0, 4.0])
    np.testing.assert_allclose((a + b).numpy(), [4, 6])
    np.testing.assert_allclose((a - b).numpy(), [-2, -2])
    np.testing.assert_allclose((a * b).numpy(), [3, 8])
    np.testing.assert_allclose((b / a).numpy(), [3, 2])
    np.testing.assert_allclose((a + 1).numpy(), [2, 3])
    np.testing.assert_allclose((2 * a).numpy(), [2, 4])
    np.testing.assert_allclose((-a).numpy(), [-1, -2])
    np.testing.assert_allclose((a**2).numpy(), [1, 4])
    assert (a == a).numpy().all()
    assert (a < b).numpy().all()


def test_clone_detach():
    t = paddle.to_tensor([1.0], stop_gradient=False)
    c = t.clone()
    d = t.detach()
    assert not c.stop_gradient
    assert d.stop_gradient
    d2 = t.detach()
    d2._value = d2._value + 1  # detached copy does not alias semantics we expose
    assert t.item() == 1.0


def test_set_value():
    t = paddle.to_tensor([1.0, 2.0])
    t.set_value(np.array([5.0, 6.0], np.float32))
    np.testing.assert_allclose(t.numpy(), [5, 6])
    with pytest.raises(ValueError):
        t.set_value(np.zeros(3, np.float32))


def test_parameter():
    p = paddle.Parameter(np.ones((2, 2), np.float32))
    assert not p.stop_gradient
    assert p.persistable


def test_tpu_place_never_resolves_to_a_cpu_device():
    """A Place is a handle onto a device of ITS backend: without a TPU,
    Place("tpu") raises instead of handing back whatever exists."""
    from paddle_tpu.core.device import Place

    assert Place("cpu", 0).jax_device.platform == "cpu"
    with pytest.raises(RuntimeError, match="tpu"):
        Place("tpu", 0).jax_device
    with pytest.raises(RuntimeError, match="device"):
        Place("cpu", 10_000).jax_device
    assert not Place("cpu").is_tpu_place() and Place("tpu").is_tpu_place()
