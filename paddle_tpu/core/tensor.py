"""Tensor: a thin mutable shell over ``jax.Array``.

Plays the role of the reference's ``phi::DenseTensor`` + eager ``Tensor``
(/root/reference/paddle/phi/core/dense_tensor.h:43 and
 /root/reference/paddle/fluid/eager/autograd_meta.h:61): holds the device
array, the autograd metadata (``stop_gradient``, ``.grad``, producing
``GradNode``) and the user-facing method surface. Memory, layout and device
placement live inside XLA — there is no allocator or DeviceContext here.

Mutability (``set_value``, in-place optimizer updates, ``__setitem__``) is
implemented by swapping the wrapped immutable ``jax.Array``; under jit the
same modules run functionally over their state pytrees instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtype_mod
from .autograd import backward as _backward_engine
from .device import get_place

__all__ = ["Tensor", "Parameter", "to_tensor"]


class Tensor:
    __slots__ = (
        "_value",
        "stop_gradient",
        "_grad",
        "_grad_node",
        "_output_index",
        "_retain_grad",
        "_grad_hooks",
        "name",
        "persistable",
        "trainable",
        "sharding_spec",  # PartitionSpec annotation used by distributed engine
        "placements",  # auto-parallel marker (dist.Shard/Replicate list)
        "process_mesh",  # auto-parallel ProcessMesh annotation
        "_recompute",  # static-graph replay closure (paddle_tpu.static)
        "__weakref__",
    )

    def __init__(self, data=None, dtype=None, place=None, stop_gradient=True, name=None):
        if data is None:
            value = jnp.zeros((), dtype_mod.convert_dtype(dtype or "float32"))
        else:
            value = _to_jax_array(data, dtype)
        self._value = value
        self.stop_gradient = stop_gradient
        self._grad = None
        self._grad_node = None
        self._output_index = 0
        self._retain_grad = False
        self._grad_hooks = []
        self.name = name
        self.persistable = False
        self.trainable = True
        self.sharding_spec = None
        self._recompute = None

    # -- construction -----------------------------------------------------
    @classmethod
    def _wrap(cls, value, stop_gradient=True, node=None, output_index=0, name=None):
        t = cls.__new__(cls)
        t._value = value
        t.stop_gradient = stop_gradient
        t._grad = None
        t._grad_node = node
        t._output_index = output_index
        t._retain_grad = False
        t._grad_hooks = []
        t.name = name
        t.persistable = False
        t.trainable = True
        t.sharding_spec = None
        t._recompute = None
        return t

    # -- metadata ---------------------------------------------------------
    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def ndim(self):
        return self._value.ndim

    dim = ndim

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def dtype(self):
        return np.dtype(self._value.dtype)

    @property
    def place(self):
        try:
            devs = self._value.devices()
            dev = next(iter(devs))
            from .device import Place

            return Place(dev.platform, dev.id)
        except Exception:
            return get_place()

    @property
    def is_leaf(self):
        return self._grad_node is None

    # -- autograd ---------------------------------------------------------
    @property
    def grad(self):
        if self._grad is None:
            return None
        return Tensor._wrap(self._grad, stop_gradient=True)

    @grad.setter
    def grad(self, value):
        if value is None:
            self._grad = None
        else:
            self._grad = value._value if isinstance(value, Tensor) else jnp.asarray(value)

    def backward(self, grad_tensor=None, retain_graph=False):
        _backward_engine([self], [grad_tensor], retain_graph)

    def clear_grad(self):
        self._grad = None

    clear_gradient = clear_grad

    def retain_grads(self):
        self._retain_grad = True

    def register_hook(self, hook):
        self._grad_hooks.append(hook)

        class _Removable:
            def remove(inner):
                try:
                    self._grad_hooks.remove(hook)
                except ValueError:
                    pass

        return _Removable()

    def detach(self):
        return Tensor._wrap(self._value, stop_gradient=True, name=self.name)

    def detach_(self):
        self._grad_node = None
        self.stop_gradient = True
        return self

    # -- value access -----------------------------------------------------
    def numpy(self):
        return np.asarray(self._value)

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        arr = np.asarray(self._value)
        return arr.astype(dtype) if dtype is not None else arr

    def set_value(self, value):
        new = _to_jax_array(value, self.dtype)
        if tuple(new.shape) != tuple(self._value.shape):
            raise ValueError(
                f"set_value shape mismatch: {new.shape} vs {self._value.shape}"
            )
        self._value = new
        return self

    def copy_(self, other, blocking=True):
        return self.set_value(other)

    def clone(self):
        from .dispatch import apply

        return apply(lambda x: x + 0, self, op_name="clone")

    # -- dunder glue (full op surface is patched in by paddle_tpu.ops) ----
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    def __bool__(self):
        return bool(self.numpy())

    def __int__(self):
        return int(self.numpy())

    def __float__(self):
        return float(self.numpy())

    def __index__(self):
        return int(self.numpy())

    def __hash__(self):
        return id(self)

    def __repr__(self):
        prefix = "Tensor" if not isinstance(self, Parameter) else "Parameter"
        return (
            f"{prefix}(shape={self.shape}, dtype={dtype_mod.dtype_name(self.dtype)}, "
            f"stop_gradient={self.stop_gradient},\n       {self._value})"
        )

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __getitem__(self, idx):
        from .dispatch import apply

        idx = _unwrap_index(idx)
        return apply(lambda x: x[idx], self, op_name="getitem")

    def __setitem__(self, idx, value):
        idx = _unwrap_index(idx)
        val = value._value if isinstance(value, Tensor) else value
        self._value = self._value.at[idx].set(val)

    # -- misc parity helpers ---------------------------------------------
    def cpu(self):
        return Tensor._wrap(jax.device_get(self._value), stop_gradient=self.stop_gradient)

    def to(self, *args, **kwargs):
        t = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and (a in dtype_mod._NAME_TO_DTYPE):
                t = t.astype(a)
            elif isinstance(a, (np.dtype, type)):
                t = t.astype(a)
        return t

    def astype(self, dtype):
        from .dispatch import apply

        nd = dtype_mod.convert_dtype(dtype)
        return apply(lambda x: x.astype(nd), self, op_name="cast")

    cast = astype

    def _block_until_ready(self):
        jax.block_until_ready(self._value)
        return self


class Parameter(Tensor):
    """Trainable leaf tensor (``paddle.create_parameter`` /
    ``EagerParamBase``, /root/reference/python/paddle/fluid/framework.py)."""

    def __init__(self, data, dtype=None, name=None, trainable=True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.trainable = trainable


def _to_jax_array(data, dtype=None):
    nd = dtype_mod.convert_dtype(dtype) if dtype is not None else None
    if isinstance(data, Tensor):
        value = data._value
        return value.astype(nd) if nd is not None and value.dtype != nd else value
    if isinstance(data, (jax.Array,)):
        return data.astype(nd) if nd is not None and data.dtype != nd else data
    if isinstance(data, np.ndarray):
        if nd is None and data.dtype == np.float64:
            nd = np.dtype(np.float64)  # preserve numpy dtypes exactly
        return jnp.asarray(data, dtype=nd)
    if isinstance(data, (bool, int, float, complex)):
        if nd is None:
            if isinstance(data, bool):
                nd = np.dtype(bool)
            elif isinstance(data, int):
                nd = np.dtype(np.int64)
            elif isinstance(data, float):
                nd = dtype_mod.convert_dtype(dtype_mod.get_default_dtype())
            else:
                nd = np.dtype(np.complex64)
        return jnp.asarray(data, dtype=nd)
    # lists/tuples and anything numpy understands
    arr = np.asarray(data)
    if nd is None and arr.dtype == np.float64:
        nd = dtype_mod.convert_dtype(dtype_mod.get_default_dtype())
    return jnp.asarray(arr, dtype=nd)


def _unwrap_index(idx):
    if isinstance(idx, Tensor):
        return idx._value
    if isinstance(idx, tuple):
        return tuple(_unwrap_index(i) for i in idx)
    if isinstance(idx, list):
        return jnp.asarray(np.asarray(idx))
    return idx


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """``paddle.to_tensor``."""
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)
