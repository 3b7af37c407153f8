"""``mx.np`` — NumPy-semantics array namespace.

Reference surface: ``python/mxnet/numpy/multiarray.py`` (SURVEY.md §3.2
"ndarray module": "mx.np/mx.npx NumPy-compatible namespace with ndarray
subclass, dispatch protocol").  The reference mirrors ~200 NumPy operators
as ``_np_*`` ops with NumPy broadcasting/dtype rules.

TPU-native: ``jax.numpy`` *is* a NumPy-semantics tensor library, so this
namespace is a thin autograd-recording bridge: each function unwraps
``ndarray`` inputs, runs the ``jnp`` function through the op-registry
``invoke`` (so the tape sees it and ``backward`` flows), and rewraps as
``mx.np.ndarray`` (class propagation via ``_wrap_like``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError, numeric_types
from ..context import Context, current_context
from ..ndarray.ndarray import NDArray
from ..ops.registry import Op, invoke

newaxis = None
pi = onp.pi
e = onp.e
euler_gamma = onp.euler_gamma
inf = onp.inf
nan = onp.nan

# dtype aliases
float16 = onp.float16
float32 = onp.float32
float64 = onp.float64
bfloat16 = jnp.bfloat16
int8 = onp.int8
int16 = onp.int16
int32 = onp.int32
int64 = onp.int64
uint8 = onp.uint8
bool_ = onp.bool_
dtype = onp.dtype


class ndarray(NDArray):
    """NumPy-semantics array (reference ``mx.np.ndarray``).  Inherits the
    async-handle machinery from NDArray; operators and indexing already
    follow NumPy broadcasting in this framework."""

    def __repr__(self):
        try:
            return f"array({onp.asarray(self._data)!r:s})".replace(
                "array(array", "array(").rstrip(")") + ")"
        except Exception:
            return f"<np.ndarray tracer {self.shape}>"

    def as_nd_ndarray(self):
        out = NDArray(self._data, self._ctx)
        out._grad = self._grad
        out._grad_req = self._grad_req
        out._autograd_node = self._autograd_node
        out._autograd_idx = self._autograd_idx
        return out

    def as_np_ndarray(self):
        return self

    # NumPy semantics: comparisons return bool arrays (the nd namespace
    # returns float 0/1 like legacy MXNet)
    def __eq__(self, o):
        return _run("equal", jnp.equal, [self, o])

    def __ne__(self, o):
        return _run("not_equal", jnp.not_equal, [self, o])

    def __lt__(self, o):
        return _run("less", jnp.less, [self, o])

    def __le__(self, o):
        return _run("less_equal", jnp.less_equal, [self, o])

    def __gt__(self, o):
        return _run("greater", jnp.greater, [self, o])

    def __ge__(self, o):
        return _run("greater_equal", jnp.greater_equal, [self, o])

    def __hash__(self):
        return id(self)

    # numpy-style reductions/methods not on the base class
    def std(self, axis=None, ddof=0, keepdims=False):
        return std(self, axis=axis, ddof=ddof, keepdims=keepdims)

    def var(self, axis=None, ddof=0, keepdims=False):
        return var(self, axis=axis, ddof=ddof, keepdims=keepdims)

    def cumsum(self, axis=None):
        return cumsum(self, axis=axis)

    def copy(self):
        return ndarray(jnp.asarray(self._data), self._ctx)

    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        return reshape(self, shape)

    def flatten(self):
        return reshape(self, (-1,))

    def ravel(self):
        return reshape(self, (-1,))

    def item(self, *args):
        return self.asnumpy().item(*args)

    def tolist(self):
        return self.asnumpy().tolist()

    def astype(self, dtype, copy=True):
        return _run1("astype", lambda x: x.astype(jnp.dtype(dtype)), self)

    def mean(self, axis=None, keepdims=False):
        return mean(self, axis=axis, keepdims=keepdims)

    def sum(self, axis=None, keepdims=False):
        return sum(self, axis=axis, keepdims=keepdims)  # noqa: A001

    def dot(self, b):
        return dot(self, b)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return transpose(self, axes if axes else None)

    def squeeze(self, axis=None):
        return squeeze(self, axis)

    @property
    def T(self):
        return transpose(self, None)


# --------------------------------------------------------------------------- #
# bridge machinery
# --------------------------------------------------------------------------- #

def _coerce_arr(x):
    if isinstance(x, NDArray):
        return x
    if isinstance(x, (onp.ndarray, list, tuple)) or isinstance(
            x, numeric_types) or isinstance(x, (bool, onp.generic)):
        return ndarray(jnp.asarray(x))
    return x


def _run(name, fn, arrays, static=None):
    """invoke() with np-class outputs.  Every legacy NDArray arg is promoted
    to the np subclass first — invoke's ``_wrap_like`` keys the output class
    off the first NDArray arg, so a leading legacy array must not win."""
    arrays = [_coerce_arr(a) for a in arrays]
    arrays = [a.as_np_ndarray()
              if isinstance(a, NDArray) and not isinstance(a, ndarray) else a
              for a in arrays]
    return invoke(Op(name=f"_np_{name}", fn=fn), arrays, static or {})


def _run1(name, fn, a):
    return _run(name, fn, [a])


def _make_unary(name, jfn):
    def wrapper(x, out=None, **kwargs):
        r = _run(name, jfn, [x])
        return _into(out, r)
    wrapper.__name__ = name
    return wrapper


def _make_binary(name, jfn):
    def wrapper(x1, x2, out=None, **kwargs):
        r = _run(name, jfn, [x1, x2])
        return _into(out, r)
    wrapper.__name__ = name
    return wrapper


def _into(out, r):
    if out is not None:
        out._rebind(r._data, r._autograd_node, r._autograd_idx)
        return out
    return r


# --------------------------------------------------------------------------- #
# creation
# --------------------------------------------------------------------------- #

def array(object, dtype=None, ctx=None):  # noqa: A002
    if isinstance(object, NDArray):
        data = object._data
    else:
        data = object
        if dtype is None:
            try:
                if onp.asarray(object).dtype == onp.float64:
                    dtype = onp.float32
            except Exception:
                pass
    arr = jnp.asarray(data, dtype=dtype)
    if ctx is not None:
        arr = jax.device_put(arr, ctx.jax_device())
    return ndarray(arr, ctx)


def asarray(a, dtype=None):
    return a if isinstance(a, ndarray) and dtype is None else array(a, dtype)


def zeros(shape, dtype=float32, order="C", ctx=None):
    return array(jnp.zeros(_shp(shape), jnp.dtype(dtype or "float32")),
                 ctx=ctx)


def ones(shape, dtype=float32, order="C", ctx=None):
    return array(jnp.ones(_shp(shape), jnp.dtype(dtype or "float32")),
                 ctx=ctx)


def full(shape, fill_value, dtype=None, order="C", ctx=None):
    return array(jnp.full(_shp(shape), fill_value,
                          jnp.dtype(dtype) if dtype else None), ctx=ctx)


def empty(shape, dtype=float32, order="C", ctx=None):
    return zeros(shape, dtype, order, ctx)


def zeros_like(a, dtype=None):
    return _run1("zeros_like", lambda x: jnp.zeros_like(
        x, jnp.dtype(dtype) if dtype else None), a)


def ones_like(a, dtype=None):
    return _run1("ones_like", lambda x: jnp.ones_like(
        x, jnp.dtype(dtype) if dtype else None), a)


def full_like(a, fill_value, dtype=None):
    return _run1("full_like", lambda x: jnp.full_like(
        x, fill_value, jnp.dtype(dtype) if dtype else None), a)


def arange(start, stop=None, step=1, dtype=None, ctx=None):
    return array(jnp.arange(start, stop, step,
                            jnp.dtype(dtype) if dtype else None), ctx=ctx)


def linspace(start, stop, num=50, endpoint=True, retstep=False, dtype=None,
             axis=0, ctx=None):
    r = jnp.linspace(start, stop, num, endpoint=endpoint, retstep=retstep,
                     dtype=jnp.dtype(dtype) if dtype else None, axis=axis)
    if retstep:
        return array(r[0], ctx=ctx), float(r[1])
    return array(r, ctx=ctx)


def logspace(start, stop, num=50, endpoint=True, base=10.0, dtype=None,
             ctx=None):
    return array(jnp.logspace(start, stop, num, endpoint=endpoint, base=base,
                              dtype=jnp.dtype(dtype) if dtype else None),
                 ctx=ctx)


def eye(N, M=None, k=0, dtype=float32, ctx=None):
    return array(jnp.eye(N, M, k, jnp.dtype(dtype or "float32")), ctx=ctx)


def identity(n, dtype=float32, ctx=None):
    return eye(n, dtype=dtype, ctx=ctx)


def meshgrid(*xi, indexing="xy"):
    arrs = [x._data if isinstance(x, NDArray) else jnp.asarray(x) for x in xi]
    return [ndarray(r) for r in jnp.meshgrid(*arrs, indexing=indexing)]


def tril(m, k=0):
    return _run1("tril", lambda x: jnp.tril(x, k), m)


def triu(m, k=0):
    return _run1("triu", lambda x: jnp.triu(x, k), m)


def _shp(shape):
    return (shape,) if isinstance(shape, int) else tuple(shape)


# --------------------------------------------------------------------------- #
# unary ufuncs
# --------------------------------------------------------------------------- #

_UNARY = {
    "negative": jnp.negative, "positive": jnp.positive, "abs": jnp.abs,
    "absolute": jnp.abs, "fabs": jnp.abs, "sign": jnp.sign,
    "exp": jnp.exp, "expm1": jnp.expm1, "exp2": jnp.exp2,
    "log": jnp.log, "log2": jnp.log2, "log10": jnp.log10,
    "log1p": jnp.log1p,
    "sqrt": jnp.sqrt, "cbrt": jnp.cbrt, "square": jnp.square,
    "reciprocal": jnp.reciprocal,
    "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
    "arcsin": jnp.arcsin, "arccos": jnp.arccos, "arctan": jnp.arctan,
    "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
    "arcsinh": jnp.arcsinh, "arccosh": jnp.arccosh, "arctanh": jnp.arctanh,
    "degrees": jnp.degrees, "radians": jnp.radians,
    "rint": jnp.rint, "fix": jnp.trunc, "floor": jnp.floor,
    "ceil": jnp.ceil, "trunc": jnp.trunc, "round": jnp.round,
    "around": jnp.round,
    "logical_not": jnp.logical_not, "invert": jnp.invert,
    "isnan": jnp.isnan, "isinf": jnp.isinf, "isfinite": jnp.isfinite,
    "isposinf": jnp.isposinf, "isneginf": jnp.isneginf,
    "conj": jnp.conj, "real": jnp.real, "imag": jnp.imag,
    "angle": jnp.angle,
    "sinc": jnp.sinc, "i0": jnp.i0,
    "nan_to_num": jnp.nan_to_num,
}

_BINARY = {
    "add": jnp.add, "subtract": jnp.subtract, "multiply": jnp.multiply,
    "divide": jnp.divide, "true_divide": jnp.true_divide,
    "floor_divide": jnp.floor_divide, "mod": jnp.mod,
    "remainder": jnp.remainder, "fmod": jnp.fmod,
    "power": jnp.power, "float_power": jnp.float_power,
    "maximum": jnp.maximum, "minimum": jnp.minimum,
    "fmax": jnp.fmax, "fmin": jnp.fmin,
    "hypot": jnp.hypot, "arctan2": jnp.arctan2,
    "logaddexp": jnp.logaddexp, "logaddexp2": jnp.logaddexp2,
    "copysign": jnp.copysign, "nextafter": jnp.nextafter,
    "equal": jnp.equal, "not_equal": jnp.not_equal,
    "less": jnp.less, "less_equal": jnp.less_equal,
    "greater": jnp.greater, "greater_equal": jnp.greater_equal,
    "logical_and": jnp.logical_and, "logical_or": jnp.logical_or,
    "logical_xor": jnp.logical_xor,
    "bitwise_and": jnp.bitwise_and, "bitwise_or": jnp.bitwise_or,
    "bitwise_xor": jnp.bitwise_xor,
    "left_shift": jnp.left_shift, "right_shift": jnp.right_shift,
    "gcd": jnp.gcd, "lcm": jnp.lcm,
    "ldexp": jnp.ldexp,
}

for _n, _f in _UNARY.items():
    globals()[_n] = _make_unary(_n, _f)
for _n, _f in _BINARY.items():
    globals()[_n] = _make_binary(_n, _f)


# --------------------------------------------------------------------------- #
# reductions
# --------------------------------------------------------------------------- #

def _axis_reduce(name, jfn):
    def wrapper(a, axis=None, dtype=None, out=None, keepdims=False, **kw):
        def impl(x):
            r = jfn(x, axis=_ax(axis), keepdims=keepdims, **kw)
            return r.astype(jnp.dtype(dtype)) if dtype else r
        return _into(out, _run(name, impl, [a]))
    wrapper.__name__ = name
    return wrapper


def _ax(axis):
    if isinstance(axis, list):
        return tuple(axis)
    return axis


sum = _axis_reduce("sum", jnp.sum)  # noqa: A001
prod = _axis_reduce("prod", jnp.prod)
mean = _axis_reduce("mean", jnp.mean)
nansum = _axis_reduce("nansum", jnp.nansum)
nanprod = _axis_reduce("nanprod", jnp.nanprod)
nanmean = _axis_reduce("nanmean", jnp.nanmean)


def _minmax(name, jfn):
    def wrapper(a, axis=None, out=None, keepdims=False):
        return _into(out, _run(name, lambda x: jfn(
            x, axis=_ax(axis), keepdims=keepdims), [a]))
    wrapper.__name__ = name
    return wrapper


max = _minmax("max", jnp.max)  # noqa: A001
min = _minmax("min", jnp.min)  # noqa: A001
amax = max
amin = min
nanmax = _minmax("nanmax", jnp.nanmax)
nanmin = _minmax("nanmin", jnp.nanmin)
ptp = _minmax("ptp", jnp.ptp)


def std(a, axis=None, dtype=None, out=None, ddof=0, keepdims=False):
    return _into(out, _run("std", lambda x: jnp.std(
        x, axis=_ax(axis), ddof=ddof, keepdims=keepdims), [a]))


def var(a, axis=None, dtype=None, out=None, ddof=0, keepdims=False):
    return _into(out, _run("var", lambda x: jnp.var(
        x, axis=_ax(axis), ddof=ddof, keepdims=keepdims), [a]))


def argmax(a, axis=None, out=None):
    return _into(out, _run("argmax", lambda x: jnp.argmax(x, axis=axis), [a]))


def argmin(a, axis=None, out=None):
    return _into(out, _run("argmin", lambda x: jnp.argmin(x, axis=axis), [a]))


def cumsum(a, axis=None, dtype=None, out=None):
    return _into(out, _run("cumsum", lambda x: jnp.cumsum(
        x, axis=axis, dtype=jnp.dtype(dtype) if dtype else None), [a]))


def cumprod(a, axis=None, dtype=None):
    return _run("cumprod", lambda x: jnp.cumprod(
        x, axis=axis, dtype=jnp.dtype(dtype) if dtype else None), [a])


def median(a, axis=None, out=None, keepdims=False):
    return _into(out, _run("median", lambda x: jnp.median(
        x, axis=_ax(axis), keepdims=keepdims), [a]))


def quantile(a, q, axis=None, keepdims=False):
    return _run("quantile", lambda x, qq: jnp.quantile(
        x, qq, axis=_ax(axis), keepdims=keepdims), [a, q])


def percentile(a, q, axis=None, keepdims=False):
    return _run("percentile", lambda x, qq: jnp.percentile(
        x, qq, axis=_ax(axis), keepdims=keepdims), [a, q])


def average(a, axis=None, weights=None, returned=False):
    if weights is None:
        return mean(a, axis=axis)
    r = _run("average", lambda x, w: jnp.average(x, _ax(axis), w),
             [a, weights])
    if returned:
        sw = sum(asarray(weights), axis=axis)
        return r, sw
    return r


def all(a, axis=None, out=None, keepdims=False):  # noqa: A001
    return _into(out, _run("all", lambda x: jnp.all(
        x, axis=_ax(axis), keepdims=keepdims), [a]))


def any(a, axis=None, out=None, keepdims=False):  # noqa: A001
    return _into(out, _run("any", lambda x: jnp.any(
        x, axis=_ax(axis), keepdims=keepdims), [a]))


def count_nonzero(a, axis=None):
    return _run("count_nonzero",
                lambda x: jnp.count_nonzero(x, axis=_ax(axis)), [a])


# --------------------------------------------------------------------------- #
# manipulation
# --------------------------------------------------------------------------- #

def reshape(a, newshape, order="C"):
    return _run("reshape", lambda x: jnp.reshape(x, _shp(newshape)), [a])


def transpose(a, axes=None):
    return _run("transpose", lambda x: jnp.transpose(
        x, tuple(axes) if axes is not None else None), [a])


def swapaxes(a, axis1, axis2):
    return _run("swapaxes", lambda x: jnp.swapaxes(x, axis1, axis2), [a])


def moveaxis(a, source, destination):
    return _run("moveaxis", lambda x: jnp.moveaxis(x, source, destination),
                [a])


def rollaxis(a, axis, start=0):
    return _run("rollaxis", lambda x: jnp.rollaxis(x, axis, start), [a])


def expand_dims(a, axis):
    return _run("expand_dims", lambda x: jnp.expand_dims(x, axis), [a])


def squeeze(a, axis=None):
    return _run("squeeze", lambda x: jnp.squeeze(
        x, _ax(axis) if axis is not None else None), [a])


def ravel(a, order="C"):
    return reshape(a, (-1,))


def atleast_1d(*arys):
    rs = [_run("atleast_1d", jnp.atleast_1d, [a]) for a in arys]
    return rs[0] if len(rs) == 1 else rs


def atleast_2d(*arys):
    rs = [_run("atleast_2d", jnp.atleast_2d, [a]) for a in arys]
    return rs[0] if len(rs) == 1 else rs


def atleast_3d(*arys):
    rs = [_run("atleast_3d", jnp.atleast_3d, [a]) for a in arys]
    return rs[0] if len(rs) == 1 else rs


def broadcast_to(a, shape):
    return _run("broadcast_to", lambda x: jnp.broadcast_to(x, _shp(shape)),
                [a])


def broadcast_arrays(*args):
    arrs = [_coerce_arr(a) for a in args]
    datas = [a._data for a in arrs]
    return [ndarray(r) for r in jnp.broadcast_arrays(*datas)]


def concatenate(seq, axis=0, out=None):
    return _into(out, _run("concatenate",
                           lambda *xs: jnp.concatenate(xs, axis=axis),
                           list(seq)))


def stack(arrays, axis=0, out=None):
    return _into(out, _run("stack", lambda *xs: jnp.stack(xs, axis=axis),
                           list(arrays)))


def vstack(tup):
    return _run("vstack", lambda *xs: jnp.vstack(xs), list(tup))


def hstack(tup):
    return _run("hstack", lambda *xs: jnp.hstack(xs), list(tup))


def dstack(tup):
    return _run("dstack", lambda *xs: jnp.dstack(xs), list(tup))


def column_stack(tup):
    return _run("column_stack", lambda *xs: jnp.column_stack(xs), list(tup))


def split(ary, indices_or_sections, axis=0):
    sec = indices_or_sections
    if isinstance(sec, NDArray):
        sec = tuple(int(v) for v in sec.asnumpy())
    elif isinstance(sec, (list, tuple)):
        sec = tuple(int(v) for v in sec)
    r = _run("split", lambda x: tuple(jnp.split(x, sec, axis=axis)), [ary])
    return r if isinstance(r, list) else [r]


def array_split(ary, indices_or_sections, axis=0):
    sec = indices_or_sections
    r = _run("array_split",
             lambda x: tuple(jnp.array_split(x, sec, axis=axis)), [ary])
    return r if isinstance(r, list) else [r]


def hsplit(ary, indices_or_sections):
    return split(ary, indices_or_sections, axis=1)


def vsplit(ary, indices_or_sections):
    return split(ary, indices_or_sections, axis=0)


def tile(a, reps):
    return _run("tile", lambda x: jnp.tile(x, reps), [a])


def repeat(a, repeats, axis=None):
    return _run("repeat", lambda x: jnp.repeat(x, repeats, axis=axis), [a])


def roll(a, shift, axis=None):
    return _run("roll", lambda x: jnp.roll(x, shift, axis=axis), [a])


def flip(m, axis=None):
    return _run("flip", lambda x: jnp.flip(x, axis=axis), [m])


def fliplr(m):
    return flip(m, 1)


def flipud(m):
    return flip(m, 0)


def rot90(m, k=1, axes=(0, 1)):
    return _run("rot90", lambda x: jnp.rot90(x, k, axes), [m])


def pad(array, pad_width, mode="constant", **kwargs):  # noqa: A002
    return _run("pad", lambda x: jnp.pad(x, pad_width, mode=mode, **kwargs),
                [array])


def delete(arr, obj, axis=None):
    # concretize indices so jnp.delete handles duplicates/slices correctly
    if isinstance(obj, NDArray):
        obj = onp.asarray(obj.asnumpy())
    elif isinstance(obj, (list, tuple)):
        obj = onp.asarray(obj)
    return _run("delete", lambda x: jnp.delete(x, obj, axis=axis), [arr])


def insert(arr, obj, values, axis=None):
    return _run("insert", lambda x, v: jnp.insert(x, obj, v, axis=axis),
                [arr, values])


def append(arr, values, axis=None):
    return _run("append", lambda x, v: jnp.append(x, v, axis=axis),
                [arr, values])


def where(condition, x=None, y=None):
    if x is None and y is None:
        cond = _coerce_arr(condition)
        rs = jnp.where(cond._data)
        return tuple(ndarray(r) for r in rs)
    return _run("where", lambda c, a, b: jnp.where(c, a, b),
                [condition, x, y])


def clip(a, a_min, a_max, out=None):
    return _into(out, _run("clip", lambda x: jnp.clip(x, a_min, a_max), [a]))


def diag(v, k=0):
    return _run("diag", lambda x: jnp.diag(x, k), [v])


def diagonal(a, offset=0, axis1=0, axis2=1):
    return _run("diagonal",
                lambda x: jnp.diagonal(x, offset, axis1, axis2), [a])


def trace(a, offset=0, axis1=0, axis2=1):
    return _run("trace", lambda x: jnp.trace(x, offset, axis1, axis2), [a])


def tril_indices(n, k=0, m=None):
    r, c = jnp.tril_indices(n, k, m)
    return ndarray(r), ndarray(c)


def indices(dimensions, dtype=int32):
    return ndarray(jnp.indices(tuple(dimensions), jnp.dtype(dtype)))


def unravel_index(indices, shape):  # noqa: A002
    arr = _coerce_arr(indices)
    rs = jnp.unravel_index(arr._data, _shp(shape))
    return tuple(ndarray(r) for r in rs)


def ravel_multi_index(multi_index, dims, mode="raise"):
    arrs = [_coerce_arr(a)._data for a in multi_index]
    return ndarray(jnp.ravel_multi_index(tuple(arrs), _shp(dims), mode=mode))


def take(a, indices, axis=None, mode="clip"):  # noqa: A002
    return _run("take", lambda x, i: jnp.take(
        x, i.astype(jnp.int32) if jnp.issubdtype(i.dtype, jnp.floating)
        else i, axis=axis, mode=mode), [a, indices])


def take_along_axis(arr, indices, axis):  # noqa: A002
    return _run("take_along_axis",
                lambda x, i: jnp.take_along_axis(x, i, axis), [arr, indices])


def searchsorted(a, v, side="left"):
    return _run("searchsorted",
                lambda x, y: jnp.searchsorted(x, y, side=side), [a, v])


def sort(a, axis=-1, kind=None, order=None):
    return _run("sort", lambda x: jnp.sort(x, axis=axis), [a])


def argsort(a, axis=-1, kind=None, order=None):
    return _run("argsort", lambda x: jnp.argsort(x, axis=axis), [a])


def unique(ar, return_index=False, return_inverse=False,
           return_counts=False, axis=None):
    arr = _coerce_arr(ar)
    rs = jnp.unique(arr._data, return_index=return_index,
                    return_inverse=return_inverse,
                    return_counts=return_counts, axis=axis)
    if isinstance(rs, tuple):
        return tuple(ndarray(r) for r in rs)
    return ndarray(rs)


def nonzero(a):
    arr = _coerce_arr(a)
    return tuple(ndarray(r) for r in jnp.nonzero(arr._data))


def flatnonzero(a):
    arr = _coerce_arr(a)
    return ndarray(jnp.flatnonzero(arr._data))


def argwhere(a):
    arr = _coerce_arr(a)
    return ndarray(jnp.argwhere(arr._data))


def extract(condition, arr):
    c = _coerce_arr(condition)
    a = _coerce_arr(arr)
    return ndarray(jnp.extract(c._data, a._data))


def copy(a):
    return _run("copy", jnp.copy, [a])


def may_share_memory(a, b, max_work=None):
    return False  # functional arrays never alias user-visibly


def shares_memory(a, b, max_work=None):
    return False


# --------------------------------------------------------------------------- #
# linear algebra (top-level)
# --------------------------------------------------------------------------- #

def dot(a, b, out=None):
    return _into(out, _run("dot", jnp.dot, [a, b]))


def matmul(a, b, out=None):
    return _into(out, _run("matmul", jnp.matmul, [a, b]))


def inner(a, b):
    return _run("inner", jnp.inner, [a, b])


def outer(a, b):
    return _run("outer", jnp.outer, [a, b])


def tensordot(a, b, axes=2):
    ax = axes
    if isinstance(ax, (list, tuple)):
        ax = tuple(tuple(x) if isinstance(x, (list, tuple)) else x
                   for x in ax)
    return _run("tensordot", lambda x, y: jnp.tensordot(x, y, ax), [a, b])


def einsum(subscripts, *operands, out=None, optimize=False):
    return _into(out, _run("einsum",
                           lambda *xs: jnp.einsum(subscripts, *xs),
                           list(operands)))


def kron(a, b):
    return _run("kron", jnp.kron, [a, b])


def cross(a, b, axis=-1):
    return _run("cross", lambda x, y: jnp.cross(x, y, axis=axis), [a, b])


def vdot(a, b):
    return _run("vdot", jnp.vdot, [a, b])


def interp(x, xp, fp, left=None, right=None):
    return _run("interp", lambda a, b, c: jnp.interp(a, b, c, left, right),
                [x, xp, fp])


def diff(a, n=1, axis=-1):
    return _run("diff", lambda x: jnp.diff(x, n, axis=axis), [a])


def ediff1d(ary):
    return _run("ediff1d", jnp.ediff1d, [ary])


def gradient(f, *varargs, axis=None):
    arr = _coerce_arr(f)
    rs = jnp.gradient(arr._data, *varargs, axis=axis)
    if isinstance(rs, list):
        return [ndarray(r) for r in rs]
    return ndarray(rs)


def convolve(a, v, mode="full"):
    return _run("convolve", lambda x, y: jnp.convolve(x, y, mode), [a, v])


def correlate(a, v, mode="valid"):
    return _run("correlate", lambda x, y: jnp.correlate(x, y, mode), [a, v])


def histogram(a, bins=10, range=None, weights=None):  # noqa: A002
    arr = _coerce_arr(a)
    h, edges = jnp.histogram(arr._data, bins=bins, range=range,
                             weights=None if weights is None
                             else _coerce_arr(weights)._data)
    return ndarray(h), ndarray(edges)


def bincount(x, weights=None, minlength=0):
    arr = _coerce_arr(x)
    return ndarray(jnp.bincount(
        arr._data, None if weights is None else _coerce_arr(weights)._data,
        minlength=minlength))


def digitize(x, bins, right=False):
    return _run("digitize", lambda a, b: jnp.digitize(a, b, right=right),
                [x, bins])


def isclose(a, b, rtol=1e-5, atol=1e-8, equal_nan=False):
    return _run("isclose", lambda x, y: jnp.isclose(
        x, y, rtol=rtol, atol=atol, equal_nan=equal_nan), [a, b])


def allclose(a, b, rtol=1e-5, atol=1e-8, equal_nan=False):
    return bool(isclose(a, b, rtol, atol, equal_nan).asnumpy().all())


def array_equal(a1, a2):
    x, y = _coerce_arr(a1), _coerce_arr(a2)
    if x.shape != y.shape:
        return False
    return bool(jnp.array_equal(x._data, y._data))


def result_type(*args):
    vals = [a._data if isinstance(a, NDArray) else a for a in args]
    return onp.dtype(jnp.result_type(*vals))


def can_cast(from_, to):
    return onp.can_cast(from_, to)


def shape(a):
    return _coerce_arr(a).shape


def ndim(a):
    return _coerce_arr(a).ndim


def size(a, axis=None):
    arr = _coerce_arr(a)
    return arr.size if axis is None else arr.shape[axis]


def expm1x(x):
    return expm1(x)  # noqa: F821


def deg2rad(x):
    return _run1("deg2rad", jnp.deg2rad, x)


def rad2deg(x):
    return _run1("rad2deg", jnp.rad2deg, x)


def signbit(x):
    return _run1("signbit", jnp.signbit, x)


def empty_like(prototype, dtype=None, order="C"):
    p = _coerce_arr(prototype)
    return ndarray(jnp.empty_like(p._data, dtype=dtype))


def diagflat(v, k=0):
    return _run1("diagflat", lambda x: jnp.diagflat(x, k), v)


def diag_indices(n, ndim=2):
    rs = jnp.diag_indices(n, ndim)
    return tuple(ndarray(r) for r in rs)


def triu_indices(n, k=0, m=None):
    r, c = jnp.triu_indices(n, k, m)
    return ndarray(r), ndarray(c)


def tri(N, M=None, k=0, dtype=float32):
    return ndarray(jnp.tri(N, M, k, dtype=jnp.dtype(dtype)))


def dsplit(ary, indices_or_sections):
    a = _coerce_arr(ary)
    return [ndarray(x) for x in jnp.dsplit(a._data, indices_or_sections)]


def row_stack(tup):
    return _run("row_stack", lambda *xs: jnp.vstack(xs), list(tup))


def nanargmax(a, axis=None):
    return _run("nanargmax", lambda x: jnp.nanargmax(x, axis=axis), [a])


def nanargmin(a, axis=None):
    return _run("nanargmin", lambda x: jnp.nanargmin(x, axis=axis), [a])


def nancumsum(a, axis=None, dtype=None):
    return _run("nancumsum",
                lambda x: jnp.nancumsum(x, axis=axis, dtype=dtype), [a])


def nancumprod(a, axis=None, dtype=None):
    return _run("nancumprod",
                lambda x: jnp.nancumprod(x, axis=axis, dtype=dtype), [a])


def nanstd(a, axis=None, ddof=0, keepdims=False):
    return _run("nanstd", lambda x: jnp.nanstd(x, axis=axis, ddof=ddof,
                                               keepdims=keepdims), [a])


def nanvar(a, axis=None, ddof=0, keepdims=False):
    return _run("nanvar", lambda x: jnp.nanvar(x, axis=axis, ddof=ddof,
                                               keepdims=keepdims), [a])


def nanpercentile(a, q, axis=None, keepdims=False):
    return _run("nanpercentile",
                lambda x: jnp.nanpercentile(x, q, axis=axis,
                                            keepdims=keepdims), [a])


def corrcoef(x, y=None, rowvar=True):
    arrs = [x] if y is None else [x, y]
    if y is None:
        return _run("corrcoef",
                    lambda a: jnp.corrcoef(a, rowvar=rowvar), arrs)
    return _run("corrcoef",
                lambda a, b: jnp.corrcoef(a, b, rowvar=rowvar), arrs)


def trapz(y, x=None, dx=1.0, axis=-1):
    # jnp.trapezoid in current jax; trapz removed upstream
    fn = getattr(jnp, "trapezoid", None) or getattr(jnp, "trapz")
    if x is None:
        return _run("trapz", lambda yy: fn(yy, dx=dx, axis=axis), [y])
    return _run("trapz", lambda yy, xx: fn(yy, x=xx, axis=axis), [y, x])


def put(a, ind, v, mode="clip"):
    """Out-of-place semantics on XLA: returns the updated array AND rebinds
    ``a``'s handle (mutable-looking surface, SURVEY.md §7 Arrays)."""
    arr = _coerce_arr(a)
    idx = _coerce_arr(ind)._data.astype(jnp.int32).reshape(-1)
    vals = jnp.broadcast_to(jnp.asarray(
        _coerce_arr(v)._data, arr._data.dtype).reshape(-1), idx.shape) \
        if onp.ndim(getattr(_coerce_arr(v), "_data", v)) <= 1 else \
        _coerce_arr(v)._data.reshape(-1)
    flat = arr._data.reshape(-1)
    if mode == "clip":
        idx = jnp.clip(idx, 0, flat.shape[0] - 1)
    elif mode == "wrap":
        idx = idx % flat.shape[0]
    new = flat.at[idx].set(vals).reshape(arr._data.shape)
    if isinstance(a, NDArray):
        a._rebind(new)
        return a
    return ndarray(new)


def resize(a, new_shape):
    arr = _coerce_arr(a)
    return ndarray(jnp.resize(arr._data, new_shape))


def bitwise_not(a):
    return _run("bitwise_not", jnp.bitwise_not, [a])


invert = bitwise_not


def polyval(p, x):
    return _run("polyval", jnp.polyval, [p, x])


def blackman(M, dtype=None):
    return ndarray(jnp.blackman(M).astype(jnp.dtype(dtype or "float32")))


def hamming(M, dtype=None):
    return ndarray(jnp.hamming(M).astype(jnp.dtype(dtype or "float32")))


def hanning(M, dtype=None):
    return ndarray(jnp.hanning(M).astype(jnp.dtype(dtype or "float32")))


def diag_indices_from(arr):
    a = _coerce_arr(arr)
    return tuple(ndarray(ix) for ix in jnp.diag_indices_from(a._data))


def share_memory(a, b):
    # jax arrays are immutable buffers; views never alias mutably
    return False


def may_share_memory(a, b):
    return False


# everything public defined in this module (functions, constants, dtypes)
__all__ = [_n for _n, _v in list(globals().items())
           if not _n.startswith("_")
           and _n not in ("jax", "jnp", "onp", "functools", "NDArray",
                          "Op", "invoke", "Context", "current_context",
                          "MXNetError", "numeric_types")]
