"""Minimal pure-JAX module system backing ``ht.nn``.

The reference's ``ht.nn`` is a passthrough to ``torch.nn`` (SURVEY §2.5);
the TPU-native equivalent exposes the same constructor names
(``ht.nn.Linear``, ``ht.nn.ReLU``, ``ht.nn.Sequential``, …) as lightweight
pure-functional modules: ``init(key) -> params`` (a pytree) and
``apply(params, x) -> y``.  Arbitrary flax modules duck-type the same
contract and work everywhere these are accepted.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "Module",
    "Linear",
    "Identity",
    "ReLU",
    "Tanh",
    "Sigmoid",
    "GELU",
    "Softmax",
    "LogSoftmax",
    "Dropout",
    "Dropout1d",
    "Dropout2d",
    "Dropout3d",
    "Flatten",
    "Unflatten",
    "Sequential",
    "Conv2d",
    "MaxPool2d",
    "AvgPool2d",
    "AdaptiveAvgPool2d",
    "BatchNorm1d",
    "BatchNorm2d",
    "BatchNorm3d",
    "LayerNorm",
    "RMSNorm",
    "SwiGLU",
    "GroupNorm",
    "Embedding",
    "Residual",
]


def _pair(v) -> Tuple[int, int]:
    """torch-style int-or-tuple normalization for 2-D spatial args."""
    return v if isinstance(v, tuple) else (v, v)


def _concrete_int(x):
    """``int(x)`` when ``x`` is concrete, else ``None`` — the probe jit-safe
    eager validations share (traced values raise the public Tracer*Error
    family; ``jax.core.Tracer`` isinstance checks are a deprecated path).
    Used by the decode-step capacity guard and EmbeddingBag's offsets
    check."""
    import jax

    try:
        return int(x)
    except (jax.errors.TracerIntegerConversionError,
            jax.errors.ConcretizationTypeError, TypeError):
        return None


def _module_accepts_train(module) -> bool:
    """Whether ``module.apply`` should be called with ``train=``/``key=``.

    heat modules always do.  Duck-typed modules qualify only via an EXPLICIT
    ``train`` parameter in their apply signature — a bare ``**kwargs`` does
    not (flax's apply has ``**kwargs`` it would forward to ``__call__``,
    crashing models whose ``__call__`` lacks ``train``)."""
    import inspect

    if isinstance(module, Module):
        return True
    try:
        sig = inspect.signature(module.apply)
        return "train" in sig.parameters
    except (TypeError, ValueError, AttributeError):
        return False


class Module:
    """Base: stateless apply + parameter init."""

    def init(self, key) -> Any:
        return ()

    def apply(self, params, x, *, train: bool = False, key=None):
        raise NotImplementedError

    def __call__(self, params, x, **kw):
        return self.apply(params, x, **kw)


class Linear(Module):
    """Dense layer y = x Wᵀ + b (torch parameter convention: W is (out, in))."""

    def __init__(self, in_features: int, out_features: int, bias: bool = True):
        self.in_features = in_features
        self.out_features = out_features
        self.bias = bias

    def init(self, key):
        wk, bk = jax.random.split(key)
        bound = 1.0 / jnp.sqrt(self.in_features)
        w = jax.random.uniform(wk, (self.out_features, self.in_features), minval=-bound, maxval=bound)
        if self.bias:
            b = jax.random.uniform(bk, (self.out_features,), minval=-bound, maxval=bound)
            return {"weight": w, "bias": b}
        return {"weight": w}

    def apply(self, params, x, **kw):
        y = x @ params["weight"].T
        if self.bias:
            y = y + params["bias"]
        return y


class _Activation(Module):
    fn: Callable = None

    def apply(self, params, x, **kw):
        return type(self).fn(x)


class ReLU(_Activation):
    fn = staticmethod(jax.nn.relu)


class Tanh(_Activation):
    fn = staticmethod(jnp.tanh)


class Sigmoid(_Activation):
    fn = staticmethod(jax.nn.sigmoid)


class GELU(Module):
    """torch parity: default is the EXACT erf form (``approximate='none'``);
    ``jax.nn.gelu``'s default is the tanh approximation, so the flag maps
    explicitly."""

    def __init__(self, approximate: str = "none"):
        if approximate not in ("none", "tanh"):
            raise ValueError(f"approximate must be 'none' or 'tanh', got {approximate!r}")
        self.approximate = approximate

    def apply(self, params, x, **kw):
        return jax.nn.gelu(x, approximate=self.approximate == "tanh")


class Softmax(Module):
    def __init__(self, dim: int = -1):
        self.dim = dim

    def apply(self, params, x, **kw):
        return jax.nn.softmax(x, axis=self.dim)


class LogSoftmax(Module):
    def __init__(self, dim: int = -1):
        self.dim = dim

    def apply(self, params, x, **kw):
        return jax.nn.log_softmax(x, axis=self.dim)


class Dropout(Module):
    def __init__(self, p: float = 0.5):
        self.p = p

    def apply(self, params, x, *, train: bool = False, key=None):
        if not train or self.p == 0.0:
            return x
        if key is None:
            raise ValueError("Dropout in train mode requires a PRNG key")
        keep = 1.0 - self.p
        mask = jax.random.bernoulli(key, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0)


class _ChannelDropout(Module):
    """Zero whole channels (torch ``Dropout1d/2d/3d``): the mask covers
    (N, C) and broadcasts over the trailing ``spatial`` dims."""

    spatial: int = 0

    def __init__(self, p: float = 0.5):
        self.p = p

    def apply(self, params, x, *, train: bool = False, key=None):
        if not train or self.p == 0.0:
            return x
        if key is None:
            raise ValueError("channel dropout in train mode requires a PRNG key")
        if x.ndim != self.spatial + 2:
            raise ValueError(
                f"expected a {self.spatial + 2}-D (N, C, ...) input, got {x.ndim}-D"
            )
        keep = 1.0 - self.p
        mask = jax.random.bernoulli(key, keep, x.shape[:2] + (1,) * self.spatial)
        return jnp.where(mask, x / keep, 0.0)


class Dropout1d(_ChannelDropout):
    spatial = 1


class Dropout2d(_ChannelDropout):
    spatial = 2


class Dropout3d(_ChannelDropout):
    spatial = 3


class Flatten(Module):
    def apply(self, params, x, **kw):
        return x.reshape(x.shape[0], -1)


class Unflatten(Module):
    """Inverse of Flatten: expand ``dim`` into ``unflattened_size`` (torch
    argument convention)."""

    def __init__(self, dim: int, unflattened_size):
        self.dim = dim
        self.unflattened_size = tuple(unflattened_size)

    def apply(self, params, x, **kw):
        d = self.dim % x.ndim
        return x.reshape(x.shape[:d] + self.unflattened_size + x.shape[d + 1:])


class Conv2d(Module):
    """2-D convolution, NCHW layout (torch convention)."""

    def __init__(self, in_channels: int, out_channels: int, kernel_size: int,
                 stride: int = 1, padding: int = 0, bias: bool = True):
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.kernel_size = _pair(kernel_size)
        self.stride = _pair(stride)
        self.padding = _pair(padding)
        self.bias = bias

    def init(self, key):
        wk, bk = jax.random.split(key)
        fan_in = self.in_channels * self.kernel_size[0] * self.kernel_size[1]
        bound = 1.0 / jnp.sqrt(fan_in)
        w = jax.random.uniform(
            wk, (self.out_channels, self.in_channels) + self.kernel_size, minval=-bound, maxval=bound
        )
        if self.bias:
            return {"weight": w, "bias": jax.random.uniform(bk, (self.out_channels,), minval=-bound, maxval=bound)}
        return {"weight": w}

    def apply(self, params, x, **kw):
        y = jax.lax.conv_general_dilated(
            x, params["weight"], window_strides=self.stride,
            padding=[(p, p) for p in self.padding],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )
        if self.bias:
            y = y + params["bias"][None, :, None, None]
        return y


class _Pool2d(Module):
    def __init__(self, kernel_size: int, stride: Optional[int] = None):
        self.kernel_size = _pair(kernel_size)
        self.stride = _pair(stride if stride is not None else kernel_size)


def _max_pool_indices(x, kernel, stride, rank):
    """Max pooling that ALSO returns torch-convention indices: each output
    position's flat index into its channel's spatial plane (what
    ``MaxUnpoolNd`` consumes).  The flat index is derived ARITHMETICALLY
    from the within-window argmax (window start = out_pos·stride, plus the
    row-major in-window offset), all in integer math — exact at any plane
    size, and no second patches pass over an index plane."""
    from math import prod

    dn = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
          3: ("NCDHW", "OIDHW", "NCDHW")}[rank]
    spatial = x.shape[2:]
    p = jax.lax.conv_general_dilated_patches(
        x, kernel, stride, [(0, 0)] * rank, dimension_numbers=dn
    )
    px = p.reshape(p.shape[0], x.shape[1], prod(kernel), *p.shape[2:])
    am = jnp.argmax(px, axis=2)  # (N, C, *out_spatial), row-major in-window
    vals = jnp.take_along_axis(px, am[:, :, None], axis=2)[:, :, 0]

    # decompose am row-major over the kernel dims (the patches layout)
    offs, rem = [], am
    for kd in reversed(kernel):
        offs.append(rem % kd)
        rem = rem // kd
    offs = offs[::-1]
    idx = jnp.zeros_like(am)
    plane = 1
    out_spatial = am.shape[2:]
    for d in reversed(range(rank)):
        pos = jnp.arange(out_spatial[d]).reshape(
            (1, 1) + (1,) * d + (-1,) + (1,) * (rank - 1 - d)
        )
        idx = idx + (pos * stride[d] + offs[d]) * plane
        plane *= spatial[d]
    return vals, idx.astype(jnp.int32)


class MaxPool2d(_Pool2d):
    def __init__(self, kernel_size: int, stride: Optional[int] = None,
                 return_indices: bool = False):
        super().__init__(kernel_size, stride)
        self.return_indices = return_indices

    def apply(self, params, x, **kw):
        if self.return_indices:
            return _max_pool_indices(x, self.kernel_size, self.stride, 2)
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max,
            window_dimensions=(1, 1) + self.kernel_size,
            window_strides=(1, 1) + self.stride,
            padding="VALID",
        )


class AvgPool2d(_Pool2d):
    def apply(self, params, x, **kw):
        summed = jax.lax.reduce_window(
            x, 0.0, jax.lax.add,
            window_dimensions=(1, 1) + self.kernel_size,
            window_strides=(1, 1) + self.stride,
            padding="VALID",
        )
        return summed / (self.kernel_size[0] * self.kernel_size[1])


class _AdaptivePool(Module):
    """Adaptive pooling over the trailing ``spatial`` dims, divisible case
    (torch semantics where input size is a multiple of output size — the
    pooled windows are then uniform).  ``output_size`` accepts an int, a
    tuple/list, and torch's ``None`` entries (keep that dim)."""

    spatial: int = 2
    op = staticmethod(jnp.mean)

    def __init__(self, output_size=1):
        n = self.spatial
        if isinstance(output_size, (tuple, list)):
            self.output_size = tuple(output_size)
        else:
            self.output_size = (output_size,) * n
        if len(self.output_size) != n:
            raise ValueError(f"output_size must have {n} entries")

    def apply(self, params, x, **kw):
        n = self.spatial
        spatial = x.shape[-n:]
        outs = tuple(
            s if o is None else int(o)  # torch: None keeps the input extent
            for s, o in zip(spatial, self.output_size)
        )
        shape = list(x.shape[:-n])
        axes = []
        for s, o in zip(spatial, outs):
            if s % o:
                raise ValueError(
                    f"{type(self).__name__}: input {s} not divisible by output {o}"
                )
            shape += [o, s // o]
            axes.append(len(shape) - 1)
        return type(self).op(x.reshape(shape), axis=tuple(axes))


class AdaptiveAvgPool2d(_AdaptivePool):
    spatial = 2


class Identity(Module):
    def apply(self, params, x, **kw):
        return x


class _BatchNorm(Module):
    """Batch normalization with torch parameter names.

    Functional-JAX contract: training normalizes with batch statistics;
    evaluation uses the stored running stats.  Because ``apply`` is pure, the
    running-stat EMA is exposed as :meth:`update_stats` (returns new params)
    for callers that track it; train steps that never call it still match the
    reference's training-mode math exactly.

    ``running_mean``/``running_var`` are buffers, not parameters: the
    framework's optimizers mask every ``running_*`` leaf from updates and
    weight decay (see ``optim.dp_optimizer._nontrainable_mask``).
    """

    def __init__(self, num_features: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True):
        self.num_features = num_features
        self.eps = eps
        self.momentum = momentum
        self.affine = affine

    def _axes(self, ndim: int) -> Tuple[int, ...]:
        # all dims except channel (dim 1): (N,C)->(0,), (N,C,L)->(0,2), (N,C,H,W)->(0,2,3)
        return (0,) + tuple(range(2, ndim))

    def init(self, key):
        c = self.num_features
        p = {"running_mean": jnp.zeros(c), "running_var": jnp.ones(c)}
        if self.affine:
            p["weight"] = jnp.ones(c)
            p["bias"] = jnp.zeros(c)
        return p

    def _bcast(self, v, ndim):
        shape = [1] * ndim
        shape[1] = self.num_features
        return v.reshape(shape)

    def apply(self, params, x, *, train: bool = False, **kw):
        if train:
            mean = jnp.mean(x, axis=self._axes(x.ndim))
            var = jnp.var(x, axis=self._axes(x.ndim))
        else:
            mean, var = params["running_mean"], params["running_var"]
        y = (x - self._bcast(mean, x.ndim)) / jnp.sqrt(self._bcast(var, x.ndim) + self.eps)
        if self.affine:
            y = y * self._bcast(params["weight"], x.ndim) + self._bcast(params["bias"], x.ndim)
        return y

    def update_stats(self, params, x):
        """EMA update of running stats from a batch (returns new params).

        Uses the unbiased (ddof=1) variance, matching torch's running-stat
        convention (train-mode normalization stays biased, also like torch).
        """
        m = self.momentum
        axes = self._axes(x.ndim)
        mean = jnp.mean(x, axis=axes)
        var = jnp.var(x, axis=axes, ddof=1)
        new = dict(params)
        new["running_mean"] = (1 - m) * params["running_mean"] + m * mean
        new["running_var"] = (1 - m) * params["running_var"] + m * var
        return new


class BatchNorm1d(_BatchNorm):
    """BatchNorm over (N, C) or (N, C, L) input."""

    def _axes(self, ndim: int) -> Tuple[int, ...]:
        if ndim not in (2, 3):
            raise ValueError(f"BatchNorm1d expects 2-D or 3-D input, got {ndim}-D")
        return super()._axes(ndim)


class BatchNorm2d(_BatchNorm):
    """BatchNorm over (N, C, H, W) input."""

    def _axes(self, ndim: int) -> Tuple[int, ...]:
        if ndim != 4:
            raise ValueError(f"BatchNorm2d expects 4-D input, got {ndim}-D")
        return super()._axes(ndim)


class BatchNorm3d(_BatchNorm):
    """BatchNorm over (N, C, D, H, W) input."""

    def _axes(self, ndim: int) -> Tuple[int, ...]:
        if ndim != 5:
            raise ValueError(f"BatchNorm3d expects 5-D input, got {ndim}-D")
        return super()._axes(ndim)


class LayerNorm(Module):
    """Layer normalization over the trailing ``normalized_shape`` dims."""

    def __init__(self, normalized_shape, eps: float = 1e-5, elementwise_affine: bool = True):
        self.normalized_shape = (
            (normalized_shape,) if isinstance(normalized_shape, int) else tuple(normalized_shape)
        )
        self.eps = eps
        self.affine = elementwise_affine

    def init(self, key):
        if self.affine:
            return {"weight": jnp.ones(self.normalized_shape), "bias": jnp.zeros(self.normalized_shape)}
        return {}

    def apply(self, params, x, **kw):
        axes = tuple(range(x.ndim - len(self.normalized_shape), x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        y = (x - mean) / jnp.sqrt(var + self.eps)
        if self.affine:
            y = y * params["weight"] + params["bias"]
        return y


class RMSNorm(Module):
    """Root-mean-square normalization over the trailing ``normalized_shape``
    dims (torch ``nn.RMSNorm``; the LLM-standard LayerNorm variant — no
    mean subtraction, no bias).  ``eps=None`` follows torch: the input
    dtype's machine epsilon."""

    def __init__(self, normalized_shape, eps: float | None = None,
                 elementwise_affine: bool = True):
        self.normalized_shape = (
            (normalized_shape,) if isinstance(normalized_shape, int) else tuple(normalized_shape)
        )
        self.eps = eps
        self.affine = elementwise_affine

    def init(self, key):
        if self.affine:
            return {"weight": jnp.ones(self.normalized_shape)}
        return {}

    def apply(self, params, x, **kw):
        axes = tuple(range(x.ndim - len(self.normalized_shape), x.ndim))
        eps = jnp.finfo(x.dtype).eps if self.eps is None else self.eps
        return rms_normalize(x, params["weight"] if self.affine else None, eps, axes)


def rms_normalize(x, weight=None, eps: float = 1e-5, axes=(-1,)):
    """``x / sqrt(mean(x^2) + eps) * weight`` over ``axes``.  The statistics
    and the scaling are float32 whatever ``x``'s dtype (a mean of squares in
    bfloat16 loses the small terms); the result has ``x``'s dtype."""
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=axes, keepdims=True) + eps)
    if weight is not None:
        y = y * weight
    return y.astype(x.dtype)


class SwiGLU(Module):
    """Gated-linear feed-forward ``w2 (silu(w1 x) * w3 x)`` without biases
    (Shazeer 2020; the FFN of most current language models).  A weight
    matrix may be kept in a wider dtype than ``x``: it is brought to ``x``'s
    dtype where it is used."""

    def __init__(self, embed_dim: int, hidden_dim: int):
        self.embed_dim = embed_dim
        self.hidden_dim = hidden_dim
        self.w1 = Linear(embed_dim, hidden_dim, bias=False)
        self.w3 = Linear(embed_dim, hidden_dim, bias=False)
        self.w2 = Linear(hidden_dim, embed_dim, bias=False)

    def init(self, key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"w1": self.w1.init(k1), "w3": self.w3.init(k3), "w2": self.w2.init(k2)}

    def apply(self, params, x, **kw):
        w1, w3, w2 = (params[n]["weight"].astype(x.dtype) for n in ("w1", "w3", "w2"))
        return (jax.nn.silu(x @ w1.T) * (x @ w3.T)) @ w2.T


class GroupNorm(Module):
    def __init__(self, num_groups: int, num_channels: int, eps: float = 1e-5, affine: bool = True):
        if num_channels % num_groups:
            raise ValueError("num_channels must be divisible by num_groups")
        self.num_groups = num_groups
        self.num_channels = num_channels
        self.eps = eps
        self.affine = affine

    def init(self, key):
        if self.affine:
            return {"weight": jnp.ones(self.num_channels), "bias": jnp.zeros(self.num_channels)}
        return {}

    def apply(self, params, x, **kw):
        n, c = x.shape[:2]
        g = self.num_groups
        xg = x.reshape((n, g, c // g) + x.shape[2:])
        axes = tuple(range(2, xg.ndim))
        mean = jnp.mean(xg, axis=axes, keepdims=True)
        var = jnp.var(xg, axis=axes, keepdims=True)
        y = ((xg - mean) / jnp.sqrt(var + self.eps)).reshape(x.shape)
        if self.affine:
            shape = [1] * x.ndim
            shape[1] = c
            y = y * params["weight"].reshape(shape) + params["bias"].reshape(shape)
        return y


class Embedding(Module):
    def __init__(self, num_embeddings: int, embedding_dim: int):
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim

    def init(self, key):
        return {"weight": jax.random.normal(key, (self.num_embeddings, self.embedding_dim))}

    def apply(self, params, x, **kw):
        return params["weight"][x]


class Residual(Module):
    """y = body(x) + shortcut(x) — the ResNet block skeleton."""

    def __init__(self, body: Module, shortcut: Optional[Module] = None):
        self.body = body
        self.shortcut = shortcut if shortcut is not None else Identity()

    def init(self, key):
        bk, sk = jax.random.split(key)
        return {"body": self.body.init(bk), "shortcut": self.shortcut.init(sk)}

    def apply(self, params, x, *, train: bool = False, key=None):
        bk = sk = None
        if key is not None:
            bk, sk = jax.random.split(key)
        return self.body.apply(params["body"], x, train=train, key=bk) + self.shortcut.apply(
            params["shortcut"], x, train=train, key=sk
        )


class Sequential(Module):
    """Chain of modules; params is a list of per-layer pytrees."""

    def __init__(self, *layers: Module):
        self.layers = list(layers)

    def init(self, key):
        keys = jax.random.split(key, max(len(self.layers), 1))
        return [l.init(k) for l, k in zip(self.layers, keys)]

    def apply(self, params, x, *, train: bool = False, key=None):
        for i, (l, p) in enumerate(zip(self.layers, params)):
            if isinstance(l, Dropout) and train and l.p > 0.0 and key is None:
                raise ValueError(
                    "Sequential contains Dropout: apply(train=True) requires a "
                    "PRNG key (use make_train_step(..., with_rng=True))"
                )
            if key is not None:
                key, sub = jax.random.split(key)
                x = l.apply(p, x, train=train, key=sub)
            else:
                x = l.apply(p, x, train=train)
        return x
