"""FFT namespace (reference: ``heat/fft/fft.py``).

The reference's rule: transforms along non-split dims are local; a transform
hitting the split axis resplits to move it local, transforms, and resplits
back ("transpose method", SURVEY §2.2).  Round 4 makes that explicit here
too: when the transform hits the split axis and another (divisible) axis
can carry the shard, the call resplits → transforms locally → resplits back
(two all_to_alls, O(n/p) per-device memory); otherwise the global form runs
and GSPMD derives the data movement.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..core import types
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in

__all__ = [
    "fft", "fft2", "fftn", "fftfreq", "fftshift",
    "hfft", "hfft2", "hfftn",
    "ifft", "ifft2", "ifftn", "ifftshift", "ihfft", "ihfft2", "ihfftn",
    "irfft", "irfft2", "irfftn",
    "rfft", "rfft2", "rfftfreq", "rfftn",
]


def _wrap(jarr, split, proto: DNDarray) -> DNDarray:
    if split is not None and split >= jarr.ndim:
        split = None
    jarr = proto.comm.shard(jarr, split)
    return DNDarray(
        jarr, tuple(jarr.shape), types.canonical_heat_type(jarr.dtype), split, proto.device, proto.comm, True
    )


# eager routing counters (tests assert the transpose method engages)
fft_paths = {"transpose": 0, "direct": 0}


def _transpose_axis(x: DNDarray, busy_axes) -> Optional[int]:
    """A reshard target for the explicit transpose method — the shared
    ``manipulations.reshard_axis_for`` rule, plus FFT's extra gate: the
    transform must actually hit the split axis."""
    if x.split not in busy_axes:
        return None
    from ..core.manipulations import reshard_axis_for

    return reshard_axis_for(x, busy_axes)


def _fft_op(op_name: str, x: DNDarray, n=None, axis=-1, norm=None) -> DNDarray:
    sanitize_in(x)
    op = getattr(jnp.fft, op_name)
    axis_n = axis % max(x.ndim, 1)
    t = _transpose_axis(x, {axis_n})
    if t is not None:
        # the reference's transpose method made explicit: resplit so the
        # transform axis is local, transform (other axes stay sharded),
        # resplit back — two all_to_alls, never a gather
        from ..core.manipulations import resplit

        fft_paths["transpose"] += 1
        xr = resplit(x, t)
        res = op(xr._jarray, n=n, axis=axis, norm=norm)
        return resplit(_wrap(res, t, x), x.split)
    fft_paths["direct"] += 1
    res = op(x._jarray, n=n, axis=axis, norm=norm)
    return _wrap(res, x.split, x)


def _fftn_op(op_name: str, x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    sanitize_in(x)
    op = getattr(jnp.fft, op_name)
    if axes is not None:
        busy = {a % x.ndim for a in (axes if isinstance(axes, (tuple, list)) else (axes,))}
    elif s is not None:
        # numpy rule: with s given and axes omitted, only the LAST len(s)
        # axes are transformed — the earlier axes are valid reshard targets
        busy = set(range(x.ndim - len(s), x.ndim))
    else:
        busy = set(range(x.ndim))
    t = _transpose_axis(x, busy)
    if t is not None:
        from ..core.manipulations import resplit

        fft_paths["transpose"] += 1
        xr = resplit(x, t)
        res = op(xr._jarray, s=s, axes=axes, norm=norm)
        return resplit(_wrap(res, t, x), x.split)
    fft_paths["direct"] += 1
    res = op(x._jarray, s=s, axes=axes, norm=norm)
    return _wrap(res, x.split, x)


def fft(x, n=None, axis=-1, norm=None) -> DNDarray:
    """1-D discrete Fourier transform along ``axis``."""
    return _fft_op("fft", x, n=n, axis=axis, norm=norm)


def ifft(x, n=None, axis=-1, norm=None) -> DNDarray:
    return _fft_op("ifft", x, n=n, axis=axis, norm=norm)


def rfft(x, n=None, axis=-1, norm=None) -> DNDarray:
    return _fft_op("rfft", x, n=n, axis=axis, norm=norm)


def irfft(x, n=None, axis=-1, norm=None) -> DNDarray:
    return _fft_op("irfft", x, n=n, axis=axis, norm=norm)


def hfft(x, n=None, axis=-1, norm=None) -> DNDarray:
    return _fft_op("hfft", x, n=n, axis=axis, norm=norm)


def ihfft(x, n=None, axis=-1, norm=None) -> DNDarray:
    return _fft_op("ihfft", x, n=n, axis=axis, norm=norm)


def fft2(x, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    return _fftn_op("fft2", x, s=s, axes=axes, norm=norm)


def ifft2(x, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    return _fftn_op("ifft2", x, s=s, axes=axes, norm=norm)


def rfft2(x, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    return _fftn_op("rfft2", x, s=s, axes=axes, norm=norm)


def irfft2(x, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    return _fftn_op("irfft2", x, s=s, axes=axes, norm=norm)


def _hfftn_op(x: DNDarray, s, axes, norm, inverse: bool) -> DNDarray:
    """Hermitian n-D transforms composed per axis (reference inherits them
    whole from ``torch.fft.hfftn``/``ihfftn``; ``jnp.fft`` has only the 1-D
    forms).  The transforms are separable, so the one-sided Hermitian axis
    — the LAST of ``axes``, the torch convention — gets ``hfft``/``ihfft``
    and every other axis gets a plain ``fft``/``ifft``; each 1-D transform
    carries its own norm factor, so any ``norm`` composes exactly.  For
    ``ihfftn`` the real input must hit ``ihfft`` first; for ``hfftn`` the
    full-size axes are transformed first so the last axis stays one-sided
    until the end.  Split handling matches ``_fftn_op``: resplit off a busy
    split axis when a divisible axis can carry the shard, else direct."""
    sanitize_in(x)
    nd = max(x.ndim, 1)
    if axes is None:
        axes = tuple(range(nd)) if s is None else tuple(range(nd - len(s), nd))
    elif not isinstance(axes, (tuple, list)):
        axes = (axes,)
    axes = tuple(a % nd for a in axes)
    if len(set(axes)) != len(axes):
        # also catches hfft2 defaults (-2, -1) aliasing on a 1-D input —
        # torch raises there too; a silent double transform would be wrong
        raise ValueError(f"axes must be unique, got {axes} on a {nd}-D array")
    if s is not None and len(s) != len(axes):
        raise ValueError(f"s and axes must have the same length, got {len(s)} != {len(axes)}")
    ss = list(s) if s is not None else [None] * len(axes)

    def run(arr):
        if inverse:
            arr = jnp.fft.ihfft(arr, n=ss[-1], axis=axes[-1], norm=norm)
            for a, n in zip(axes[:-1], ss[:-1]):
                arr = jnp.fft.ifft(arr, n=n, axis=a, norm=norm)
        else:
            for a, n in zip(axes[:-1], ss[:-1]):
                arr = jnp.fft.fft(arr, n=n, axis=a, norm=norm)
            arr = jnp.fft.hfft(arr, n=ss[-1], axis=axes[-1], norm=norm)
        return arr

    t = _transpose_axis(x, set(axes))
    if t is not None:
        from ..core.manipulations import resplit

        fft_paths["transpose"] += 1
        xr = resplit(x, t)
        return resplit(_wrap(run(xr._jarray), t, x), x.split)
    fft_paths["direct"] += 1
    return _wrap(run(x._jarray), x.split, x)


def hfft2(x, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    return _hfftn_op(x, s, axes, norm, inverse=False)


def ihfft2(x, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    return _hfftn_op(x, s, axes, norm, inverse=True)


def fftn(x, s=None, axes=None, norm=None) -> DNDarray:
    return _fftn_op("fftn", x, s=s, axes=axes, norm=norm)


def ifftn(x, s=None, axes=None, norm=None) -> DNDarray:
    return _fftn_op("ifftn", x, s=s, axes=axes, norm=norm)


def rfftn(x, s=None, axes=None, norm=None) -> DNDarray:
    return _fftn_op("rfftn", x, s=s, axes=axes, norm=norm)


def irfftn(x, s=None, axes=None, norm=None) -> DNDarray:
    return _fftn_op("irfftn", x, s=s, axes=axes, norm=norm)


def hfftn(x, s=None, axes=None, norm=None) -> DNDarray:
    """n-D FFT of a Hermitian-symmetric (one-sided last axis) signal — real
    output.  torch.fft.hfftn semantics (the reference's source for it);
    composed per axis, see :func:`_hfftn_op`."""
    return _hfftn_op(x, s, axes, norm, inverse=False)


def ihfftn(x, s=None, axes=None, norm=None) -> DNDarray:
    """Inverse of :func:`hfftn`: real input, one-sided complex output."""
    return _hfftn_op(x, s, axes, norm, inverse=True)


def fftfreq(n: int, d: float = 1.0, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    from ..core import factories

    res = jnp.fft.fftfreq(n, d=d)
    return factories.array(res, dtype=dtype, split=split, device=device, comm=comm)


def rfftfreq(n: int, d: float = 1.0, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    from ..core import factories

    res = jnp.fft.rfftfreq(n, d=d)
    return factories.array(res, dtype=dtype, split=split, device=device, comm=comm)


def fftshift(x, axes=None) -> DNDarray:
    sanitize_in(x)
    return _wrap(jnp.fft.fftshift(x._jarray, axes=axes), x.split, x)


def ifftshift(x, axes=None) -> DNDarray:
    sanitize_in(x)
    return _wrap(jnp.fft.ifftshift(x._jarray, axes=axes), x.split, x)
