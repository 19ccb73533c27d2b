"""Signal processing (reference: ``heat/core/signal.py``).

1-D ``convolve`` with full/same/valid modes.  Distributed signals take the
reference's halo path (``DNDarray.get_halo`` + local ``torch.conv1d``,
SURVEY §5.7): each shard exchanges ``m-1`` boundary elements with its ring
neighbors (``parallel.halo.halo_exchange`` → ``lax.ppermute``) and runs a
LOCAL valid-mode XLA conv on ``[halo_prev | block | halo_next]`` — no
global gather.  A distributed kernel is gathered first (kernels are small;
same as the reference's ``v`` broadcast).  Replicated signals use one
global XLA convolution.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import types
from ._cache import comm_cached
from .dndarray import DNDarray

__all__ = ["convolve", "convolve2d"]

# diagnostics: tests assert the halo path actually executes
_HALO_CONV_RUNS = 0


def _conv1d_full(a: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Full correlation-free convolution via XLA conv (MXU-eligible)."""
    n, m = a.shape[0], v.shape[0]
    # conv_general_dilated computes correlation; flip the kernel for convolution
    lhs = a.reshape(1, 1, n)
    rhs = v[::-1].reshape(1, 1, m)
    out = jax.lax.conv_general_dilated(
        lhs, rhs, window_strides=(1,), padding=[(m - 1, m - 1)]
    )
    return out.reshape(-1)


def _conv1d_valid(x: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    lhs = x.reshape(1, 1, -1)
    rhs = v[::-1].reshape(1, 1, -1)
    return jax.lax.conv_general_dilated(
        lhs, rhs, window_strides=(1,), padding=[(0, 0)]
    ).reshape(-1)


def _halo_body(a: DNDarray, jv: jnp.ndarray, offset: int) -> jnp.ndarray:
    """Per-shard rows ``G[lo+offset : lo+offset+c]`` of the signal's FULL
    convolution, via halo exchange — the reference's convolve mechanism.

    Each shard extends its block with ``m-1`` neighbor elements on both sides
    (zeros at the global edges = conv zero-padding; the PHYSICAL padded array
    is used, whose trailing pad zeros are exactly conv semantics) and runs a
    local valid conv: ``valid(ext)[i] == G[lo + i]``.  Returns the padded
    physical result aligned with the signal's shards.
    """
    global _HALO_CONV_RUNS
    comm = a.comm
    # pads are DEAD data, not guaranteed zero (elementwise fast paths leave
    # f(0) garbage there) — mask to the conv zero-padding this path relies on
    phys = a._masked(0).astype(jv.dtype)
    body = _halo_conv_program(comm, int(jv.shape[0]), offset)(phys, jv)
    _HALO_CONV_RUNS += 1
    return body


@comm_cached
def _halo_conv_program(comm, m: int, offset: int):
    """Jitted + comm-cached halo-convolve pipeline (the TSQR recompile
    lesson applied to the op surface: convolve is called eagerly, so a
    fresh shard_map per call would recompile every time).  The kernel rides
    as a replicated argument, not a closure constant, so one program serves
    every kernel of length ``m``."""
    from ..parallel.halo import halo_exchange

    h = m - 1

    def shard_fn(blk, jv):
        prev, nxt = halo_exchange(blk, h, comm.axis, comm.size, 0)
        ext = jnp.concatenate([prev, blk, nxt], axis=0)
        val = _conv1d_valid(ext, jv)  # c + m - 1 rows: G[lo : lo + c + m - 1]
        return jax.lax.dynamic_slice_in_dim(val, offset, blk.shape[0])

    return jax.jit(comm.shard_map(
        shard_fn, in_splits=((1, 0), (1, None)), out_splits=(1, 0)
    ))


def convolve(a: DNDarray, v: DNDarray, mode: str = "full", stride: int = 1) -> DNDarray:
    """Discrete 1-D convolution of ``a`` with kernel ``v`` (numpy modes)."""
    from . import factories

    if not isinstance(a, DNDarray):
        a = factories.array(a)
    if not isinstance(v, DNDarray):
        v = factories.array(v)
    if a.ndim != 1 or v.ndim != 1:
        raise ValueError("convolve requires 1-D inputs")
    if mode not in ("full", "same", "valid"):
        raise ValueError(f"Unsupported mode {mode!r}")
    if stride != 1:
        raise NotImplementedError("stride != 1 not supported (reference parity)")
    n, m = a.shape[0], v.shape[0]
    signal = a  # output metadata follows the SIGNAL even if operands swap
    if n < m:
        a, v = v, a
        n, m = m, n
    dt = types.promote_types(a.dtype, v.dtype)
    if types.heat_type_is_exact(dt):
        work_dt = types.float32
    else:
        work_dt = dt
    # a distributed kernel is gathered — kernels are small and every shard
    # needs all of it (reference: Bcast of v)
    jv = (v.resplit(None) if v.split is not None else v)._jarray.astype(work_dt.jax_dtype())

    comm = a.comm
    c_blk = comm.padded_extent(n) // comm.size if comm.size else n
    use_halo = (
        a.split == 0
        and comm.is_distributed()
        and m - 1 <= c_blk  # halo must fit in one neighbor block
    )

    if use_halo:
        split = signal.split
        if mode == "same":
            body = _halo_body(a, jv, (m - 1) // 2)  # G[lo+(m-1)//2 : …+c] per shard
            res_d = DNDarray(
                body, (n,), types.canonical_heat_type(body.dtype), 0,
                signal.device, comm, True,
            )
        else:
            body = _halo_body(a, jv, 0)  # G[lo : lo+c] per shard → G[0:n]
            body_d = DNDarray(
                body, (n,), types.canonical_heat_type(body.dtype), 0,
                signal.device, comm, True,
            )
            if mode == "valid":
                res_d = body_d[m - 1 : n]
            else:  # full: append the global tail G[n : n+m-1] (last m-1 rows)
                if m > 1:
                    t = a[n - (m - 1) :]._jarray.astype(jv.dtype)
                    tail = _conv1d_full(t, jv)[m - 1 : 2 * (m - 1)]
                    res = jnp.concatenate([body_d._jarray, tail])
                else:
                    res = body_d._jarray
                res_d = DNDarray(
                    res, tuple(res.shape), types.canonical_heat_type(res.dtype), 0,
                    signal.device, comm, True,
                )
        if types.heat_type_is_exact(dt):
            res_d = DNDarray(
                jnp.round(res_d._parray).astype(dt.jax_dtype()), res_d.shape,
                dt, res_d.split, res_d.device, res_d.comm, True,
            )
        if res_d.split != split:
            res_d.resplit_(split)  # result split follows the SIGNAL operand
        return res_d

    ja = a._jarray.astype(work_dt.jax_dtype())
    full = _conv1d_full(ja, jv)
    if mode == "full":
        res = full
    elif mode == "same":
        lo = (m - 1) // 2
        res = full[lo : lo + n]
    else:  # valid
        res = full[m - 1 : m - 1 + n - m + 1]
    if types.heat_type_is_exact(dt):
        res = jnp.round(res).astype(dt.jax_dtype())
    split = signal.split
    res = signal.comm.shard(res, split)
    return DNDarray(
        res, tuple(res.shape), types.canonical_heat_type(res.dtype), split,
        signal.device, signal.comm, True,
    )


def convolve2d(a: DNDarray, v: DNDarray, mode: str = "full") -> DNDarray:
    """2-D convolution (extension beyond the reference's 1-D surface)."""
    from . import factories

    if not isinstance(a, DNDarray):
        a = factories.array(a)
    if not isinstance(v, DNDarray):
        v = factories.array(v)
    if a.ndim != 2 or v.ndim != 2:
        raise ValueError("convolve2d requires 2-D inputs")
    n0, n1 = a.shape
    m0, m1 = v.shape
    lhs = a._jarray.astype(jnp.float32).reshape(1, 1, n0, n1)
    rhs = v._jarray.astype(jnp.float32)[::-1, ::-1].reshape(1, 1, m0, m1)
    if mode == "full":
        pad = [(m0 - 1, m0 - 1), (m1 - 1, m1 - 1)]
    elif mode == "same":
        pad = [((m0 - 1) // 2, m0 // 2), ((m1 - 1) // 2, m1 // 2)]
    elif mode == "valid":
        pad = [(0, 0), (0, 0)]
    else:
        raise ValueError(f"Unsupported mode {mode!r}")
    out = jax.lax.conv_general_dilated(lhs, rhs, window_strides=(1, 1), padding=pad)
    res = out.reshape(out.shape[2], out.shape[3])
    res = a.comm.shard(res, a.split)
    return DNDarray(
        res, tuple(res.shape), types.canonical_heat_type(res.dtype), a.split, a.device, a.comm, True
    )


def correlate(a: DNDarray, v: DNDarray, mode: str = "valid") -> DNDarray:
    """Cross-correlation of 1-D sequences (numpy ``correlate`` semantics:
    ``a ⋆ v = a * conj(reverse(v))``) — rides the distributed ``convolve``
    halo path for split signals."""
    from . import factories, manipulations

    if not isinstance(v, DNDarray):
        v = factories.array(v)
    flipped = manipulations.flip(v, 0)
    if jnp.issubdtype(flipped.dtype.jax_dtype(), jnp.complexfloating):
        from .complex_math import conjugate

        flipped = conjugate(flipped)
    return convolve(a, flipped, mode=mode)


__all__ += ["correlate"]
