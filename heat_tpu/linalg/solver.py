"""Iterative/triangular solvers (reference: ``heat/core/linalg/solver.py``).

``cg`` and ``lanczos`` are written purely against the array API — all
communication is implicit in the distributed matmuls/dots, exactly like the
reference (SURVEY §2.3).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import types
from ..core._cache import comm_cached
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in

__all__ = ["cg", "lanczos", "solve_triangular"]


def _wrap(jarr, split, proto):
    if split is not None and split >= jarr.ndim:
        split = None
    jarr = proto.comm.shard(jarr, split)
    return DNDarray(
        jarr, tuple(jarr.shape), types.canonical_heat_type(jarr.dtype), split, proto.device, proto.comm, True
    )


def cg(A: DNDarray, b: DNDarray, x0: Optional[DNDarray] = None, out: Optional[DNDarray] = None,
       maxit: Optional[int] = None, tol: float = 1e-8) -> DNDarray:
    """Conjugate gradients for SPD ``A`` — jit-compiled while_loop on device.

    The reference iterates in Python with implicit MPI in each matvec; here
    the whole Krylov loop is ONE compiled XLA program (matvec collectives
    included), eliminating per-iteration dispatch latency.
    """
    sanitize_in(A)
    sanitize_in(b)
    n = b.shape[0]
    maxit = maxit if maxit is not None else n
    jA, jb = A._jarray, b._jarray
    jx0 = x0._jarray if x0 is not None else jnp.zeros_like(jb)
    x = _cg_impl(jA, jb, jx0, jnp.asarray(maxit, jnp.int32), jnp.asarray(tol, jnp.float32))
    res = _wrap(x, b.split, b)
    if out is not None:
        out._jarray = res._jarray
        return out
    return res


@jax.jit
def _cg_impl(jA, jb, jx0, maxit, tol):
    # module-level jit: repeat solves at the same shapes reuse ONE compiled
    # program (an eager while_loop re-traces per call — the round-4b
    # recompile lesson applied to the Krylov loop).  maxit/tol ride as
    # DYNAMIC operands — while_loop's cond handles traced bounds, so a
    # tolerance sweep reuses the same executable instead of recompiling
    def cond(state):
        _, _, _, rs, it = state
        return jnp.logical_and(jnp.sqrt(rs) > tol, it < maxit)

    def body(state):
        x, r, p, rs, it = state
        Ap = jA @ p
        alpha = rs / jnp.vdot(p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        rs_new = jnp.vdot(r, r).real
        p = r + (rs_new / rs) * p
        return x, r, p, rs_new, it + 1

    r0 = jb - jA @ jx0
    state = (jx0, r0, r0, jnp.vdot(r0, r0).real, jnp.asarray(0))
    x, *_ = jax.lax.while_loop(cond, body, state)
    return x


def lanczos(
    A: DNDarray,
    m: int,
    v0: Optional[DNDarray] = None,
    V_out: Optional[DNDarray] = None,
    T_out: Optional[DNDarray] = None,
) -> Tuple[DNDarray, DNDarray]:
    """Lanczos tridiagonalization: returns (V: n×m basis, T: m×m tridiagonal).

    Matches the reference's full-reorthogonalization variant for stability.
    """
    sanitize_in(A)
    n = A.shape[0]
    jA = A._jarray
    if v0 is None:
        from ..core import random as ht_random

        v = ht_random.randn(n, dtype=types.float32)._jarray
        v = v / jnp.linalg.norm(v)
    else:
        v = v0._jarray
    V, T = _lanczos_impl(jA, v, m)
    Vd = _wrap(V, 0 if A.split == 0 else None, A)
    Td = _wrap(T, None, A)
    if V_out is not None:
        V_out._jarray = Vd._jarray
        T_out._jarray = Td._jarray
        return V_out, T_out
    return Vd, Td


@functools.partial(jax.jit, static_argnames=("m",))
def _lanczos_impl(jA, v, m: int):
    """ONE compiled program for the whole recursion (``lax.fori_loop``): the
    old per-iteration eager loop paid ~100 host dispatches and re-traced every
    call.  Full reorthogonalization per step, as the reference does."""
    n = jA.shape[0]
    V = jnp.zeros((n, m), dtype=jA.dtype).at[:, 0].set(v)
    alphas = jnp.zeros(m, dtype=jA.dtype)
    betas = jnp.zeros(m, dtype=jA.dtype)

    w = jA @ v
    a0 = jnp.vdot(w, v).real.astype(jA.dtype)
    w = w - a0 * v
    alphas = alphas.at[0].set(a0)

    def body(i, carry):
        V, alphas, betas, w = carry
        beta = jnp.linalg.norm(w)
        vi = jnp.where(beta > 1e-12, w / jnp.maximum(beta, 1e-30), jnp.zeros_like(w))
        # full reorthogonalization (reference does the same for stability)
        vi = vi - V @ (V.T @ vi)
        nrm = jnp.linalg.norm(vi)
        vi = jnp.where(nrm > 1e-12, vi / jnp.maximum(nrm, 1e-30), vi)
        V = V.at[:, i].set(vi)
        w = jA @ vi
        ai = jnp.vdot(w, vi).real.astype(jA.dtype)
        w = w - ai * vi - beta * V[:, i - 1]
        return V, alphas.at[i].set(ai), betas.at[i].set(beta), w

    V, alphas, betas, _ = jax.lax.fori_loop(1, m, body, (V, alphas, betas, w))
    T = jnp.diag(alphas) + jnp.diag(betas[1:], 1) + jnp.diag(betas[1:], -1)
    return V, T


def solve_triangular(A: DNDarray, b: DNDarray, lower: bool = False, blocked=None) -> DNDarray:
    """Triangular solve with the reference's blocked-substitution algorithm
    for distributed ``A`` (reference: ``heat/core/linalg/solver.py``
    ``solve_triangular`` — blocked over ``tiling.SquareDiagTiles`` with tile
    Bcast; here each tile op is a GLOBAL-array slice partitioned by GSPMD, so
    the per-step "broadcast of the diagonal tile" lowers to XLA collectives
    instead of explicit Bcast).

    ``blocked=None`` auto-selects: the tiled substitution when ``A`` is
    distributed along a split axis (its off-diagonal updates are large GEMMs —
    MXU-friendly — while XLA's native triangular solve would gather the
    operand), the native fused solve otherwise.
    """
    sanitize_in(A)
    sanitize_in(b)
    m, n = A.shape
    if m != n:
        raise ValueError(f"A must be square, got {A.shape}")
    if blocked is None:
        blocked = A.split is not None and A.comm.is_distributed() and n >= 2 * A.comm.size
    if not blocked:
        res = jax.scipy.linalg.solve_triangular(A._jarray, b._jarray, lower=lower)
        return _wrap(res, b.split, b)

    from ..core.tiling import SquareDiagTiles

    tiles = SquareDiagTiles(A, tiles_per_proc=2)
    ends = tuple(int(e) for e in tiles.row_indices[1:]) + (n,)
    prog = _blocked_tri_program(A.comm, ends, lower)
    jb = b._jarray if b.ndim == 2 else b._jarray[:, None]
    x = prog(A._jarray, jb)
    if b.ndim == 1:
        x = x[:, 0]
    return _wrap(x, b.split, b)


@comm_cached
def _blocked_tri_program(comm, row_ends: tuple, lower: bool):
    """One compiled XLA program per tile layout: the whole blocked
    substitution (tile boundaries are static) traces once, so repeated solves
    pay zero per-tile dispatch — unlike the reference, whose Python loop
    re-issues tile Bcasts every call."""
    starts = (0,) + row_ends[:-1]
    nt = len(row_ends)

    def fn(jA, jb):
        x = jnp.zeros_like(jb)
        order = range(nt) if lower else range(nt - 1, -1, -1)
        for i in order:
            rs = slice(starts[i], row_ends[i])
            acc = jb[rs]
            # subtract the solved tiles' contribution: one GEMM per solved
            # block-column (the reference's Bcast-accumulate, GSPMD-partitioned)
            solved = range(i) if lower else range(nt - 1, i, -1)
            for j in solved:
                cs = slice(starts[j], row_ends[j])
                acc = acc - jA[rs, cs] @ x[cs]
            xi = jax.scipy.linalg.solve_triangular(jA[rs, rs], acc, lower=lower)
            x = x.at[rs].set(xi)
        return x

    return jax.jit(fn)
