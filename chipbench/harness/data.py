"""Inputs made on the device from the seed, one jitted program each.

The peak-memory counter cannot be reset, so a generator whose temporaries
outgrew its result would be what ``peak_hbm_gib`` measures.  Both generators
therefore fill their result in row blocks inside a ``fori_loop`` (the update
is in place), each chip its own rows under ``shard_map``; a block is keyed by
its global index, so the values do not depend on the number of chips.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _blocks(rows: int, block_rows: int) -> tuple:
    """The largest block of at most ``block_rows`` that divides ``rows``."""
    block = next(b for b in range(min(block_rows, rows), 0, -1) if rows % b == 0)
    return block, rows // block


def _blobs_program(mesh, axis, n, d, k, spread, dtype, block_rows):
    block, n_blocks = _blocks(n // mesh.shape[axis], block_rows)

    def local(key):
        k_centers, k_rows = jax.random.split(key)
        centers = spread * jax.random.normal(k_centers, (k, d), jnp.float32)
        first = lax.axis_index(axis) * n_blocks

        def fill(i, xt):
            k_label, k_noise = jax.random.split(jax.random.fold_in(k_rows, first + i))
            label = jax.random.randint(k_label, (block,), 0, k)
            onehot = (jnp.arange(k)[:, None] == label[None, :]).astype(jnp.float32)
            cols = jnp.matmul(centers.T, onehot, precision=lax.Precision.HIGHEST)
            cols = cols + jax.random.normal(k_noise, (d, block), jnp.float32)
            return lax.dynamic_update_slice(xt, cols.astype(dtype), (0, i * block))

        # built feature-major: a (d, rows) buffer has no narrow minor
        # dimension to pad, whatever layout the backend prefers for (rows, d)
        xt = lax.fori_loop(0, n_blocks, fill, jnp.zeros((d, n_blocks * block), dtype))
        return xt.T, centers

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=P(), out_specs=(P(axis, None), P()), check_vma=False))


def blobs(mesh, axis: str, seed: int, n: int, d: int, k: int, spread: float,
          dtype=jnp.float32, block_rows: int = 1 << 20) -> tuple:
    """``n`` rows drawn from ``k`` unit Gaussians whose centres are
    ``spread`` apart per coordinate: ``(X (n, d) split along rows, centres)``."""
    if n % mesh.shape[axis]:
        raise ValueError(f"{n} rows do not divide over {mesh.shape[axis]} chips")
    program = _blobs_program(mesh, axis, n, d, k, float(spread), jnp.dtype(dtype), block_rows)
    return program(jax.random.key(seed))


def blob_draws(centers, seed: int):
    """One more row from each blob: its centre plus unit noise.  As initial
    centres they give every blob exactly one, so no boundary runs through a
    blob and the comparison with the float32 reference is well-conditioned."""
    noise = jax.random.normal(jax.random.fold_in(jax.random.key(seed), 1), centers.shape)
    return centers + noise


def _dense_program(mesh, axis, rows, cols, scale, dtype, block_rows):
    block, n_blocks = _blocks(rows // mesh.shape[axis], block_rows)

    def local(key):
        first = lax.axis_index(axis) * n_blocks

        def fill(i, x):
            tile = scale * jax.random.normal(
                jax.random.fold_in(key, first + i), (block, cols), jnp.float32)
            return lax.dynamic_update_slice(x, tile.astype(dtype), (i * block, 0))

        return lax.fori_loop(0, n_blocks, fill, jnp.zeros((n_blocks * block, cols), dtype))

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=P(), out_specs=P(axis, None), check_vma=False))


def dense(mesh, axis: str, seed: int, rows: int, cols: int, scale: float = 1.0,
          dtype=jnp.bfloat16, block_rows: int = 1024):
    """A ``(rows, cols)`` matrix of ``scale * N(0, 1)``, split along rows."""
    if rows % mesh.shape[axis]:
        raise ValueError(f"{rows} rows do not divide over {mesh.shape[axis]} chips")
    program = _dense_program(mesh, axis, rows, cols, float(scale), jnp.dtype(dtype), block_rows)
    return program(jax.random.key(seed))
