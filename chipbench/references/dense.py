"""Entries of a matrix product and the Gram test of a QR factor, written plainly.

Reference of the ``dense_n40960`` configuration (job ``matmul_resplit``) and of
the ``qr`` job: float32 at ``highest`` matmul precision, on the rows and
columns asked for only, so it fits beside the operands.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def product_block(a_rows, b_cols):
    """``a_rows @ b_cols`` in float32: the block of ``A @ B`` at those rows
    of ``A`` and columns of ``B``."""
    with jax.default_matmul_precision("highest"):
        return a_rows.astype(jnp.float32) @ b_cols.astype(jnp.float32)


@jax.jit
def gram_gap(a, r):
    """``||R'R - A'A|| / ||A'A||``: zero exactly when ``R`` is a QR factor
    of ``A`` up to the signs of its rows."""
    with jax.default_matmul_precision("highest"):
        gram = a.T @ a
        return jnp.linalg.norm(r.T @ r - gram) / jnp.linalg.norm(gram)


@jax.jit
def orthogonality_gap(q):
    """``||Q'Q - I||`` (Frobenius)."""
    with jax.default_matmul_precision("highest"):
        return jnp.linalg.norm(q.T @ q - jnp.eye(q.shape[1], dtype=q.dtype))
