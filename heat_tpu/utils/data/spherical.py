"""Synthetic clustered data (reference: ``heat/utils/data/spherical.py``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import factories, types
from ...core.dndarray import DNDarray

__all__ = ["create_spherical_dataset", "create_clusters"]


def create_spherical_dataset(
    num_samples_cluster: int,
    radius: float = 1.0,
    offset: float = 4.0,
    dtype=types.float32,
    random_state: int = 1,
) -> DNDarray:
    """Four Gaussian blobs on a diagonal (the reference's KMeans test set)."""
    key = jax.random.key(random_state)
    keys = jax.random.split(key, 4)
    blobs = []
    for i, k in enumerate(keys):
        center = (i - 1.5) * offset
        pts = jax.random.normal(k, (num_samples_cluster, 3)) * radius + center
        blobs.append(pts)
    data = jnp.concatenate(blobs, axis=0).astype(types.canonical_heat_type(dtype).jax_dtype())
    return factories.array(data, split=0)


def create_clusters(
    n_samples: int,
    n_features: int,
    n_clusters: int,
    cluster_mean,
    cluster_std=1.0,
    cluster_weight=None,
    device=None,
    random_state: int = 42,
) -> DNDarray:
    """Gaussian blobs with the given per-cluster means/stds (reference API)."""
    key = jax.random.key(random_state)
    means = jnp.asarray(cluster_mean, dtype=jnp.float32)
    if means.shape[0] != n_clusters:
        raise ValueError("cluster_mean must have n_clusters rows")
    if cluster_weight is None:
        counts = [n_samples // n_clusters] * n_clusters
        counts[-1] += n_samples - sum(counts)
    else:
        counts = [int(w * n_samples) for w in cluster_weight]
        counts[-1] += n_samples - sum(counts)
    stds = jnp.broadcast_to(jnp.asarray(cluster_std, dtype=jnp.float32), (n_clusters,))
    parts = []
    for i in range(n_clusters):
        key, sub = jax.random.split(key)
        parts.append(jax.random.normal(sub, (counts[i], n_features)) * stds[i] + means[i])
    data = jnp.concatenate(parts, axis=0)
    return factories.array(data, split=0, device=device)
