"""Random number generation (reference: ``heat/core/random.py``).

The reference implements two modes:

- ``Threefry``: counter-based — each element's value is a function of
  (seed, global index), so results are **split- and nprocs-invariant**.
- ``Batchparallel``: per-rank generator (faster, split-dependent).

``jax.random`` is Threefry counter-based *natively*, so the reference's
split-invariance guarantee holds by construction: we generate from a key
derived from (global seed, call counter) and shard the result.  Where
available, sharded generation (``out_sharding``) materializes each shard on
its own device.  A ``batchparallel`` mode is kept for API parity and simply
folds the process index into the key.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np

from . import _hooks, devices, types
from .communication import sanitize_comm
from .dndarray import DNDarray
from .stride_tricks import sanitize_shape


__all__ = [
    "derive_seed",
    "get_state",
    "host_rng",
    "normal",
    "permutation",
    "rand",
    "randint",
    "randn",
    "random",
    "random_integer",
    "random_sample",
    "randperm",
    "ranf",
    "sample",
    "seed",
    "set_state",
    "standard_normal",
    "uniform",
]

# global RNG state: (mode, seed, counter)
__seed: int = 0
__counter: int = 0
__mode: str = "threefry"


def seed(seed: Optional[int] = None) -> None:
    """(Re-)seed the global generator."""
    global __seed, __counter
    if seed is None:
        seed = int(np.random.SeedSequence().entropy % (2**63))
    __seed = int(seed)
    __counter = 0


def get_state() -> Tuple[str, int, int, int, float]:
    """Reference-compatible state tuple (name, seed, counter, _, _)."""
    return ("Threefry" if __mode == "threefry" else "Batchparallel", __seed, __counter, 0, 0.0)


def set_state(state: Tuple) -> None:
    global __seed, __counter, __mode
    if state[0] not in ("Threefry", "Batchparallel"):
        raise ValueError(f"unknown RNG type {state[0]}")
    __mode = state[0].lower()
    __seed = int(state[1])
    __counter = int(state[2]) if len(state) > 2 else 0


def host_rng(seed: int) -> np.random.Generator:
    """Host-side numpy ``Generator`` for an explicitly-seeded draw.

    The sanctioned route for host-side (numpy) randomness in library code:
    the caller supplies a seed that is identical on every rank — a
    literal, a broadcast value, or :func:`derive_seed` — so nominally
    identical SPMD code draws identical values on every process.  A raw
    ``np.random.default_rng(...)`` anywhere else is the per-process-entropy
    hazard heatlint HT105 flags (and its autofixer rewrites to this)."""
    return np.random.default_rng(seed)


def derive_seed() -> int:
    """Rank-uniform 63-bit seed derived from the broadcast RNG state.

    Advances the global ``(seed, counter)`` state exactly like device-side
    generation, so lockstep SPMD callers derive the IDENTICAL value on
    every rank with no communication — the replacement for seeding host
    RNGs from ``np.random.randint(...)`` (per-process entropy: every rank
    would shuffle differently and desynchronize)."""
    global __counter
    ss = np.random.SeedSequence(entropy=__seed, spawn_key=(__counter,))
    __counter += 1
    return int(ss.generate_state(1, dtype=np.uint64)[0] >> 1)


def _next_key() -> jax.Array:
    global __counter
    key = jax.random.fold_in(jax.random.key(__seed), __counter)
    __counter += 1
    if __mode == "batchparallel":
        key = jax.random.fold_in(key, jax.process_index())
    return key


def _generate(sampler, shape, dtype, split, device, comm, **kw) -> DNDarray:
    shape = sanitize_shape(shape)
    dtype = types.canonical_heat_type(dtype)
    comm = sanitize_comm(comm)
    device = devices.sanitize_device(device)
    key = _next_key()
    sharding = comm.sharding(len(shape), split)
    try:
        # sharded generation (requires Explicit-mode mesh axes)
        jarr = sampler(key, shape, dtype=dtype.jax_dtype(), out_sharding=sharding, **kw)
    except (TypeError, ValueError):
        jarr = sampler(key, shape, dtype=dtype.jax_dtype(), **kw)
        jarr = comm.shard(jarr, split)
    ret = DNDarray(jarr, shape, dtype, split, device, comm, True)
    ml = _hooks.memledger
    if ml is not None:
        # ledger choke point: op=None -> the ledger's frame walk names the
        # public factory up-stack (rand/randn/randint/normal/...)
        ml.register(ret._parray, op=None, site="factory")
    return ret


def rand(*d, dtype=types.float32, split=None, device=None, comm=None) -> DNDarray:
    """Uniform [0, 1) samples of the given shape."""
    shape = d if len(d) > 0 else (1,)
    if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
        shape = tuple(shape[0])
    return _generate(jax.random.uniform, shape, dtype, split, device, comm)


def random_sample(shape=(1,), dtype=types.float32, split=None, device=None, comm=None) -> DNDarray:
    return _generate(jax.random.uniform, shape, dtype, split, device, comm)


random = random_sample
ranf = random_sample
sample = random_sample


def randn(*d, dtype=types.float32, split=None, device=None, comm=None) -> DNDarray:
    """Standard-normal samples of the given shape."""
    shape = d if len(d) > 0 else (1,)
    if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
        shape = tuple(shape[0])
    return _generate(jax.random.normal, shape, dtype, split, device, comm)


def standard_normal(shape=(1,), dtype=types.float32, split=None, device=None, comm=None) -> DNDarray:
    return _generate(jax.random.normal, shape, dtype, split, device, comm)


def normal(mean=0.0, std=1.0, shape=(1,), dtype=types.float32, split=None, device=None, comm=None) -> DNDarray:
    """Normal(mean, std) samples."""
    base = _generate(jax.random.normal, shape, dtype, split, device, comm)
    if np.isscalar(mean) and np.isscalar(std):
        if float(std) < 0:
            raise ValueError("std must be non-negative")
        base._jarray = base._jarray * float(std) + float(mean)
        return base
    from . import arithmetics

    return arithmetics.add(arithmetics.mul(base, std), mean)


def uniform(low=0.0, high=1.0, size=(1,), dtype=types.float32, split=None, device=None, comm=None) -> DNDarray:
    return _generate(
        jax.random.uniform, size, dtype, split, device, comm, minval=float(low), maxval=float(high)
    )


def randint(low, high=None, size=None, dtype=types.int32, split=None, device=None, comm=None) -> DNDarray:
    """Random integers in [low, high)."""
    if high is None:
        low, high = 0, low
    if size is None:
        size = (1,)
    if high <= low:
        raise ValueError("low >= high")
    return _generate(
        jax.random.randint, size, dtype, split, device, comm, minval=int(low), maxval=int(high)
    )


random_integer = randint


def permutation(x, split=None, device=None, comm=None) -> DNDarray:
    """Random permutation of arange(x) or a shuffle of the array x along axis 0."""
    key = _next_key()
    if isinstance(x, DNDarray):
        res = jax.random.permutation(key, x._jarray, axis=0)
        res = x.comm.shard(res, x.split)
        return DNDarray(res, x.gshape, x.dtype, x.split, x.device, x.comm, True)
    if isinstance(x, (int, np.integer)):
        res = jax.random.permutation(key, int(x))
        comm = sanitize_comm(comm)
        res = comm.shard(res, split)
        return DNDarray(
            res, tuple(res.shape), types.canonical_heat_type(res.dtype), split,
            devices.sanitize_device(device), comm, True,
        )
    raise TypeError(f"x must be int or DNDarray, got {type(x)}")


def randperm(n: int, dtype=types.int32, split=None, device=None, comm=None) -> DNDarray:
    """Random permutation of range(n)."""
    return permutation(int(n), split=split, device=device, comm=comm).astype(dtype, copy=False)


seed()


