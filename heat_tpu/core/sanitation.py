"""Input/output sanitation (reference: ``heat/core/sanitation.py``).

Host-sync contract (zero-copy dispatch audit): every check in this module
is METADATA-ONLY — shapes, dtypes, splits, types.  No function here may
read array *values* (no ``item()``/``np.asarray``/comparisons on device
data): sanitation runs on every op dispatch, and a value-dependent check
would be a blocking device→host sync in the middle of an async pipeline.
Value-dependent validation belongs behind explicit materialization points
(``numpy()``, ``item()``, printing) or inside the computation itself.
"""

from __future__ import annotations

import os
import warnings
import zlib
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import _hooks, types
from .dndarray import DNDarray

__all__ = [
    "sanitize_in",
    "sanitize_infinity",
    "sanitize_in_tensor",
    "sanitize_lshape",
    "sanitize_out",
    "sanitize_distribution",
    "sanitize_sequence",
    "scalar_to_1d",
    "MetadataError",
    "checks_enabled",
    "enable_checks",
    "disable_checks",
    "validate_metadata",
    "validate_dispatch",
    "check",
    "check_placement",
    "assert_cross_rank_consistent",
]


def sanitize_in(x) -> None:
    """Raise if ``x`` is not a DNDarray."""
    if not isinstance(x, DNDarray):
        raise TypeError(f"Input must be a DNDarray, got {type(x)}")


def sanitize_infinity(x) -> Union[int, float]:
    """Largest representable value of ``x``'s dtype (for ±inf substitution)."""
    dtype = x.dtype if isinstance(x, DNDarray) else types.canonical_heat_type(x.dtype)
    if types.heat_type_is_exact(dtype):
        return types.iinfo(dtype).max
    return types.finfo(dtype).max


def sanitize_in_tensor(x) -> jnp.ndarray:
    """Coerce to a raw jax array."""
    if isinstance(x, DNDarray):
        return x._jarray
    return jnp.asarray(x)


def sanitize_lshape(array: DNDarray, tensor) -> None:
    """Validate that a local tensor is a plausible shard of ``array``."""
    tshape = tuple(tensor.shape)
    if array.split is None:
        if tshape != array.gshape:
            raise ValueError(f"local tensor shape {tshape} inconsistent with {array.gshape}")
        return
    for i, (t, g) in enumerate(zip(tshape, array.gshape)):
        if i != array.split and t != g:
            raise ValueError(f"local tensor shape {tshape} inconsistent with {array.gshape}")


def sanitize_out(
    out: DNDarray,
    output_shape: Sequence[int],
    output_split: Optional[int],
    output_device,
    output_comm=None,
) -> None:
    """Validate an ``out=`` buffer against the expected result metadata."""
    sanitize_in(out)
    if tuple(out.shape) != tuple(output_shape):
        raise ValueError(f"Expecting output buffer of shape {tuple(output_shape)}, got {out.shape}")
    if out.split != output_split:
        # like the reference, repartition out to the required split (with warning)
        warnings.warn(
            f"Split axis of output buffer is inconsistent with split semantics (resplitting out from {out.split} to {output_split})."
        )
        out.resplit_(output_split)


def sanitize_distribution(*args, target: DNDarray, diff_map=None):
    """Force all DNDarray args onto the split/comm of ``target`` (reference parity).

    Under XLA this is a resharding ``device_put`` per mismatched operand.
    Returns single array or tuple.
    """
    out = []
    for a in args:
        sanitize_in(a)
        if a.split != target.split:
            a = a.resplit(target.split)
        out.append(a)
    return out[0] if len(out) == 1 else tuple(out)


def sanitize_sequence(seq) -> list:
    if isinstance(seq, list):
        return seq
    if isinstance(seq, tuple):
        return list(seq)
    if isinstance(seq, DNDarray):
        if seq.split is None:
            return [seq[i] for i in range(len(seq))]
        raise TypeError("seq must not be distributed")
    raise TypeError(f"seq must be a list, tuple or DNDarray, got {type(seq)}")


def scalar_to_1d(x: DNDarray) -> DNDarray:
    """Reshape a scalar DNDarray to shape (1,)."""
    if x.ndim == 0:
        return DNDarray(
            x._jarray.reshape(1), (1,), x.dtype, None, x.device, x.comm, True
        )
    return x


# ---------------------------------------------------------------------- #
# runtime metadata sanitizer — HEAT_TPU_CHECKS=1
#
# The opt-in dynamic complement of heatlint (heat_tpu/analysis): a
# METADATA-ONLY validator armed at the dispatch tails (_operations), the
# factory boundary (factories._finalize) and the resplit boundaries
# (Communication.resplit / DNDarray.resplit_ / manipulations.resplit).
# It re-checks the invariants the zero-copy fast paths are allowed to
# *assume* (DNDarray._from_parts skips __init__'s enforcement): gshape/
# pad/physical-shape agreement, dtype agreement, split range, chunk-map
# self-consistency, and canonical-sharding placement.  Everything here
# honors this module's no-value-reads contract — shapes, dtypes, splits,
# shardings only; never ``.item()``/``np.asarray``/``device_get`` of
# array data — so arming the sanitizer cannot introduce a host sync.
#
# Arming: ``sanitation.enable_checks()`` in-process, or HEAT_TPU_CHECKS=1
# in the environment (checked once at import).  enable/disable set the
# ``checks`` and ``resplit_check`` slots of ``core._hooks``, which the
# dispatch tails and the resplit boundary read: disarmed, one attribute load.
# ---------------------------------------------------------------------- #

_CHECKS_ENABLED = False


class MetadataError(ValueError):
    """A DNDarray's metadata disagrees with its physical array/sharding."""


def checks_enabled() -> bool:
    return _CHECKS_ENABLED


def enable_checks() -> None:
    """Arm the runtime metadata sanitizer (equivalent: HEAT_TPU_CHECKS=1)."""
    global _CHECKS_ENABLED
    _CHECKS_ENABLED = True
    _hooks.checks = validate_dispatch
    _hooks.resplit_check = check_placement


def disable_checks() -> None:
    global _CHECKS_ENABLED
    _CHECKS_ENABLED = False
    _hooks.checks = None
    _hooks.resplit_check = None


def _is_tracer(arr) -> bool:
    return isinstance(arr, jax.core.Tracer)


def validate_metadata(x, where: str = "") -> DNDarray:
    """Raise :class:`MetadataError` unless ``x``'s metadata is self-consistent
    and agrees with its physical array.  METADATA-ONLY: no value reads.

    Checks: gshape is a tuple of non-negative ints; split in range; pad
    bookkeeping matches the comm's padded extent; the physical array's shape
    is exactly the expected (padded) shape; dtype metadata matches the
    array; and (concrete, mesh-divisible, native-dtype arrays only) the
    sharding is the canonical one for ``split`` — which is what makes the
    derived lshape/chunk-map metadata truthful.  Returns ``x`` so call
    sites can tail-call it.
    """
    tag = f" [{where}]" if where else ""
    if not isinstance(x, DNDarray):
        raise MetadataError(f"expected DNDarray, got {type(x)}{tag}")
    gshape = x.gshape
    if not isinstance(gshape, tuple) or not all(
        isinstance(s, (int, np.integer)) and s >= 0 for s in gshape
    ):
        raise MetadataError(f"gshape {gshape!r} is not a tuple of non-negative ints{tag}")
    split = x.split
    if split is not None and not (0 <= split < len(gshape)):
        raise MetadataError(f"split {split} out of range for gshape {gshape}{tag}")
    comm = x.comm
    arr = x._parray
    pad = x._pad
    if pad:
        if split is None:
            raise MetadataError(f"pad={pad} recorded on an unsplit array{tag}")
        want_pad = comm.padded_extent(gshape[split]) - gshape[split]
        if pad != want_pad:
            raise MetadataError(
                f"pad {pad} disagrees with padded extent of {gshape[split]} over "
                f"{comm.size} shards (want {want_pad}){tag}"
            )
        expect = gshape[:split] + (gshape[split] + pad,) + gshape[split + 1 :]
    else:
        expect = gshape
    ashape = tuple(getattr(arr, "shape", expect))
    if ashape != expect:
        raise MetadataError(
            f"physical shape {ashape} != expected {'padded ' if pad else ''}shape "
            f"{expect} (gshape {gshape}, split {split}, pad {pad}){tag}"
        )
    jdt = x.dtype.jax_dtype()
    adt = getattr(arr, "dtype", None)
    if adt is not None and jnp.dtype(adt) != jnp.dtype(jdt):
        raise MetadataError(f"dtype metadata {x.dtype} != array dtype {adt}{tag}")
    # (no separate lshape check: lshape/lshape_map are pure functions of
    # (gshape, split, comm), so their consistency IS the gshape/split/pad
    # checks above plus the canonical-sharding check below)
    # canonical-sharding agreement: only where the constructor would have
    # enforced it (concrete array, mesh-divisible axis, device-native dtype)
    if (
        not _is_tracer(arr)
        and isinstance(arr, jax.Array)
        and split is not None
        and comm.size > 1
        and pad == 0
        and gshape[split] % comm.size == 0
    ):
        check_placement(arr, comm, split, where=where)
    return x


def validate_dispatch(x, where: str = "") -> DNDarray:
    """Dispatch-tail hook target (the ``checks`` slot of ``core._hooks``)."""
    return validate_metadata(x, where)


def check(x, where: str = "") -> DNDarray:
    """Validate ``x`` when the sanitizer is armed; identity otherwise.  The
    boundary wiring for the non-hot call sites (factories, resplit)."""
    if not _CHECKS_ENABLED:
        return x
    return validate_metadata(x, where)


def check_placement(array, comm, split: Optional[int], where: str = ""):
    """Raise unless a concrete array carries the canonical sharding of
    ``split`` over ``comm`` (resplit-boundary hook target, the
    ``resplit_check`` slot of ``core._hooks``).  Tracers and ragged extents are
    skipped — their placement is legitimately not the canonical one.
    Returns ``array``."""
    if _is_tracer(array) or not isinstance(array, jax.Array):
        return array
    ndim = array.ndim
    if split is not None:
        split = split % ndim if ndim else None
    if split is not None and (ndim == 0 or array.shape[split] % comm.size != 0):
        return array  # ragged: split stays logical
    want = comm.sharding(ndim, split)
    cur = getattr(array, "sharding", None)
    if cur == want:
        return array
    try:
        if cur is not None and cur.is_equivalent_to(want, ndim):
            return array
    except Exception:
        pass
    tag = f" [{where}]" if where else ""
    raise MetadataError(
        f"array sharding {cur} is not the canonical sharding for split={split} "
        f"({want}){tag}"
    )


def assert_cross_rank_consistent(x, tag: str = "") -> DNDarray:
    """Multi-process SPMD: every process must hold identical metadata for the
    'same' array — a rank whose (gshape, split, dtype, pad) diverged will
    stage different collectives and deadlock its peers.  Gathers a CRC of
    the metadata tuple (a few host bytes, NOT array values) with
    ``process_allgather`` and compares; collective, so every process must
    call it together.  No-op on a single process."""
    validate_metadata(x, where=tag or "cross-rank")
    comm = x.comm
    if comm.n_processes <= 1:
        return x
    desc = repr((x.gshape, x.split, str(x.dtype), x._pad)).encode()
    digest = np.asarray([np.int64(zlib.crc32(desc))])
    from jax.experimental import multihost_utils

    digests = np.asarray(multihost_utils.process_allgather(digest))
    if not (digests == digests.ravel()[0]).all():
        raise MetadataError(
            f"cross-rank metadata disagreement for {tag or 'array'}: digests "
            f"{digests.ravel().tolist()} (this rank: gshape={x.gshape}, "
            f"split={x.split}, dtype={x.dtype}, pad={x._pad})"
        )
    return x


# env arming (checked once at import, like HEAT_TPU_TELEMETRY)
if os.environ.get("HEAT_TPU_CHECKS", "").strip().lower() in ("1", "true", "on", "yes"):
    enable_checks()
