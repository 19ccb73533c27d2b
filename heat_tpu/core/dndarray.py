"""DNDarray — the distributed N-D array, TPU-native.

Re-design of the reference's ``heat/core/dndarray.py`` (SURVEY §2.1).  The
reference's DNDarray is *locally a torch.Tensor, globally a chunked array*;
each MPI rank stores its chunk and all global bookkeeping (gshape, lshape_map,
index translation) is hand-maintained Python.  Here a DNDarray wraps ONE
globally-shaped :class:`jax.Array` whose ``NamedSharding`` over the
communicator's mesh realizes the ``split`` axis:

- ``split=None``  ⇔  fully replicated (``PartitionSpec()``)
- ``split=k``     ⇔  axis ``k`` sharded over the mesh axis
  (``PartitionSpec(..., 'x', ...)``)

All inter-chip data movement is emitted by XLA when ops require it; the
explicit ``resplit_`` maps to ``Communication.resplit``'s reshard program
(→ all-to-all).

DNDarray is registered as a JAX pytree (the array is the leaf; split/device/
comm are static aux data), so user functions over DNDarrays can be ``jax.jit``
-ed, differentiated, and vmapped — something the reference fundamentally
cannot offer.
"""

from __future__ import annotations

from typing import List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import _hooks, types
from .communication import Communication
from .devices import Device
from .stride_tricks import sanitize_axis

__all__ = ["DNDarray"]

Scalar = Union[int, float, bool, complex]


class LocalIndex:
    """Marker for local-index assignment, parity with reference ``x.lloc``."""

    def __init__(self, arr: "DNDarray"):
        self.arr = arr

    def __getitem__(self, key):
        return self.arr.larray[key]

    def __setitem__(self, key, value):
        # local == global view on a single controller; route through global set
        self.arr[key] = value


class DNDarray:
    """A globally-shaped, mesh-sharded N-D array with a NumPy-style API."""

    def __init__(
        self,
        array: jax.Array,
        gshape: Tuple[int, ...],
        dtype,
        split: Optional[int],
        device: Device,
        comm: Communication,
        balanced: Optional[bool] = True,
    ):
        gshape = tuple(int(s) for s in gshape)
        if split is not None and len(gshape):
            split = split % len(gshape)
        elif split is not None:
            split = None
        self.__gshape = gshape
        self.__dtype = types.canonical_heat_type(dtype)
        self.__split = split
        self.__device = device
        self.__comm = comm
        # `balanced` is accepted for reference API parity but not stored:
        # balancedness is a pure function of (gshape, split, comm) under the
        # canonical ceil-div layout — see is_balanced()
        self.__pad = 0
        self.__unpadded = None
        # --- physical normalization (pad-and-mask, SURVEY §7 hard part #1) ---
        # NamedSharding requires the sharded axis to be divisible by the mesh
        # axis size.  Ragged axes are physically stored zero-padded to
        # ceil(n/p)*p; `gshape` carries the logical (true) extent and `_pad`
        # the trailing dead region.  This constructor is the single choke
        # point: any DNDarray with a split axis is guaranteed physically
        # sharded over the full mesh, so `split` metadata never lies
        # (cf. reference `heat/core/dndarray.py` chunk-map invariant).
        if split is not None and comm.size > 1 and hasattr(array, "shape"):
            n = gshape[split]
            target = comm.padded_extent(n)
            pad = target - n
            ashape = tuple(array.shape)
            expect_logical = gshape
            expect_physical = gshape[:split] + (target,) + gshape[split + 1 :]
            if ashape == expect_physical and pad:
                # caller provides the padded physical — still enforce placement
                self.__pad = pad
                array = self._enforce_placement(array, comm, split)
            elif ashape == expect_logical:
                if pad:
                    array = comm.pad_shard(array, split)
                    self.__pad = pad
                else:
                    array = self._enforce_placement(array, comm, split)
            else:
                raise ValueError(
                    f"array shape {ashape} matches neither the logical gshape "
                    f"{expect_logical} nor the padded physical shape {expect_physical}"
                )
        self.__array = array

    @classmethod
    def _from_parts(cls, array, gshape, dtype, split, device, comm) -> "DNDarray":
        """Wrap a dispatch-cache program output WITHOUT re-validation.

        The cached executables compile the canonical output sharding in
        (``with_sharding_constraint``) and their plans pre-resolve shape,
        heat dtype and split — re-running ``__init__``'s placement
        enforcement and pad bookkeeping per call would re-derive facts the
        plan already guarantees.  Callers must guarantee: ``gshape`` is a
        tuple of ints matching ``array.shape``, ``split`` is in range (or
        None), and the split axis is mesh-divisible (pad-free).
        """
        self = object.__new__(cls)
        self._DNDarray__gshape = gshape
        self._DNDarray__dtype = dtype
        self._DNDarray__split = split
        self._DNDarray__device = device
        self._DNDarray__comm = comm
        self._DNDarray__pad = 0
        self._DNDarray__unpadded = None
        self._DNDarray__array = array
        ml = _hooks.memledger
        if ml is not None:
            # ledger choke point, hot-tier recorder: one lean call —
            # under-threshold buffers coalesce into a counter, buffers of
            # consequence get the full provenance entry (op name resolved
            # by frame peek: the public wrapper above the dispatch tail)
            ml.register_dispatch(array)
        return self

    @staticmethod
    def _enforce_placement(array, comm, split):
        """No DNDarray may claim a split its sharding doesn't have: place
        concrete arrays on the canonical sharding unless already equivalent.
        Hosted-complex arrays (transport without native complex) stay on the
        host backend; tracers are left to the surrounding jit."""
        if isinstance(array, jax.core.Tracer):
            return array
        sh = comm.sharding(array.ndim, split)
        cur = getattr(array, "sharding", None)
        if cur == sh:
            return array
        try:
            if cur is not None and cur.is_equivalent_to(sh, array.ndim):
                return array
        except Exception:
            pass
        return jax.device_put(array, sh)

    # ------------------------------------------------------------------ #
    # internal access
    # ------------------------------------------------------------------ #
    @property
    def _jarray(self) -> jax.Array:
        """The LOGICAL global jax.Array — true ``gshape``, pad sliced off.

        For the (common) divisible case this is the stored array itself; for
        ragged splits it is a cached slice of the padded physical array. Ops
        that consume `_jarray` are correct by construction; pad-aware fast
        paths use `_parray`/`_masked` instead.
        """
        if self.__pad == 0:
            return self.__array
        if self.__unpadded is None:
            sl = tuple(
                slice(0, self.__gshape[i]) if i == self.__split else slice(None)
                for i in range(len(self.__gshape))
            )
            self.__unpadded = self.__array[sl]
        return self.__unpadded

    @_jarray.setter
    def _jarray(self, arr) -> None:
        """Replace contents with a LOGICAL (true-shape) array; re-pads/places."""
        self._renormalize(arr)

    @property
    def _parray(self) -> jax.Array:
        """The PHYSICAL stored array (padded along split when `_pad` > 0)."""
        return self.__array

    @property
    def _pad(self) -> int:
        """Trailing zero-pad extent along the split axis (0 when divisible)."""
        return self.__pad

    def _masked(self, fill) -> jax.Array:
        """Physical array with the pad region replaced by ``fill`` — the
        reduction-identity masking of pad-and-mask (e.g. 0 for sum, -inf for
        max).  No-op when the array is not padded."""
        if self.__pad == 0:
            return self.__array
        from jax import lax as _lax

        iota = _lax.broadcasted_iota(jnp.int32, self.__array.shape, self.__split)
        fillv = jnp.asarray(fill, dtype=self.__array.dtype)
        return jnp.where(iota < self.__gshape[self.__split], self.__array, fillv)

    def _renormalize(self, logical: jax.Array) -> None:
        """Install ``logical`` (true-shape) as the new contents: recompute the
        global shape, pad and physically place as needed."""
        self.__gshape = tuple(int(s) for s in logical.shape)
        self.__unpadded = None
        self.__pad = 0
        split = self.__split
        if split is not None and split < len(self.__gshape) and self.__comm.size > 1:
            n = self.__gshape[split]
            target = self.__comm.padded_extent(n)
            if target != n:
                logical = self.__comm.pad_shard(logical, split)
                self.__pad = target - n
        self.__array = logical

    # ------------------------------------------------------------------ #
    # reference-parity attributes
    # ------------------------------------------------------------------ #
    @property
    def larray(self) -> jax.Array:
        """The process-local data.

        Single-controller JAX addresses all chips, so the 'local' view is the
        global (logical) array itself.  (Reference users index shards via
        ``lshape_map``/``chunk``.)
        """
        return self._jarray

    @larray.setter
    def larray(self, array: jax.Array) -> None:
        self._renormalize(array)

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.__gshape

    @property
    def gshape(self) -> Tuple[int, ...]:
        return self.__gshape

    @property
    def lshape(self) -> Tuple[int, ...]:
        """Shape of shard 0's chunk (reference: "this rank's chunk").

        Single-controller semantics: there is ONE process addressing all
        shards, so "local" is a convention — this reports the FIRST shard's
        valid extent from the canonical ceil-div chunk map.  Per-shard truth
        for every shard is ``lshape_map()``; for ragged shapes the shards
        differ (e.g. 100 rows on 8 devices → 13,…,13,9) and ``lshape`` alone
        cannot describe them all.
        """
        _, lshape, _ = self.__comm.chunk(self.__gshape, self.__split, rank=0)
        return lshape

    def lshape_map(self, force_check: bool = False) -> np.ndarray:
        """(size, ndim) matrix of all shard shapes — pure math, no comm needed."""
        return self.__comm.lshape_map(self.__gshape, self.__split)

    @property
    def dtype(self):
        return self.__dtype

    @property
    def split(self) -> Optional[int]:
        return self.__split

    @property
    def device(self) -> Device:
        return self.__device

    @property
    def comm(self) -> Communication:
        return self.__comm

    @property
    def balanced(self) -> bool:
        return self.is_balanced()

    @property
    def ndim(self) -> int:
        return len(self.__gshape)

    @property
    def size(self) -> int:
        return int(np.prod(self.__gshape, dtype=np.int64)) if self.__gshape else 1

    @property
    def gnumel(self) -> int:
        return self.size

    @property
    def lnumel(self) -> int:
        return int(np.prod(self.lshape, dtype=np.int64)) if self.lshape else 1

    @property
    def nbytes(self) -> int:
        return self.size * self.__dtype.np_dtype().itemsize

    @property
    def gnbytes(self) -> int:
        return self.nbytes

    @property
    def lnbytes(self) -> int:
        return self.lnumel * self.__dtype.np_dtype().itemsize

    @property
    def imag(self) -> "DNDarray":
        from . import complex_math

        return complex_math.imag(self)

    @property
    def real(self) -> "DNDarray":
        from . import complex_math

        return complex_math.real(self)

    @property
    def T(self) -> "DNDarray":
        from ..linalg import basics

        return basics.transpose(self)

    @property
    def lloc(self) -> LocalIndex:
        return LocalIndex(self)

    @property
    def stride(self) -> Tuple[int, ...]:
        """Row-major strides in elements (XLA owns the physical layout)."""
        strides = np.cumprod((1,) + self.__gshape[:0:-1])[::-1]
        return tuple(int(s) for s in strides)

    @property
    def strides(self) -> Tuple[int, ...]:
        return tuple(s * self.__dtype.np_dtype().itemsize for s in self.stride)

    @property
    def __partitioned__(self) -> dict:
        """Cross-framework partitioned-array protocol (reference parity)."""
        comm = self.__comm
        parts = {}
        for r in range(comm.size if self.__split is not None else 1):
            off, lsh, _ = comm.chunk(self.__gshape, self.__split, r)
            pos = (r,)
            start = tuple(
                off if i == self.__split else 0 for i in range(self.ndim)
            ) if self.__split is not None else (0,) * self.ndim
            parts[pos] = {
                "start": start,
                "shape": lsh,
                "data": None,
                "location": [r],
                "dtype": self.__dtype.np_dtype(),
            }
        return {
            "shape": self.__gshape,
            "partition_tiling": (comm.size,) if self.__split is not None else (1,),
            "partitions": parts,
            "locals": [(comm.rank,)],
            "get": lambda x: x,
        }

    # ------------------------------------------------------------------ #
    # basic conversions
    # ------------------------------------------------------------------ #
    def astype(self, dtype, copy: bool = True) -> "DNDarray":
        dtype = types.canonical_heat_type(dtype)
        casted = self.__array.astype(dtype.jax_dtype())
        # honor JAX canonicalization (64→32-bit when x64 is off) in metadata
        dtype = types.canonical_heat_type(casted.dtype)
        if copy:
            return DNDarray(
                casted, self.__gshape, dtype, self.__split, self.__device, self.__comm, True
            )
        self.__array = casted
        self.__unpadded = None
        self.__dtype = dtype
        return self

    def numpy(self) -> np.ndarray:
        """Gather the global (logical) array to host memory as a numpy array."""
        src = self.__array
        try:
            out = self.__comm.host_fetch(src)
        except jax.errors.JaxRuntimeError:
            if jnp.issubdtype(src.dtype, jnp.complexfloating):
                # some TPU transports cannot ship complex buffers to host;
                # move the real/imag planes separately and recombine
                re = np.asarray(jax.device_get(jnp.real(src)))
                im = np.asarray(jax.device_get(jnp.imag(src)))
                out = (re + 1j * im).astype(self.__dtype.np_dtype())
            else:
                raise
        if self.__pad:
            sl = tuple(
                slice(0, self.__gshape[i]) if i == self.__split else slice(None)
                for i in range(len(self.__gshape))
            )
            out = out[sl]
        return out

    def __array__(self, dtype=None) -> np.ndarray:
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def tolist(self, keepsplit: bool = False) -> List:
        return self.numpy().tolist()

    def item(self):
        if self.size != 1:
            raise ValueError("only one-element DNDarrays can be converted to scalars")
        return self._jarray.reshape(()).item()

    def __bool__(self) -> bool:
        return bool(self.item())

    def __int__(self) -> int:
        return int(self.item())

    def __float__(self) -> float:
        return float(self.item())

    def __complex__(self) -> complex:
        return complex(self.item())

    def __index__(self) -> int:
        if not types.heat_type_is_exact(self.__dtype):
            raise TypeError("only integer scalar arrays can be used as an index")
        return int(self.item())

    def __len__(self) -> int:
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.__gshape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # ------------------------------------------------------------------ #
    # device / distribution management
    # ------------------------------------------------------------------ #
    def is_distributed(self) -> bool:
        return self.__split is not None and self.__comm.is_distributed()

    def is_balanced(self, force_check: bool = False) -> bool:
        """True iff every shard's valid extent differs by at most one row —
        the reference's balancedness criterion, computed from the REAL
        ceil-division chunk map (truthful for ragged shapes: e.g. 100 rows on
        8 devices gives chunks 13×7+9, which is NOT balanced).  Closed form:
        chunks are ``c = ceil(n/p)`` except the tail, so balanced ⇔
        ``c - clamp(n - (p-1)c, 0, c) <= 1``."""
        if self.__split is None or not self.__comm.is_distributed():
            return True
        n, p = self.__gshape[self.__split], self.__comm.size
        c = -(-n // p)
        tail = max(0, min(c, n - (p - 1) * c))
        return c - tail <= 1

    def balance_(self) -> None:
        """Reference parity stub: under GSPMD the ceil-division grid is the
        ONLY physical layout — there is no unbalanced state to repair (ragged
        shapes are padded, not unevenly chunked), so this is a no-op.
        ``is_balanced()`` may legitimately stay False for ragged shapes; that
        reports the ceil-div chunk asymmetry, not a repairable state."""

    def resplit_(
        self, axis: Optional[int] = None, memory_budget: Optional[int] = None
    ) -> "DNDarray":
        """In-place redistribution to a new split axis (reference SURVEY §3.3).

        Lowered by XLA to an all-to-all (split↔split) or allgather (→None);
        ragged axes are re-padded along the new split axis.  In-place means
        in-place: the old buffer is DONATED to the reshard program (layout
        permitting, XLA aliases or early-frees it), so other DNDarrays
        sharing this array's buffer — ``astype(copy=False)`` views — must
        not be read afterwards.  Use ``resplit()`` for the copying form.

        ``memory_budget`` (bytes; ``None`` → the process default from
        ``ht.set_redistribution_budget()`` / ``HEAT_TPU_RESPLIT_BUDGET``)
        bounds the bytes moved per step: an oversized transition streams as
        K budget-sized tiled all-to-alls with the destination written in
        place and the source freed as soon as its last tile is staged (see
        ``core.redistribution``).
        """
        axis = sanitize_axis(self.__gshape, axis)
        if axis == self.__split:
            return self
        logical = self._jarray
        self.__split = axis
        self.__pad = 0
        self.__unpadded = None
        if axis is None:
            self.__array = self.__comm.resplit(
                logical, None, donate=True, memory_budget=memory_budget
            )
        else:
            self._renormalize(logical)
            if self.__pad == 0:
                self.__array = self.__comm.resplit(
                    self.__array, axis, donate=True, memory_budget=memory_budget
                )
        from . import sanitation  # lazy: sanitation imports this module

        return sanitation.check(self, "resplit_")

    def redistribute_(self, lshape_map=None, target_map=None) -> None:
        """Redistribute to a target chunk map (reference
        ``DNDarray.redistribute_``).

        Under GSPMD the per-shard placement is canonically determined by the
        ``NamedSharding`` (ceil-division chunks): the canonical map is
        enforced physically (a ``device_put``, lowered to all-to-all if data
        is elsewhere); any OTHER chunk map is not representable — JAX offers
        no per-device uneven placement — so a non-canonical ``target_map``
        raises ``NotImplementedError`` instead of silently lying about the
        layout (SURVEY §7 hard part #1).
        """
        if self.__split is None:
            return
        if target_map is not None:
            tm = np.asarray(target_map)
            canonical = self.__comm.lshape_map(self.__gshape, self.__split)
            if tm.shape != canonical.shape or not (tm == canonical).all():
                raise NotImplementedError(
                    "arbitrary chunk maps are not representable under GSPMD "
                    "even-sharding; only the canonical ceil-division map is "
                    f"supported (requested {tm.tolist()}, canonical "
                    f"{canonical.tolist()}). Use resplit_() to change the "
                    "split axis instead."
                )
        # enforce canonical physical placement
        if self.__pad == 0:
            self.__array = self.__comm.shard(self.__array, self.__split)
        else:
            self.__array = self.__comm.pad_shard(self._jarray, self.__split)
            self.__unpadded = None

    def resplit(
        self, axis: Optional[int] = None, memory_budget: Optional[int] = None
    ) -> "DNDarray":
        from . import manipulations

        return manipulations.resplit(self, axis, memory_budget=memory_budget)

    def cpu(self) -> "DNDarray":
        from . import devices as _dev

        return self.to_device(_dev.cpu)

    def to_device(self, device) -> "DNDarray":
        from . import devices as _dev
        from .communication import Communication

        device = _dev.sanitize_device(device)
        if device == self.__device:
            return self
        comm = Communication(device.mesh)
        host = jnp.asarray(self.numpy())
        split = self.__split
        if split is None or self.__gshape[split] % comm.size == 0:
            host = jax.device_put(host, comm.sharding(self.ndim, split))
        # ragged: the constructor pad-shards onto the target mesh
        return DNDarray(host, self.__gshape, self.__dtype, split, device, comm, True)

    # ------------------------------------------------------------------ #
    # halo support (reference: get_halo / array_with_halos, used by convolve)
    # ------------------------------------------------------------------ #
    def get_halo(self, halo_size: int, prev: bool = True, next: bool = True) -> None:
        """Record the requested halo width; materialization happens inside the
        shard_map of the consuming op (see ``parallel.halo.halo_exchange``)."""
        if not isinstance(halo_size, int) or halo_size < 0:
            raise (TypeError if not isinstance(halo_size, int) else ValueError)(
                f"halo_size needs to be a non-negative int, got {halo_size}"
            )
        self.__halo_size = halo_size

    @property
    def array_with_halos(self) -> jax.Array:
        from ..parallel.halo import with_halos

        hs = getattr(self, "_DNDarray__halo_size", 0)
        if self.__split is None or hs == 0:
            return self._jarray
        return with_halos(self._jarray, hs, self.__split, self.__comm)

    # ------------------------------------------------------------------ #
    # indexing
    # ------------------------------------------------------------------ #
    def _normalized_key(self, key):
        def conv(k):
            if isinstance(k, DNDarray):
                return k._jarray
            if isinstance(k, (list, np.ndarray)):
                # numpy-style list/ndarray fancy index → jnp array
                return jnp.asarray(k)
            return k

        if isinstance(key, tuple):
            return tuple(conv(k) for k in key)
        return conv(key)

    def _result_split_of_key(self, key) -> Optional[int]:
        """Compute the split axis of an indexing result (None ⇒ replicated)."""
        if self.__split is None:
            return None
        key_t = key if isinstance(key, tuple) else (key,)
        # expand Ellipsis
        if any(k is Ellipsis for k in key_t):
            n_specified = sum(1 for k in key_t if k is not None and k is not Ellipsis)
            fill = self.ndim - n_specified
            out = []
            for k in key_t:
                if k is Ellipsis:
                    out.extend([slice(None)] * fill)
                else:
                    out.append(k)
            key_t = tuple(out)
        # walk input axes vs output axes
        in_ax = 0
        out_ax = 0
        has_advanced = any(
            isinstance(k, (list, np.ndarray, jax.Array)) and not isinstance(k, (bool, np.bool_))
            for k in key_t
        )
        for k in key_t:
            if k is None:
                out_ax += 1
                continue
            if in_ax == self.__split:
                if isinstance(k, slice):
                    return out_ax
                if isinstance(k, (int, np.integer)):
                    return None
                # advanced index on the split axis
                if has_advanced and not isinstance(k, (bool, np.bool_)):
                    # 1-D fancy index keeps a distributed result axis
                    return 0 if not isinstance(k, slice) else out_ax
                return None
            if isinstance(k, (int, np.integer)):
                in_ax += 1  # consumes an axis, produces none
            elif isinstance(k, slice):
                in_ax += 1
                out_ax += 1
            else:
                # advanced index consumes (possibly several for bool) axes
                if isinstance(k, (np.ndarray, jax.Array)) and k.dtype == np.bool_:
                    in_ax += k.ndim
                else:
                    in_ax += 1
                out_ax += 1
        # remaining untouched axes
        if in_ax <= self.__split:
            return out_ax + (self.__split - in_ax)
        return None

    def __getitem__(self, key) -> "DNDarray":
        nkey = self._normalized_key(key)
        result = self._jarray[nkey]
        new_split = self._result_split_of_key(nkey)
        if new_split is not None and new_split >= result.ndim:
            new_split = None
        result = self.__comm.shard(result, new_split)
        return DNDarray(
            result,
            tuple(result.shape),
            types.canonical_heat_type(result.dtype),
            new_split,
            self.__device,
            self.__comm,
            True,
        )

    def __setitem__(self, key, value) -> None:
        nkey = self._normalized_key(key)
        if isinstance(value, DNDarray):
            value = value._jarray
        if self.__pad:
            self._renormalize(self._jarray.at[nkey].set(value))
        else:
            updated = self.__array.at[nkey].set(value)
            self.__array = self.__comm.shard(updated, self.__split)

    def fill_diagonal(self, value) -> "DNDarray":
        n = min(self.__gshape[-2], self.__gshape[-1]) if self.ndim >= 2 else 0
        idx = jnp.arange(n)
        if self.__pad:
            self._renormalize(self._jarray.at[..., idx, idx].set(value))
        else:
            updated = self.__array.at[..., idx, idx].set(value)
            self.__array = self.__comm.shard(updated, self.__split)
        return self

    # ------------------------------------------------------------------ #
    # printing
    # ------------------------------------------------------------------ #
    def __repr__(self) -> str:
        from . import printing

        return printing.__repr__(self)

    def __str__(self) -> str:
        from . import printing

        return printing.__str__(self)

    # ------------------------------------------------------------------ #
    # interop stubs
    # ------------------------------------------------------------------ #
    def __torch_proxy__(self):
        import torch

        return torch.from_numpy(np.asarray(self.numpy()))

    def counts_displs(self):
        if self.__split is None:
            raise ValueError("Non-distributed DNDarray has no counts and displacements")
        return self.__comm.counts_displs_shape(self.__gshape, self.__split)


# ---------------------------------------------------------------------- #
# pytree registration: DNDarray-valued functions are jit/grad/vmap-able
# ---------------------------------------------------------------------- #
def _dnd_flatten(x: DNDarray):
    # the LOGICAL array is the leaf: transforms must see the true gshape or
    # vmap(in_axes=0) over a ragged array maps over pad rows.  The unpad
    # slice this costs at a trace boundary is re-padded by the constructor on
    # the way out (concrete leaves), so distribution is restored at every
    # concrete boundary; pad in the aux is always 0 here, kept (with ndim)
    # so unflatten can re-anchor split when batching transforms add axes
    return (x._jarray,), (x.split, x.device, x.comm, 0, x.ndim)


def _dnd_unflatten(aux, children):
    (arr,) = children
    split, device, comm, _pad_unused, ndim0 = aux  # flatten always emits pad=0
    shape = list(arr.shape) if hasattr(arr, "shape") else []
    nd = len(shape)
    if split is not None:
        delta = nd - ndim0
        adj = split + delta if delta > 0 else split  # leading batch dims added
        split = adj if 0 <= adj < nd else None
    shape = tuple(shape)
    try:
        dtype = types.canonical_heat_type(arr.dtype)
    except (TypeError, AttributeError):
        dtype = types.float32
    try:
        return DNDarray(arr, shape, dtype, split, device, comm, True)
    except ValueError:
        # a transform (vmap batching, scan carry) reshaped the leaf so the
        # pad bookkeeping no longer lines up; treat the leaf as logical
        return DNDarray(arr, tuple(arr.shape), dtype, None, device, comm, True)


jax.tree_util.register_pytree_node(DNDarray, _dnd_flatten, _dnd_unflatten)

