"""Communication backend: XLA collectives over an ICI/DCN device mesh.

This is the TPU-native re-design of the reference's
``heat/core/communication.py::MPICommunication`` (SURVEY §2.1, §5.8).  The
reference wraps ``mpi4py``: every rank owns a local torch tensor and ships
bytes explicitly (derived datatypes, CUDA-aware fast paths, request objects).
Here the roles invert — arrays are globally-shaped ``jax.Array``s sharded over
a :class:`jax.sharding.Mesh`, and *implicit* collectives are emitted by XLA's
SPMD partitioner whenever a computation needs them.  What remains for an
explicit ``Communication`` object:

- **shard math** (``chunk``, ``counts_displs_shape``) for I/O boundaries and
  test oracles, matching JAX's ceil-division placement convention;
- **sharding constructors** (``sharding(ndim, split)``) translating the
  reference's ``split`` axis to a ``NamedSharding``;
- **redistribution** (``resplit`` → one cached program per signature, a
  jitted identity with the new sharding as ``out_shardings``, which XLA
  lowers to an all-to-all over ICI: see ``resplit``);
- **functional collectives** (``psum``/``all_gather``/``all_to_all``/
  ``ppermute``/…) for use inside ``shard_map`` — the building blocks of the
  manual-control paths (ring cdist, halo convolve, TSQR, DASO);
- process-level helpers for the multi-host control plane.

MPI-name parity table (reference → here):
``Allreduce→psum``, ``Allgather(v)→all_gather``, ``Alltoall(v)→all_to_all``,
``Bcast→select-from-source ppermute``, ``Isend/Irecv→ppermute`` (XLA
collectives are asynchronously dispatched, so every op is effectively the
nonblocking variant; ``jax.block_until_ready`` is ``Wait``), ``Exscan→
associative_scan over shards``.
"""

from __future__ import annotations

import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import devices
from ._cache import cached_program, launch

__all__ = [
    "Communication",
    "sanitize_comm",
    "get_comm",
    "use_comm",
    "world",
]

# telemetry is imported lazily (core modules load before utils) and cached;
# every call below is at collective STAGING time or inside resplit — never
# the per-op dispatch hot path
_TELEMETRY_MOD = None

# health (deadline watchdog) and faults are lazily cached the same way:
# used at collective staging and around the blocking waits, never in the
# dispatch hot path
_HEALTH_MOD = None

# runtime sanitizer hook (HEAT_TPU_CHECKS=1): ``core.sanitation.
# enable_checks()`` points this at ``sanitation.check_placement`` so every
# eager resplit verifies the produced array actually carries the canonical
# sharding of its target split (metadata-only: sharding objects, no value
# reads).  Disabled cost: one module-global load per resplit.  This module
# currently loads before sanitation (sanitation → dndarray → here), so the
# env-arming poke lands after this line runs — but that ordering is
# transitive and fragile, so the module bottom re-arms defensively like
# ``_operations`` does.
_RESPLIT_CHECK = None

# flight-recorder hook (``utils.flightrec.enable()`` pokes the module in,
# ``disable()`` clears it): every staged collective is seq-stamped at the
# ``_account_bytes`` choke point below.  Disabled cost: one module-global
# load at staging time.  Module bottom re-arms against import-order races
# exactly like the two hooks above.
_FLIGHTREC = None

# device-memory-ledger hook (``utils.memledger.enable()`` pokes the module
# in): resplit outputs are registration choke points, the ``mem.alloc``
# fault site fires ahead of each transfer's allocation, donated sources
# are consumed, and a RESOURCE_EXHAUSTED out of the transfer renders the
# ledger dump into the flight ring before re-raising.  Disabled cost: one
# module-global load per resplit.  Module bottom re-arms.
_MEMLEDGER = None


def _telemetry():
    global _TELEMETRY_MOD
    if _TELEMETRY_MOD is None:
        from ..utils import telemetry

        _TELEMETRY_MOD = telemetry
    return _TELEMETRY_MOD


def _health():
    global _HEALTH_MOD
    if _HEALTH_MOD is None:
        from ..utils import health

        _HEALTH_MOD = health
    return _HEALTH_MOD


def _payload_nbytes(x) -> int:
    """nbytes of an array OR a tracer (shape/dtype live on the aval, so the
    collective wrappers can account bytes while being traced)."""
    try:
        n = 1
        for s in x.shape:
            n *= int(s)
        return n * np.dtype(x.dtype).itemsize
    except Exception:
        return 0


def _array_from_callback(host: "np.ndarray", sh: NamedSharding) -> jax.Array:
    """Global array from host data, one slice per addressable device.

    The explicit dtype matters on sub-meshes that leave this process with
    ZERO addressable shards (inference has no data there)."""
    return jax.make_array_from_callback(
        host.shape, sh, lambda idx: host[idx], dtype=host.dtype
    )


class Communication:
    """A communicator: a device mesh axis over which arrays are sharded.

    The analogue of the reference's ``MPICommunication``.  ``size`` is the
    number of shards along the communicator's mesh axis (the reference's
    ``comm.size``); ``rank`` is the *process* index, which on a single
    controller addressing all chips is 0 — per-shard identity only exists
    inside ``shard_map`` (use :meth:`axis_index`).
    """

    def __init__(self, mesh: Optional[Mesh] = None, axis: str = "x"):
        if mesh is None:
            mesh = devices.get_default_mesh()
        if axis not in mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")
        self.__mesh = mesh
        self.__axis = axis

    # ------------------------------------------------------------------ #
    # identity / topology
    # ------------------------------------------------------------------ #
    @property
    def mesh(self) -> Mesh:
        return self.__mesh

    @property
    def axis(self) -> str:
        return self.__axis

    @property
    def size(self) -> int:
        """Number of shards along this communicator's axis (= reference nprocs)."""
        return self.__mesh.shape[self.__axis]

    @property
    def rank(self) -> int:
        """The PROCESS index — NOT a shard index.

        Single-controller JAX addresses all chips from one process, so this
        is 0 everywhere today; under multi-process JAX it is the host index
        (0..n_processes-1), NOT 0..size-1.  Code needing per-shard identity
        must use :meth:`axis_index` inside ``shard_map`` — reference code
        that branches on ``comm.rank`` for data placement should consult
        ``chunk()``/``lshape_map`` instead.
        """
        return jax.process_index()

    @property
    def n_processes(self) -> int:
        return jax.process_count()

    def is_distributed(self) -> bool:
        return self.size > 1

    def axis_index(self):
        """Shard index along this communicator's axis — ONLY inside shard_map."""
        return lax.axis_index(self.__axis)

    def __eq__(self, other) -> bool:
        if not isinstance(other, Communication):
            return NotImplemented
        return self.__mesh == other.mesh and self.__axis == other.axis

    def __hash__(self) -> int:
        return hash((self.__mesh, self.__axis))

    def __repr__(self) -> str:
        return f"Communication(size={self.size}, axis={self.__axis!r}, mesh={tuple(self.__mesh.shape.items())})"

    # ------------------------------------------------------------------ #
    # shard math — matches JAX's ceil-division placement so that
    # `chunk()` predictions agree with jax.Array.addressable_shards.
    # (Deviation from the reference, which gives the first gshape%size
    # ranks one extra row; documented in SURVEY §7 "Hard parts" #1.)
    # ------------------------------------------------------------------ #
    def chunk(
        self, shape, split: Optional[int], rank: Optional[int] = None
    ) -> Tuple[int, Tuple[int, ...], Tuple[slice, ...]]:
        """Offset, local shape and slices of shard ``rank`` of a global ``shape``.

        cf. reference ``MPICommunication.chunk`` — pure shard math, no comm.
        """
        shape = tuple(int(s) for s in shape)
        if split is None:
            return 0, shape, tuple(slice(0, s) for s in shape)
        split = split % len(shape)
        if rank is None:
            rank = 0
        n, p = shape[split], self.size
        c = -(-n // p)  # ceil division, JAX/GSPMD convention
        start = min(rank * c, n)
        end = min(start + c, n)
        lshape = shape[:split] + (end - start,) + shape[split + 1 :]
        slices = tuple(
            slice(start, end) if i == split else slice(0, s) for i, s in enumerate(shape)
        )
        return start, lshape, slices

    def padded_extent(self, n: int) -> int:
        """Smallest multiple of ``size`` ≥ ``ceil(n/size)*size`` — the physical
        extent of a ragged axis under pad-and-mask sharding (SURVEY §7 hard
        part #1)."""
        c = -(-int(n) // self.size)
        return c * self.size

    def counts_displs_shape(self, shape, split: int):
        """Per-shard counts and displacements along ``split`` (I/O hyperslabs)."""
        counts, displs = [], []
        for r in range(self.size):
            off, lsh, _ = self.chunk(shape, split, r)
            counts.append(lsh[split])
            displs.append(off)
        return tuple(counts), tuple(displs)

    def lshape_map(self, shape, split: Optional[int]) -> np.ndarray:
        """(size, ndim) array of every shard's local shape (reference: DNDarray.lshape_map)."""
        out = np.empty((self.size, len(shape)), dtype=np.int64)
        for r in range(self.size):
            _, lsh, _ = self.chunk(shape, split, r)
            out[r] = lsh
        return out

    # ------------------------------------------------------------------ #
    # shardings
    # ------------------------------------------------------------------ #
    def spec(self, ndim: int, split: Optional[int]) -> PartitionSpec:
        if split is None:
            return PartitionSpec()
        split = split % ndim if ndim else 0
        return PartitionSpec(*(self.__axis if i == split else None for i in range(ndim)))

    def sharding(self, ndim: int, split: Optional[int]) -> NamedSharding:
        """The ``NamedSharding`` realizing ``split`` over this communicator.

        Memoized per ``(ndim, split)`` on the instance: the dispatch layer
        asks for the canonical sharding on EVERY op, and returning the same
        object each time makes the placement-equality checks in
        ``DNDarray._enforce_placement``/``shard`` an identity comparison
        instead of a structural one.
        """
        cache = self.__dict__.setdefault("_sharding_cache", {})
        key = (ndim, split)
        sh = cache.get(key)
        if sh is None:
            sh = cache[key] = NamedSharding(self.__mesh, self.spec(ndim, split))
        return sh

    @staticmethod
    def host_fetch(array) -> "np.ndarray":
        """Fetch a (possibly multi-process) jax array to host memory.

        Single-controller arrays are fully addressable and ``device_get``
        suffices; under multi-process JAX a sharded array's remote shards
        are NOT addressable, so the fetch is an SPMD ``process_allgather``
        (every process must call this together — the same contract the
        reference's gather-to-all has).  Fully-replicated arrays read their
        local replica directly — no collective, so ``if rank == 0: print(x)``
        on replicated data stays legal — PROVIDED this process holds a
        replica: an array on a sub-mesh of purely remote devices is
        "replicated" yet unreadable locally, and must allgather (found by
        the -m mp lane's sub-mesh sweep).

        Fault site ``comm.host_fetch``: transient injected faults are
        retried with short backoff (every process fires the site the same
        number of times — fault countdowns are process-local and the call
        pattern is SPMD, so retries stay collective-aligned).

        Deadline-guarded: under an armed ``comm.deadline(...)`` a fetch
        whose peers never show up (the collective ``process_allgather``
        against a dead rank) raises ``CollectiveTimeoutError`` instead of
        blocking forever — this is the real-world hang point of a dead
        peer, not the staged collectives."""
        from ..utils import faults as _flt  # lazy: core imports before utils
        from ..utils import health as _hlth

        def _fetch():
            _flt.fire("comm.host_fetch")
            if getattr(array, "is_fully_addressable", True) or (
                getattr(array, "is_fully_replicated", False)
                and len(array.addressable_shards) > 0
            ):
                return np.asarray(jax.device_get(array))
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(array, tiled=True))

        return _hlth.guard_blocking(
            lambda: _flt.call_with_retries(
                _fetch, "comm.host_fetch", retries=3, base_delay=0.02, max_delay=0.5,
                retry_on=(_flt.TransientFault,),
            ),
            "comm.host_fetch",
        )

    @staticmethod
    def host_fetch_all(arrays) -> "list":
        """Batched :meth:`host_fetch` of many (possibly non-addressable)
        arrays in ONE collective: ``process_allgather`` accepts a pytree,
        so a checkpoint of a model with hundreds of cross-process leaves
        costs one round-trip, not one per leaf.  Same contract as
        ``host_fetch``: collective (every process calls together), fault
        site ``comm.host_fetch``, retried, deadline-guarded."""
        from ..utils import faults as _flt
        from ..utils import health as _hlth

        arrays = list(arrays)
        if not arrays:
            return []

        def _fetch():
            _flt.fire("comm.host_fetch")
            if all(getattr(a, "is_fully_addressable", True) for a in arrays):
                return [np.asarray(a) for a in jax.device_get(arrays)]
            from jax.experimental import multihost_utils

            out = multihost_utils.process_allgather(arrays, tiled=True)
            return [np.asarray(o) for o in out]

        return _hlth.guard_blocking(
            lambda: _flt.call_with_retries(
                _fetch, "comm.host_fetch", retries=3, base_delay=0.02, max_delay=0.5,
                retry_on=(_flt.TransientFault,),
            ),
            "comm.host_fetch",
        )

    def shard(self, array: jax.Array, split: Optional[int]) -> jax.Array:
        """Place/constrain ``array`` to the sharding of ``split``.

        Eager: ``device_put`` (no-op if already so sharded).  Traced (inside
        jit): ``with_sharding_constraint``.

        JAX requires the sharded dimension to be divisible by the mesh axis
        size; for ragged shapes the physical placement is left to XLA's
        computation-follows-data propagation and ``split`` remains *logical*
        metadata (SURVEY §7, hard part #1 — padding-free best-effort design).
        """
        if split is not None:
            split = split % array.ndim if array.ndim else None
        if split is not None and (
            array.ndim == 0 or array.shape[split] % self.size != 0
        ):
            return array  # ragged: keep XLA's placement, split stays logical
        sh = self.sharding(array.ndim, split)
        if isinstance(array, jax.core.Tracer):
            return lax.with_sharding_constraint(array, sh)
        if getattr(array, "sharding", None) == sh:
            return array
        if self.n_processes > 1 and getattr(array, "is_fully_addressable", True):
            # multi-process device_put runs multihost assert_equal, whose
            # np.equal makes NaN != NaN — identical NaN-bearing inputs would
            # spuriously fail.  Inputs are SPMD-identical by contract, so
            # build the global array from per-device slices instead (found
            # by the -m mp lane: nansum's ht.array([1, nan, 3]))
            host = np.asarray(array)
            return _array_from_callback(host, sh)
        return jax.device_put(array, sh)

    def pad_shard(self, array: jax.Array, split: int) -> jax.Array:
        """Zero-pad ``array`` along ``split`` to a mesh-divisible extent and
        physically place it on this communicator's sharding.

        This is the ragged-shape ingest path (pad-and-mask, SURVEY §7 hard
        part #1): JAX's ``NamedSharding`` requires the sharded dimension to be
        divisible by the mesh axis size, so non-divisible ("ragged") axes are
        padded to ``ceil(n/p)*p`` with zeros.  The logical extent is carried by
        ``DNDarray.gshape``; the pad region is dead data masked at reduction
        boundaries.  Returns the padded, sharded physical array.
        """
        split = split % array.ndim
        n = array.shape[split]
        pad = self.padded_extent(n) - n
        if pad:
            widths = [(0, pad if i == split else 0) for i in range(array.ndim)]
            array = jnp.pad(array, widths)
        sh = self.sharding(array.ndim, split)
        if isinstance(array, jax.core.Tracer):
            try:
                return lax.with_sharding_constraint(array, sh)
            except Exception:
                return array  # inside a transform where constraints don't apply
        if getattr(array, "sharding", None) == sh:
            return array
        if self.n_processes > 1 and getattr(array, "is_fully_addressable", True):
            # same NaN-vs-assert_equal hazard as shard() (see there)
            host = np.asarray(array)
            return _array_from_callback(host, sh)
        return jax.device_put(array, sh)

    def split_of(self, array: jax.Array) -> Optional[int]:
        """Infer the split axis from a concrete array's sharding (None if replicated)."""
        sh = getattr(array, "sharding", None)
        if not isinstance(sh, NamedSharding):
            return None
        for i, p in enumerate(sh.spec):
            names = p if isinstance(p, tuple) else (p,)
            if self.__axis in [n for n in names if n]:
                return i
        return None

    # ------------------------------------------------------------------ #
    # redistribution — the reference's Alltoallv-based resplit_
    # ------------------------------------------------------------------ #
    def resplit(
        self,
        array: jax.Array,
        split: Optional[int],
        donate: bool = False,
        memory_budget: Optional[int] = None,
    ) -> jax.Array:
        """Redistribute a global array to a new split axis.

        Across chips the change of sharding is ONE compiled program: a
        jitted identity whose ``out_shardings`` is ``self.sharding(ndim,
        split)`` (``redistribution.identity_program``), which XLA lowers to
        an all-to-all over ICI for k→j, an all-gather for k→None and a local
        slice for None→k — the memory-efficient reshard of arXiv 2112.01075,
        what the reference does by hand with derived datatypes +
        ``Alltoallv`` in ``DNDarray.resplit_`` (SURVEY §3.3).  It is built
        once per ``(shape, dtype, src split, dst split, donate)`` in this
        communicator's program table (``_cache.cached_program``) and called
        through ``_cache.launch``.  ``jax.device_put(array, sharding)`` is
        NOT that: on a TPU v5e 2x2 the runtime moved 3.125 GiB in 6.7 s
        (0.5 GB/s) of synchronous host work with every chip idle (PERF.md,
        finding 4, PR 22).

        Whether the program engages is read off the input, there is no
        switch: the array is concrete, the communicator spans more than one
        device in one process, the array carries this communicator's
        canonical sharding of its current split, and the target extent
        divides by ``size``.  Everything else goes through :meth:`shard`:
        tracers (``with_sharding_constraint``), ragged targets (returned as
        they are), multi-process meshes (host assembly), arrays that are not
        on the mesh yet, and a one-device communicator, where the
        ``device_put`` between two shardings of one device is cheaper than a
        launch.

        ``memory_budget`` (bytes; ``None`` → the process default set via
        ``heat_tpu.set_redistribution_budget()`` / ``HEAT_TPU_RESPLIT_BUDGET``)
        bounds the bytes moved per step: when the transition is tileable and
        the array exceeds the budget, the transfer runs as the chunked
        pipeline of ``core.redistribution`` — K tiled all-to-alls along a
        non-split axis, each ≤ budget bytes, destination written in place,
        transient memory ≤ budget + one tile beyond source + destination.
        K=1 (or no budget) degenerates to the monolithic program below.

        ``donate=True`` (the in-place ``resplit_`` path) builds the program
        with ``donate_argnums=(0,)``: the source buffer is handed to the
        transfer and freed as soon as the all-to-all has consumed it, so
        peak memory stays at ~one copy instead of two.  The caller must not
        use ``array`` afterwards.  Where the program does not engage,
        donation frees nothing the plain path does not, and ``donate`` is
        ignored.

        Telemetry: every resharding call counts under
        ``comm.resplit.calls``/``.bytes`` (the all-to-all moves (p-1)/p of
        the GLOBAL payload — the known hot spot of redistribution traffic;
        a chunked transfer accounts per tile, summing to the identical
        total), plus ``comm.resplit.tiles``/``.peak_tile_bytes`` for the
        plan shape and ``comm.resplit.compiled`` for every call that took
        the monolithic program, and the eager transfer runs under a
        ``comm.resplit`` span (stat ``path``: ``"program"`` or
        ``"device_put"``) when telemetry is enabled or a profile records
        (there as ``ht.comm.resplit``, holding the program's
        ``ht.dispatch.launch``).  A no-op call (the array already carries the
        target sharding) moves nothing and is NOT counted — defensive
        resplit calls must not inflate the traffic metric.
        """
        if self._already_placed(array, split):
            return array
        from . import redistribution as _redist

        plan = _redist.make_plan(self, array, split, memory_budget)
        if plan is not None and plan.n_tiles > 1:
            return self.resplit_tiled(array, split, donate=donate, _plan=plan)
        prog, src_split = self._resplit_program(array, split, donate)
        self._account(
            "resplit",
            array,
            (self.size - 1) / self.size,
            src_split=src_split,
            dst_split=split,
        )
        tel = _telemetry()
        tel.counter_inc("comm.resplit.tiles", 1)
        nbytes = _payload_nbytes(array)
        with tel.span(
            "comm.resplit",
            split=split,
            donate=donate,
            nbytes=nbytes,
            tiles=1,
            path="device_put" if prog is None else "program",
        ):
            ml = _MEMLEDGER
            src_cat = ml.category_of(array) if ml is not None else None
            try:
                if ml is not None:
                    # the mem.alloc fault site: chaos CI injects a
                    # deterministic allocation failure ahead of the transfer
                    ml.alloc_check(nbytes, "comm.resplit")
                if prog is None:
                    out = self.shard(array, split)
                else:
                    out = (_redist.launch_quiet if donate else launch)(prog, array)
                    tel.counter_inc("comm.resplit.compiled", 1)
                    if ml is not None and donate:
                        # consumed only AFTER a successful donating transfer:
                        # a RESOURCE_EXHAUSTED out of the program must still
                        # find the in-flight source in the OOM dump (it is
                        # typically the dominant buffer).  Metadata-only id
                        # lookup, not a buffer read.
                        ml.consume(array)
            except Exception as e:
                if ml is not None:
                    ml.note_oom(e, "comm.resplit", nbytes)
                raise
            if ml is not None:
                # the output inherits the source's category (a resplit moves
                # a buffer, it does not change what the buffer IS)
                ml.register(out, op="resplit", site="resplit", category=src_cat)
            if _RESPLIT_CHECK is not None:
                _RESPLIT_CHECK(out, self, split, where="comm.resplit")
            return out

    def resplit_tiled(
        self,
        array: jax.Array,
        split: Optional[int],
        memory_budget: Optional[int] = None,
        donate: bool = False,
        _plan=None,
    ) -> jax.Array:
        """Explicit tiled-redistribution entry: stream ``array`` to ``split``
        in budget-bounded tiles (``core.redistribution.execute_plan``).

        ``resplit`` routes here whenever a budget yields K>1; calling it
        directly forces the planner with ``memory_budget`` and degenerates
        to :meth:`resplit` when the transition is not tileable.  Byte
        accounting happens PER TILE at the executor's staging points (one
        ``_account_bytes`` per tile — telescoped so the ``comm.resplit.bytes``
        total is identical to the monolithic path's), which also gives every
        tile the ``comm.collective`` fault site and ``comm.deadline``
        refusal/watchdog semantics — a hung tile trips the deadline instead
        of wedging the plan."""
        from . import redistribution as _redist

        plan = _plan
        if plan is None:
            if self._already_placed(array, split):
                return array
            plan = _redist.make_plan(self, array, split, memory_budget)
        if plan is None or plan.n_tiles <= 1:
            return self.resplit(array, split, donate=donate, memory_budget=0)
        tel = _telemetry()
        nbytes = _payload_nbytes(array)
        with tel.span(
            "comm.resplit",
            split=split,
            donate=donate,
            nbytes=nbytes,
            tiles=plan.n_tiles,
            tile_axis=plan.tile_axis,
            budget=plan.budget,
        ):
            ml = _MEMLEDGER
            src_cat = ml.category_of(array) if ml is not None else None
            try:
                out = _redist.execute_plan(self, array, plan, donate=donate)
            except Exception as e:
                if ml is not None:
                    # the per-tile alloc_check inside execute_plan (or a
                    # real RESOURCE_EXHAUSTED mid-plan) lands here: dump
                    # the ledger with the failed tile's request size
                    ml.note_oom(e, "comm.resplit_tiled", plan.max_tile_bytes)
                raise
            if ml is not None:
                # the finished destination is no longer a transient: it IS
                # the moved array, carrying its source's category
                ml.reclassify(
                    out, op="resplit",
                    category=src_cat or "activation", site="resplit",
                )
            if _RESPLIT_CHECK is not None:
                _RESPLIT_CHECK(out, self, split, where="comm.resplit_tiled")
            return out

    def _already_placed(self, array, split: Optional[int]) -> bool:
        """True when ``array`` is concrete and already carries exactly the
        canonical sharding of ``split`` — a resplit of it moves no bytes
        (the same early-return condition ``shard``/the donate path apply)."""
        if isinstance(array, jax.core.Tracer) or not isinstance(array, jax.Array):
            return False
        if split is not None:
            split = split % array.ndim if array.ndim else None
        if split is not None and (
            array.ndim == 0 or array.shape[split] % self.size != 0
        ):
            return False  # ragged: placement is XLA's, not the canonical one
        return getattr(array, "sharding", None) == self.sharding(array.ndim, split)

    def _resplit_program(self, array, split: Optional[int], donate: bool):
        """``(program, src_split)`` of the monolithic reshard of ``array`` to
        ``split``; ``program`` is None where :meth:`shard` moves it instead.
        Decided by what the input shows (see :meth:`resplit`): concrete, more
        than one device in one process, on this communicator's canonical
        sharding, target not ragged."""
        if isinstance(array, jax.core.Tracer) or not isinstance(array, jax.Array):
            return None, None
        src_split = self.split_of(array)
        if self.size == 1 or self.n_processes > 1:
            return None, src_split  # one device: shard()'s device_put is cheaper
        ndim = array.ndim
        if split is not None:
            if ndim == 0 or array.shape[split % ndim] % self.size != 0:
                return None, src_split  # ragged: split stays logical
            split = split % ndim
        from . import redistribution as _redist

        if not _redist.on_mesh(self, array, src_split):
            return None, src_split
        # keyed on the signature, never the array: a job's fresh result of
        # the same shape must be a hit
        key = (
            "resplit", "mono", tuple(array.shape), str(array.dtype),
            src_split, split, bool(donate),
        )
        dst_sh = self.sharding(ndim, split)
        return (
            cached_program(
                self, key, lambda: _redist.identity_program(dst_sh, donate)
            ),
            src_split,
        )

    # ------------------------------------------------------------------ #
    # functional collectives — valid ONLY inside shard_map over this mesh.
    # These carry the MPI names for discoverability by reference users.
    # ------------------------------------------------------------------ #
    # mesh size above which gather-based collectives warn (module-level so
    # tests can lower it; 8 ≈ one host's worth of chips)
    GATHER_WARN_THRESHOLD = 8

    def _account(
        self,
        name: str,
        x,
        factor: float,
        src_split: Optional[int] = None,
        dst_split: Optional[int] = None,
    ) -> None:
        """Byte accounting of one staged collective: ``comm.<name>.calls``
        += 1 and ``comm.<name>.bytes`` += per-shard payload nbytes × the
        collective's algorithmic traffic factor (the wire cost per shard in
        payload units — factor table in design.md "Telemetry & metrics").

        Counted at STAGING (trace) time: a cached executable's replays never
        re-enter these Python wrappers, so ``calls`` counts distinct staged
        collectives per compilation — a collective inside ``lax.scan``
        counts once however many iterations run.  Derived collectives
        (``Reduce``, ``Scatter``) account under the primitive they are
        built from (``Allreduce``, ``Bcast``).

        Health hooks ride the same choke point: fault site
        ``comm.collective`` fires here (delay/hang model a slow or dead
        peer at staging), and an armed :meth:`deadline` both refuses to
        stage more work once blown AND catches an injected staging hang —
        under a deadline the fire runs inside ``guard_blocking``, so a
        ``hang=`` injection trips ``CollectiveTimeoutError`` exactly like
        a hang in ``Wait`` would, instead of wedging the caller's thread."""
        self._account_bytes(
            name,
            int(round(_payload_nbytes(x) * factor)),
            x=x,
            src_split=src_split,
            dst_split=dst_split,
        )

    def _account_bytes(
        self,
        name: str,
        wire_bytes: int,
        x=None,
        src_split: Optional[int] = None,
        dst_split: Optional[int] = None,
    ) -> None:
        """The staging choke point itself, taking pre-computed WIRE bytes:
        :meth:`_account` (payload × factor) and the tiled-resplit executor
        (telescoped per-tile bytes, ``core.redistribution.execute_plan``)
        both land here, so fault injection, deadline refusal, byte
        accounting AND the flight-recorder seq stamp cover every staged
        collective — monolithic or per-tile — through one code path.

        The stamp is written FIRST, before the fault site fires: a hang
        injected (or suffered) at staging leaves the collective it hung on
        as the rank's last ring record — "stuck AT seq N op X", which is
        exactly what ``scripts/postmortem.py`` names."""
        if _FLIGHTREC is not None:
            _FLIGHTREC.record_collective(name, wire_bytes, x, src_split, dst_split)
        from ..utils import faults as _flt  # lazy: core imports before utils

        hlth = _health()
        if hlth.active_deadline() is None:
            _flt.fire("comm.collective")
        else:
            # checks expiry first (raises CollectiveTimeoutError with this
            # site name), then runs the fire on the watchdog thread
            hlth.guard_blocking(
                lambda: _flt.fire("comm.collective"), f"comm.{name}"
            )
        _telemetry().account_collective(name, wire_bytes)

    def _warn_gather_based(self, name: str) -> None:
        """Perf-trap warning (reference: ``warnings.warn`` on implicit-comm
        traps, SURVEY §5.5): this collective is implemented via all_gather, so
        every shard materializes p× the buffer — fine at p≤8, a memory trap at
        pod scale.  Warned at trace time.  Every call additionally counts
        under ``comm.gather_fallback.<name>`` so slow-path collective usage
        is visible in ``telemetry.report()`` even below the warn threshold
        (where the one-shot warning stays silent)."""
        from ..utils import profiler as _profiler

        _profiler.counter_inc(f"comm.gather_fallback.{name}")
        if self.size > Communication.GATHER_WARN_THRESHOLD:
            warnings.warn(
                f"Communication.{name} is gather-based: each shard holds "
                f"size×buffer = {self.size}× the payload. At this mesh size "
                "prefer psum/reduce_scatter formulations.",
                stacklevel=3,
            )

    def Allreduce(self, x, op: str = "sum"):
        p = self.size
        # prod is realized as a log-p prefix scan + one masked psum — its
        # true wire cost, accounted here ONCE (the shared _inclusive_scan
        # helper deliberately does no accounting of its own)
        factor = 2.0 * (p - 1) / p
        if op == "prod":
            factor += float(max(p - 1, 0).bit_length())
        self._account("Allreduce", x, factor)
        ops = {
            "sum": lax.psum,
            "max": lax.pmax,
            "min": lax.pmin,
            "mean": lax.pmean,
        }
        if op in ("prod", "land", "lor"):
            if op == "prod":
                # sign/zero-safe product in O(1) memory: inclusive-scan
                # product via log-p recursive doubling, then broadcast the
                # last shard's total with a masked psum (no all_gather)
                inc = self._inclusive_scan(x, jnp.multiply, unit=1)
                last = jnp.where(
                    lax.axis_index(self.__axis) == self.size - 1,
                    inc,
                    jnp.zeros_like(inc),
                )
                # psum promotes bool/small ints — restore the caller's dtype
                return lax.psum(last, self.__axis).astype(x.dtype)
            if op == "land":
                return lax.pmin(x.astype(jnp.int32), self.__axis).astype(jnp.bool_)
            return lax.pmax(x.astype(jnp.int32), self.__axis).astype(jnp.bool_)
        return ops[op](x, self.__axis)

    def hierarchical_allreduce(self, x, op: str = "sum", domains: Optional[int] = None):
        """Two-level allreduce over this communicator's axis (valid only
        inside ``shard_map``, like ``Allreduce``): reduce-scatter within
        each of ``domains`` contiguous process subgroups (the fast tier),
        cross-domain exchange of the 1/i shard (the slow tier — the only
        traffic that crosses domains), allgather back (arXiv 2004.09362).

        ``domains=None`` derives the slow-domain count from the process
        topology (one domain per host process); when the world has one
        domain — or the hierarchy does not divide the axis — this falls
        back to the flat allreduce.  ``op`` is ``"sum"`` or ``"mean"``.

        Accounting: every stage routes through ``_account_bytes`` under
        ``comm.allreduce`` — per-stage seq stamps in the flight ring, the
        ``comm.collective`` fault site, deadline enforcement — with the
        stage factors telescoping exactly to the flat ring total:
        (i−1)/i + 2(d−1)/(d·i) + (i−1)/i = 2(p−1)/p, so
        ``comm.allreduce.bytes`` for the K staged records reconciles
        against the monolithic accounting to the byte."""
        if op not in ("sum", "mean"):
            raise ValueError(f"hierarchical_allreduce supports sum/mean, got {op!r}")
        from . import collectives as _coll

        p = self.size
        d = _coll._derive_domains(self, domains)
        factors = _coll._hier_stage_factors(p, d)
        if factors is None:
            # single domain: the hierarchy is the flat ring
            self._account_bytes(
                "allreduce",
                int(round(_payload_nbytes(x) * 2.0 * (p - 1) / p)),
                x=x,
            )
            out = lax.psum(x, self.__axis)
            return out / p if op == "mean" else out
        nbytes = _payload_nbytes(x)
        tele = _coll._Telescope()
        _coll._account_stages(self, tele, nbytes, factors, x=x)
        return _coll._hierarchical_body(x, self.__axis, p, d, mean=(op == "mean"))

    def Allgather(self, x, axis: int = 0, tiled: bool = True):
        self._account("Allgather", x, self.size - 1)
        return lax.all_gather(x, self.__axis, axis=axis, tiled=tiled)

    def Alltoall(self, x, split_axis: int, concat_axis: int):
        self._account("Alltoall", x, (self.size - 1) / self.size)
        return lax.all_to_all(
            x, self.__axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True
        )

    def Bcast(self, x, root: int = 0):
        """Every shard receives shard ``root``'s block.

        O(1)-memory: the non-root shards contribute zeros to a ``psum``, so
        the wire cost is one allreduce of the payload and no shard ever holds
        a p× buffer (the reference Bcasts a single buffer too — this is the
        SPMD-collective realization of the same cost)."""
        p = self.size
        self._account("Bcast", x, 2.0 * (p - 1) / p)
        mine = lax.axis_index(self.__axis) == root
        contrib = jnp.where(mine, x, jnp.zeros_like(x))
        # psum promotes bool to int32 — restore the caller's dtype
        return lax.psum(contrib, self.__axis).astype(x.dtype)

    def Send(self, x, shift: int = 1):
        """Ring shift by ``shift`` (reference Isend/Irecv neighbor exchange)."""
        self._account("Send", x, 1.0)
        n = self.size
        perm = [(i, (i + shift) % n) for i in range(n)]
        return lax.ppermute(x, self.__axis, perm)

    def ReduceScatter(self, x, axis: int = 0):
        self._account("ReduceScatter", x, (self.size - 1) / self.size)
        return lax.psum_scatter(x, self.__axis, scatter_dimension=axis, tiled=True)

    def _inclusive_scan(self, x, combine, unit):
        """Inclusive prefix combine across shards in O(log p) ``ppermute``
        steps (Hillis–Steele recursive doubling), O(1) memory per shard.
        ``unit`` fills the holes of the partial permutation (ranks below the
        stride receive nothing).  No telemetry accounting here: the PUBLIC
        entry points (Scan, Exscan, Allreduce-prod) each account their own
        end-to-end cost — accounting in this shared helper would double-count
        and misattribute (found in review)."""
        idx = lax.axis_index(self.__axis)
        n = self.size
        acc = x
        shift = 1
        while shift < n:
            perm = [(i, i + shift) for i in range(n - shift)]
            recvd = lax.ppermute(acc, self.__axis, perm)
            filled = jnp.where(idx >= shift, recvd, jnp.full_like(recvd, unit))
            acc = combine(acc, filled)
            shift *= 2
        return acc

    def Exscan(self, x):
        """Exclusive prefix sum across shards (reference ``comm.Exscan``).

        O(log p) ``ppermute`` rounds, O(1) memory: the inclusive scan is
        computed by recursive doubling, then shifted one rank down the ring
        (rank 0 receives the empty-sum zero) — exact, unlike
        ``inclusive - x`` which reassociates floats."""
        # ceil(log2 p) doubling rounds + the one-rank down-shift
        self._account("Exscan", x, float(max(self.size - 1, 0).bit_length()) + 1.0)
        inc = self._inclusive_scan(x, jnp.add, unit=0)
        n = self.size
        perm = [(i, i + 1) for i in range(n - 1)]
        shifted = lax.ppermute(inc, self.__axis, perm)
        idx = lax.axis_index(self.__axis)
        return jnp.where(idx > 0, shifted, jnp.zeros_like(shifted))

    def Scan(self, x):
        # ceil(log2 p) recursive-doubling rounds, one payload each
        self._account("Scan", x, float(max(self.size - 1, 0).bit_length()))
        return self._inclusive_scan(x, jnp.add, unit=0)

    def Reduce(self, x, root: int = 0, op: str = "sum"):
        """Reduce to shard ``root``; other shards receive zeros (XLA is SPMD —
        every shard computes; the root-masking preserves MPI semantics)."""
        red = self.Allreduce(x, op)
        mine = lax.axis_index(self.__axis) == root
        return jnp.where(mine, red, jnp.zeros_like(red))

    def Scatter(self, x, root: int = 0, axis: int = 0):
        """Shard ``root``'s block, split along ``axis``, one piece per shard.

        Transient memory = ONE copy of root's buffer per shard (the masked-
        psum Bcast), then the local slice — no p× gather."""
        src = self.Bcast(x, root=root)
        n = self.size
        idx = lax.axis_index(self.__axis)
        piece = src.shape[axis] // n
        return lax.dynamic_slice_in_dim(src, idx * piece, piece, axis=axis)

    def Gather(self, x, root: int = 0, axis: int = 0):
        """All blocks concatenated on shard ``root`` (others receive the same
        buffer zeroed — SPMD equivalence of the MPI rooted gather).

        O(p)-memory by definition (every shard materializes the gathered
        buffer before root-masking); see ``_warn_gather_based``."""
        self._warn_gather_based("Gather")
        self._account("Gather", x, self.size - 1)
        full = lax.all_gather(x, self.__axis, axis=axis, tiled=True)
        mine = lax.axis_index(self.__axis) == root
        return jnp.where(mine, full, jnp.zeros_like(full))

    # nonblocking names: EVERY XLA collective is asynchronously dispatched,
    # so the I* forms are the same ops; Wait == block_until_ready
    Iallreduce = Allreduce
    Iallgather = Allgather
    Ialltoall = Alltoall
    Ibcast = Bcast
    Isend = Send
    Irecv = Send

    @staticmethod
    def Wait(x):
        """Block until a dispatched result is ready (reference MPIRequest.Wait).

        Deadline-guarded: under an armed :meth:`deadline` a wait on a
        collective whose peer died raises ``CollectiveTimeoutError`` (with
        a full stack dump) instead of hanging the process forever — the
        elastic runtime's detection point for a wedged world.  Fault site
        ``comm.collective`` fires inside the guard so an injected hang is
        caught by the watchdog exactly like a real one."""
        from ..utils import faults as _flt

        def _wait():
            _flt.fire("comm.collective")
            return jax.block_until_ready(x)

        return _health().guard_blocking(_wait, "comm.Wait")

    def Barrier(self) -> None:
        """Host-level barrier: forces completion of all enqueued work.
        Deadline-guarded like :meth:`Wait` (same watchdog, same fault
        site)."""
        from ..utils import faults as _flt

        def _barrier():
            _flt.fire("comm.collective")
            tok = jax.device_put(jnp.zeros(()), self.sharding(0, None))
            jax.block_until_ready(tok)

        _health().guard_blocking(_barrier, "comm.Barrier")

    def deadline(self, seconds: float):
        """Arm a collective deadline for the block (``with comm.deadline(30):``).

        Inside it, the blocking waits (:meth:`Wait`, :meth:`Barrier`,
        :meth:`host_fetch`) run under a watchdog that raises
        :class:`heat_tpu.utils.health.CollectiveTimeoutError` — after
        dumping every thread's stack — once the budget is exhausted, and
        collective *staging* points refuse to stage more work past the
        deadline.  A hung Allreduce becomes a catchable error the caller
        (or the supervisor, via process exit) can recover from, instead of
        being indistinguishable from slow progress."""
        return _health().deadline(seconds)

    # convenience: run fn under shard_map over this communicator
    def shard_map(self, fn, in_splits, out_splits, check_vma: bool = False):
        """Wrap ``fn`` in a ``shard_map`` where each argument is split per ``in_splits``.

        ``in_splits``/``out_splits`` are pytrees of ``split`` values (ints or
        None) which are translated to PartitionSpecs over this communicator's
        axis.  The per-shard function sees local blocks and may call the
        collective methods above.
        """
        def is_leaf(s):
            return (
                isinstance(s, PartitionSpec)
                or (isinstance(s, tuple) and len(s) == 2 and isinstance(s[0], int))
            )

        def to_spec(s):
            if isinstance(s, PartitionSpec):
                return s
            return self.spec(s[0], s[1])

        in_specs = jax.tree.map(to_spec, in_splits, is_leaf=is_leaf)
        out_specs = jax.tree.map(to_spec, out_splits, is_leaf=is_leaf)
        return jax.shard_map(
            fn, mesh=self.__mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
        )


# ---------------------------------------------------------------------- #
# world communicator bootstrap
# ---------------------------------------------------------------------- #
_world_cache = {}


def world() -> Communication:
    """The default communicator over the default device's mesh (= ``MPI_WORLD``)."""
    dev = devices.get_device()
    comm = _world_cache.get(dev.device_type)
    if comm is None or comm.mesh is not dev.mesh:
        mesh = dev.mesh
        axis = mesh.axis_names[-1] if "x" not in mesh.axis_names else "x"
        comm = Communication(mesh, axis)
        _world_cache[dev.device_type] = comm
    return comm


_default_comm: Optional[Communication] = None


def _invalidate_default(device=None) -> None:
    global _default_comm
    _default_comm = None
    _world_cache.clear()


def get_comm() -> Communication:
    return _default_comm if _default_comm is not None else world()


def use_comm(comm: Optional[Communication] = None) -> None:
    global _default_comm
    if comm is not None and not isinstance(comm, Communication):
        raise TypeError(f"Expected Communication, got {type(comm)}")
    _default_comm = comm


def sanitize_comm(comm: Optional[Communication]) -> Communication:
    if comm is None:
        return get_comm()
    if isinstance(comm, Communication):
        return comm
    raise TypeError(f"Expected Communication or None, got {type(comm)}")


# reference-name aliases: the class the reference calls MPICommunication is
# this mesh-backed Communication; MPI_WORLD/MPI_SELF resolve lazily so that
# importing the module does not force device initialization
MPICommunication = Communication


def __getattr__(name):
    if name == "MPI_WORLD":
        return world()
    if name == "MPI_SELF":
        import jax
        from jax.sharding import Mesh

        return Communication(Mesh(np.asarray(jax.devices()[:1]), ("x",)), "x")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# the sanitizer may have been armed while this module was still importing
# (if any import path ever makes sanitation load first, its poke would hit
# the half-initialized module and the `_RESPLIT_CHECK = None` line above
# would clobber it) — re-read the flag now that the body is done, same
# defensive pattern as core._operations
import sys as _sys  # noqa: E402

# getattr default: in the hypothetical sanitation-loads-first ordering,
# sanitation would be MID-import here (this import triggered by its own
# top-of-module imports) and checks_enabled not yet defined — treat that as
# "not armed"; sanitation's own env-arming poke runs once it finishes
_san = _sys.modules.get("heat_tpu.core.sanitation")
if _san is not None and getattr(_san, "checks_enabled", lambda: False)():
    _RESPLIT_CHECK = _san.check_placement
# same defensive re-arm for the flight recorder: if utils.flightrec was
# env-armed before this module finished importing, its poke hit the
# half-initialized module and the `_FLIGHTREC = None` line clobbered it
_fr = _sys.modules.get("heat_tpu.utils.flightrec")
if _fr is not None and getattr(_fr, "enabled", lambda: False)():
    _FLIGHTREC = _fr
# and for the memory ledger (HEAT_TPU_MEMLEDGER=1 arms at utils.memledger
# import time, which may precede or follow this module)
_ml = _sys.modules.get("heat_tpu.utils.memledger")
if _ml is not None and getattr(_ml, "enabled", lambda: False)():
    _MEMLEDGER = _ml
del _sys, _san, _fr, _ml
