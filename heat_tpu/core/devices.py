"""Device and mesh handles — the TPU-native seam of the framework.

In the reference (``heat/core/devices.py``) a ``Device`` names a torch device
(``cpu``/``gpu``) and each MPI rank pins itself to one accelerator.  In the
TPU-native design a ``Device`` instead names a *platform* (``tpu``/``cpu``/
``gpu``) together with the :class:`jax.sharding.Mesh` built over all visible
devices of that platform.  Arrays live as globally-shaped, sharded
``jax.Array``s on that mesh; there is no per-rank device pinning because JAX's
single-controller SPMD model addresses every chip at once.

Public parity surface: ``ht.cpu``, ``ht.gpu`` (alias of the accelerator
platform), ``ht.use_device``, ``ht.get_device``, ``sanitize_device``; new
TPU-native handles: ``ht.tpu``, ``use_mesh``, ``get_default_mesh``.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = [
    "Device",
    "cpu",
    "get_device",
    "sanitize_device",
    "use_device",
    "use_mesh",
    "get_default_mesh",
    "make_mesh",
]


class Device:
    """Handle for a compute platform and the device mesh spanned over it.

    Parameters
    ----------
    device_type : str
        Platform name: ``'cpu'``, ``'gpu'`` or ``'tpu'``.
    device_id : int
        Kept for API parity with the reference; always 0 (the mesh addresses
        all devices of the platform collectively).
    """

    def __init__(self, device_type: str, device_id: int = 0):
        self.__device_type = device_type
        self.__device_id = device_id
        self.__mesh: Optional[Mesh] = None

    @property
    def device_type(self) -> str:
        return self.__device_type

    @property
    def device_id(self) -> int:
        return self.__device_id

    @property
    def jax_devices(self):
        """All JAX devices of this platform (raises if platform unavailable)."""
        return jax.devices(self.__device_type)

    @property
    def mesh(self) -> Mesh:
        """The (lazily built, cached) 1-D mesh over all devices of the platform."""
        if self.__mesh is None:
            self.__mesh = make_mesh(platform=self.__device_type)
        return self.__mesh

    def set_mesh(self, mesh: Mesh) -> None:
        self.__mesh = mesh

    @property
    def available(self) -> bool:
        try:
            return len(jax.devices(self.__device_type)) > 0
        except RuntimeError:
            return False

    def __eq__(self, other) -> bool:
        if isinstance(other, Device):
            return self.__device_type == other.device_type
        if isinstance(other, str):
            return self.__device_type == _canonical_name(other)
        return NotImplemented

    def __hash__(self) -> int:
        return hash(self.__device_type)

    def __repr__(self) -> str:
        return f"device({self.__str__()!r})"

    def __str__(self) -> str:
        return f"{self.__device_type}:{self.__device_id}"


def make_mesh(
    platform: Optional[str] = None,
    shape: Optional[tuple] = None,
    axis_names: tuple = ("x",),
) -> Mesh:
    """Build a mesh over the devices of ``platform``.

    Default is a 1-D mesh named ``('x',)`` over all devices — the direct
    analogue of the reference's ``MPI_WORLD`` world communicator.  Hierarchical
    meshes (e.g. ``('dcn', 'ici')`` for DASO, SURVEY §5.8) are produced by
    passing an explicit ``shape``/``axis_names``.
    """
    devs = jax.devices(platform) if platform else jax.devices()
    if shape is None:
        shape = (len(devs),)
    arr = np.asarray(devs).reshape(shape)
    return Mesh(arr, axis_names[: arr.ndim])


def _canonical_name(name: str) -> str:
    name = name.lower()
    aliases = {"cuda": "gpu"}
    return aliases.get(name, name)


# Platform singletons.  `gpu` / `tpu` are created on demand because the
# platforms may be absent; `cpu` always exists.
cpu = Device("cpu")
_devices = {"cpu": cpu}

# default device: prefer the accelerator jax itself defaults to
__default_device: Optional[Device] = None


def _platform_singleton(name: str) -> Device:
    name = _canonical_name(name)
    if name not in _devices:
        dev = Device(name)
        if not dev.available:
            raise ValueError(f"Platform '{name}' has no available devices")
        _devices[name] = dev
    return _devices[name]


def __getattr__(name):  # module-level: ht.core.devices.gpu / .tpu resolve lazily
    if name in ("gpu", "tpu"):
        return _platform_singleton(name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def get_device() -> Device:
    """The current default :class:`Device`."""
    global __default_device
    if __default_device is None:
        backend = jax.default_backend()
        __default_device = _platform_singleton(_canonical_name(backend))
    return __default_device


def platform_of(x) -> str:
    """Platform of the devices ``x`` lives on.  A tracer has no devices
    yet: it gets the default device's platform, over whose mesh the
    surrounding program is sharded."""
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).platform
    return get_device().device_type


def use_device(device: Union[str, Device, None] = None) -> None:
    """Set the default device, cf. ``ht.use_device('gpu')`` in the reference."""
    global __default_device
    if device is None:
        return
    __default_device = sanitize_device(device)


def sanitize_device(device: Union[str, Device, None]) -> Device:
    """Resolve ``device`` to a :class:`Device` (default device for ``None``)."""
    if device is None:
        return get_device()
    if isinstance(device, Device):
        return device
    if isinstance(device, str):
        return _platform_singleton(device)
    raise ValueError(f"Unknown device, must be 'cpu', 'gpu' or 'tpu', got {device}")


def use_mesh(mesh: Mesh, device: Union[str, Device, None] = None) -> None:
    """Install ``mesh`` as the mesh of ``device`` (default device if None).

    This is the TPU-native analogue of selecting a communicator: subsequent
    factories build arrays sharded over ``mesh``'s first axis by default.
    """
    dev = sanitize_device(device)
    dev.set_mesh(mesh)
    # invalidate cached world communication handles built on the old mesh
    from . import communication

    communication._invalidate_default(dev)


def get_default_mesh() -> Mesh:
    return get_device().mesh
