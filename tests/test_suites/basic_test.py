"""Shared test base (reference: ``heat/core/tests/test_suites/basic_test.py``).

``assert_array_equal`` checks the GLOBAL result against a numpy oracle;
``assert_func_equal`` sweeps a numpy op vs a heat op over shapes × splits —
the reference's distributed-coverage strategy, with the world-size sweep
replaced by the 8-device CPU mesh.
"""

from __future__ import annotations

import numpy as np

import heat_tpu as ht


class TestCase:
    comm = None  # set lazily; mesh exists after jax init

    @classmethod
    def get_comm(cls):
        if cls.comm is None:
            cls.comm = ht.communication.get_comm()
        return cls.comm

    @staticmethod
    def assert_distributed(x):
        """Assert that ``split`` metadata reflects PHYSICAL sharding: the array
        actually lives on every device of its communicator and the sharding
        spec names the split axis.  This is what lets the suite distinguish a
        distributed framework from a single-device one (SURVEY §4: the split
        sweep must check the shard)."""
        from jax.sharding import NamedSharding

        if not isinstance(x, ht.DNDarray) or x.split is None or x.ndim == 0:
            return
        comm = x.comm
        if not comm.is_distributed() or x.shape[x.split] == 0:
            return
        arr = x._parray
        ndev = len(getattr(arr, "sharding", None).device_set) if hasattr(arr, "sharding") else 0
        assert ndev >= comm.size, (
            f"split={x.split} claims distribution over {comm.size} shards but the "
            f"array physically lives on {ndev} device(s) — metadata lies"
        )
        if isinstance(arr.sharding, NamedSharding):
            spec = arr.sharding.spec
            entry = spec[x.split] if x.split < len(spec) else None
            names = entry if isinstance(entry, tuple) else (entry,)
            assert comm.axis in [n for n in names if n], (
                f"split={x.split} but sharding spec {spec} does not shard that axis "
                f"over {comm.axis!r}"
            )

    def assert_array_equal(self, heat_array, expected_array, rtol=1e-5, atol=1e-6):
        if isinstance(expected_array, ht.DNDarray):
            expected_array = expected_array.numpy()
        expected_array = np.asarray(expected_array)
        assert isinstance(heat_array, ht.DNDarray), f"expected DNDarray, got {type(heat_array)}"
        assert tuple(heat_array.shape) == tuple(expected_array.shape), (
            f"global shape mismatch: {heat_array.shape} != {expected_array.shape}"
        )
        got = heat_array.numpy()
        if got.dtype.kind in "fc":
            np.testing.assert_allclose(got.astype(np.float64), expected_array.astype(np.float64), rtol=rtol, atol=atol)
        else:
            np.testing.assert_array_equal(got, expected_array)
        # sharding metadata must be self-consistent AND physically true
        if heat_array.split is not None:
            assert 0 <= heat_array.split < max(heat_array.ndim, 1)
        self.assert_distributed(heat_array)

    def assert_func_equal(
        self,
        shape,
        heat_func,
        numpy_func,
        distributed_result=True,
        heat_args=None,
        numpy_args=None,
        data_types=(np.int32, np.float32),
        low=-10000,
        high=10000,
        splits=None,
    ):
        heat_args = heat_args or {}
        numpy_args = numpy_args or {}
        if splits is None:
            splits = [None] + list(range(len(shape)))
        rng = np.random.default_rng(42)
        for dtype in data_types:
            if np.issubdtype(dtype, np.integer):
                data = rng.integers(low, high, size=shape).astype(dtype)
            else:
                data = rng.uniform(low, high, size=shape).astype(dtype)
            expected = numpy_func(data, **numpy_args)
            for split in splits:
                a = ht.array(data, split=split)
                got = heat_func(a, **heat_args)
                self.assert_array_equal(got, expected, rtol=1e-4, atol=1e-4 * max(1.0, abs(high)))
