"""Runtime metadata sanitizer (HEAT_TPU_CHECKS=1) + the sanitation
metadata-only contract (ISSUE 4).

Three tiers:

1. the sanitizer itself: arming sets the dispatch/resplit slots, armed
   dispatch passes on healthy arrays (all split shapes incl. ragged),
   corrupted metadata is caught with a precise error;
2. the no-value-reads contract: every ``sanitize_*`` function (and the new
   validators) runs with ALL device→host entry points monkeypatched to
   raise — none may trip;
3. env arming: ``HEAT_TPU_CHECKS=1`` in a fresh interpreter arms the hooks
   and survives a round of real ops.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import _hooks, sanitation
from heat_tpu.core.communication import Communication
from heat_tpu.core.dndarray import DNDarray

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def armed():
    was_on = sanitation.checks_enabled()
    sanitation.enable_checks()
    try:
        yield
    finally:
        # restore rather than disarm: under the HEAT_TPU_CHECKS=1 CI lane
        # the rest of the session must stay armed
        if not was_on:
            sanitation.disable_checks()


# ---------------------------------------------------------------------- #
# arming / hooks
# ---------------------------------------------------------------------- #
class TestArming:
    def test_state_matches_environment(self):
        # default off in a plain session; ON when the suite itself runs
        # under the HEAT_TPU_CHECKS=1 CI lane
        want = os.environ.get("HEAT_TPU_CHECKS", "").strip().lower() in (
            "1", "true", "on", "yes",
        )
        assert sanitation.checks_enabled() == want
        assert (_hooks.checks is not None) == want
        assert (_hooks.resplit_check is not None) == want

    def test_poke_roundtrip(self):
        was_on = sanitation.checks_enabled()
        try:
            sanitation.enable_checks()
            assert sanitation.checks_enabled()
            assert _hooks.checks is sanitation.validate_dispatch
            assert _hooks.resplit_check is sanitation.check_placement
            sanitation.disable_checks()
            assert not sanitation.checks_enabled()
            assert _hooks.checks is None
            assert _hooks.resplit_check is None
        finally:
            (sanitation.enable_checks if was_on else sanitation.disable_checks)()

    def test_check_is_identity_when_disabled(self):
        if sanitation.checks_enabled():
            pytest.skip("suite is running with HEAT_TPU_CHECKS=1")
        x = ht.ones(4)
        assert sanitation.check(x, "test") is x

    @pytest.mark.slow  # fresh-interpreter jax import ~40s; the quick lane's
    # budget can't carry it, and the checks-tier1 CI lane proves env arming
    # end-to-end anyway (whole suite under HEAT_TPU_CHECKS=1)
    def test_env_arming_fresh_interpreter(self):
        out = subprocess.run(
            [sys.executable, "-c", (
                "import heat_tpu as ht\n"
                "from heat_tpu.core import _hooks, sanitation\n"
                "assert sanitation.checks_enabled()\n"
                "assert _hooks.checks is sanitation.validate_dispatch\n"
                "assert _hooks.resplit_check is sanitation.check_placement\n"
                "x = ht.arange(16, dtype=ht.float32, split=0)\n"
                "y = ((x + 1.0) * 2.0).sum()\n"
                "r = ht.arange(101, dtype=ht.float32, split=0) * 3.0\n"
                "print('ARMED-OK', float(y.numpy()), float(r.sum().numpy()))\n"
            )],
            env={**os.environ, "HEAT_TPU_CHECKS": "1", "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=240, cwd=REPO,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "ARMED-OK" in out.stdout


# ---------------------------------------------------------------------- #
# armed dispatch on healthy arrays
# ---------------------------------------------------------------------- #
class TestArmedDispatch:
    def test_ops_pass_all_split_shapes(self, armed):
        for split in (None, 0):
            x = ht.arange(16, dtype=ht.float32, split=split)
            np.testing.assert_allclose(
                ((x + 1.0) * 2.0).sum().numpy(), np.sum((np.arange(16.0) + 1) * 2)
            )
        m = ht.reshape(ht.arange(64, dtype=ht.float32, split=0), (8, 8))
        assert m.cumsum(0).shape == (8, 8)
        assert float(m.max().numpy()) == 63.0

    def test_ragged_ops_pass(self, armed):
        x = ht.arange(101, dtype=ht.float32, split=0)
        np.testing.assert_allclose((x * 2.0).sum().numpy(), np.arange(101.0).sum() * 2)

    def test_factory_and_resplit_boundaries_pass(self, armed):
        m = ht.array(np.arange(24.0, dtype=np.float32).reshape(6, 4), split=0)
        m2 = m.resplit(1)
        assert m2.split == 1
        m.resplit_(1)
        assert m.split == 1

    def test_out_path_validated(self, armed):
        x = ht.ones((4, 4), split=0)
        out = ht.zeros((4, 4), split=0)
        ht.add(x, x, out=out)
        np.testing.assert_allclose(out.numpy(), 2.0)


# ---------------------------------------------------------------------- #
# corruption detection
# ---------------------------------------------------------------------- #
class TestValidator:
    def test_non_dndarray_rejected(self):
        with pytest.raises(sanitation.MetadataError, match="expected DNDarray"):
            sanitation.validate_metadata(np.ones(3))

    def test_wrong_gshape_caught(self):
        x = ht.arange(16, dtype=ht.float32)
        bad = DNDarray._from_parts(x._jarray, (17,), x.dtype, None, x.device, x.comm)
        with pytest.raises(sanitation.MetadataError, match="physical shape"):
            sanitation.validate_metadata(bad, "unit")

    def test_wrong_dtype_caught(self):
        x = ht.arange(16, dtype=ht.float32)
        bad = DNDarray._from_parts(x._jarray, (16,), ht.int32, None, x.device, x.comm)
        with pytest.raises(sanitation.MetadataError, match="dtype metadata"):
            sanitation.validate_metadata(bad)

    def test_split_out_of_range_caught(self):
        x = ht.arange(16, dtype=ht.float32)
        bad = DNDarray._from_parts(x._jarray, (16,), x.dtype, 3, x.device, x.comm)
        with pytest.raises(sanitation.MetadataError, match="split 3 out of range"):
            sanitation.validate_metadata(bad)

    def test_wrong_sharding_caught(self):
        comm = ht.communication.get_comm()
        if comm.size < 2:
            pytest.skip("needs a multi-device mesh")
        n = comm.size
        m = ht.array(np.arange(float(n * n), dtype=np.float32).reshape(n, n), split=0)
        # claim split=1 on an array physically sharded along axis 0
        lying = DNDarray._from_parts(m._parray, (n, n), m.dtype, 1, m.device, m.comm)
        with pytest.raises(sanitation.MetadataError, match="canonical sharding"):
            sanitation.validate_metadata(lying, "unit")

    def test_bad_pad_caught(self):
        comm = ht.communication.get_comm()
        if comm.size < 2:
            pytest.skip("needs a multi-device mesh")
        x = ht.arange(101, dtype=ht.float32, split=0)
        assert x._pad > 0  # ragged on any multi-device mesh
        # corrupt the logical extent: pad no longer matches padded_extent
        bad = DNDarray._from_parts(x._parray, x.gshape, x.dtype, 0, x.device, x.comm)
        bad._DNDarray__pad = x._pad + 1  # heatlint: disable=HT106 (test corrupts on purpose)
        with pytest.raises(sanitation.MetadataError, match="pad"):
            sanitation.validate_metadata(bad)

    def test_validator_returns_input(self):
        x = ht.ones((4,))
        assert sanitation.validate_metadata(x) is x

    def test_cross_rank_single_process_passes(self):
        x = ht.arange(8, dtype=ht.float32, split=0)
        assert sanitation.assert_cross_rank_consistent(x, tag="unit") is x


# ---------------------------------------------------------------------- #
# the metadata-only contract: NO sanitize_*/validator may read values
# ---------------------------------------------------------------------- #
class TestNoValueReads:
    @pytest.fixture
    def no_value_reads(self, monkeypatch):
        """Every device→host value-read entry point raises; metadata-only
        code must never trip one."""

        def _boom(*a, **k):
            raise AssertionError("device→host value read inside sanitation!")

        real_asarray = np.asarray

        def guarded_asarray(obj, *a, **k):
            if isinstance(obj, jax.Array):
                _boom()
            return real_asarray(obj, *a, **k)

        monkeypatch.setattr(jax, "device_get", _boom)
        monkeypatch.setattr(np, "asarray", guarded_asarray)
        monkeypatch.setattr(Communication, "host_fetch", staticmethod(_boom))
        monkeypatch.setattr(DNDarray, "numpy", _boom)
        monkeypatch.setattr(DNDarray, "item", _boom)
        return None

    def test_every_sanitize_function_is_metadata_only(self, no_value_reads):
        x = ht.array(np.arange(24.0, dtype=np.float32).reshape(6, 4), split=0)
        y = ht.ones((6, 4), dtype=ht.float32, split=0)
        rep = ht.ones((6, 4), dtype=ht.float32)  # replicated

        sanitation.sanitize_in(x)
        assert sanitation.sanitize_infinity(x) > 0
        assert sanitation.sanitize_in_tensor(x) is x._jarray
        sanitation.sanitize_in_tensor([1.0, 2.0])
        sanitation.sanitize_lshape(x, x._jarray)
        sanitation.sanitize_out(y, (6, 4), 0, x.device)
        sanitation.sanitize_distribution(y, target=x)
        # distribution repair (replicated -> split) is a device_put, NOT a
        # value read — it must survive the patched entry points too
        sanitation.sanitize_distribution(rep, target=x)
        sanitation.sanitize_sequence([1, 2, 3])
        sanitation.sanitize_sequence((1, 2, 3))
        sanitation.sanitize_sequence(ht.ones(3))
        sanitation.scalar_to_1d(ht.array(np.float32(2.0)))

    def test_out_resplit_repair_is_metadata_only(self, no_value_reads, recwarn):
        if ht.communication.get_comm().n_processes > 1:
            pytest.skip("multi-process placement goes through host assembly")
        x = ht.array(np.arange(24.0, dtype=np.float32).reshape(6, 4), split=0)
        out = ht.ones((6, 4), dtype=ht.float32)  # wrong split: triggers resplit_
        sanitation.sanitize_out(out, (6, 4), 0, x.device)
        assert out.split == 0

    def test_runtime_validators_are_metadata_only(self, no_value_reads):
        x = ht.array(np.arange(24.0, dtype=np.float32).reshape(6, 4), split=0)
        sanitation.validate_metadata(x, "contract")
        sanitation.check_placement(x._parray, x.comm, x.split, "contract")
        sanitation.assert_cross_rank_consistent(x, "contract")
        rag = ht.arange(101, dtype=ht.float32, split=0)
        sanitation.validate_metadata(rag, "contract-ragged")

    def test_armed_dispatch_is_metadata_only(self, no_value_reads, armed):
        # a full armed dispatch round (fast path + general path + factory)
        # must not read a single value either
        x = ht.arange(16, dtype=ht.float32, split=0)
        _ = (x + 1.0) * 2.0
        _ = x.sum()
        _ = x.cumsum(0)
