"""Round-3 numpy-parity batch 4: sorting/selection, set ops, gradients,
histograms, factories (windows, index helpers), inner/tensordot, correlate.

Every DNDarray-returning op goes through ``assert_array_equal`` (value vs
numpy oracle AND physical-sharding check) where the result is deterministic.
"""

import numpy as np
import pytest

import heat_tpu as ht

from test_suites.basic_test import TestCase

rng = np.random.default_rng(0)
X = rng.standard_normal((24, 6)).astype(np.float32)
V = rng.standard_normal(24).astype(np.float32)


class TestSortingSelection(TestCase):
    @pytest.mark.parametrize("split", [None, 0])
    def test_argsort_take_partition(self, split):
        a = ht.array(X, split=split)
        av = ht.array(V, split=split)
        self.assert_array_equal(ht.argsort(av), np.argsort(V, stable=True))
        self.assert_array_equal(ht.take(a, [3, 1, 2], axis=0), np.take(X, [3, 1, 2], axis=0))
        idx = np.argsort(X, axis=0)
        self.assert_array_equal(ht.take_along_axis(a, ht.array(idx, split=split), 0), np.take_along_axis(X, idx, 0))
        got = np.sort(ht.partition(av, 5).numpy()[:5])
        np.testing.assert_allclose(got, np.sort(np.partition(V, 5)[:5]))
        self.assert_array_equal(ht.searchsorted(ht.array(np.sort(V)), av), np.searchsorted(np.sort(V), V))

    def test_take_split_bookkeeping(self):
        a = ht.array(X, split=1)
        t = ht.take(a, [0, 2], axis=0)  # take before the split axis
        assert t.split == 1
        self.assert_array_equal(t, np.take(X, [0, 2], axis=0))
        t2 = ht.take(ht.array(X, split=0), 3, axis=0)  # scalar drops the axis
        assert t2.split is None

    def test_selection_ops(self):
        a = ht.array(X, split=0)
        av = ht.array(V, split=0)
        self.assert_array_equal(ht.compress(V > 0, av), np.compress(V > 0, V))
        self.assert_array_equal(ht.extract(a > 0, a), np.extract(X > 0, X))
        self.assert_array_equal(ht.select([a > 1, a < -1], [a, -a], default=0.0), np.select([X > 1, X < -1], [X, -X], 0.0))
        self.assert_array_equal(ht.lexsort([av, ht.array(V[::-1].copy(), split=0)]), np.lexsort([V, V[::-1]]))

    def test_reorder_and_trim(self):
        a = ht.array(X, split=0)
        self.assert_array_equal(ht.rollaxis(a, 1), np.rollaxis(X, 1))
        self.assert_array_equal(ht.resize(a, (5, 7)), np.resize(X, (5, 7)))
        z = np.array([0, 0, 1, 2, 0], np.float32)
        self.assert_array_equal(ht.trim_zeros(ht.array(z)), np.trim_zeros(z))
        self.assert_array_equal(ht.concat([a, a]), np.concatenate([X, X]))
        self.assert_array_equal(ht.permute_dims(a), X.T)
        self.assert_array_equal(ht.matrix_transpose(a), X.T)
        self.assert_array_equal(ht.argwhere(a > 0.5), np.argwhere(X > 0.5))

    def test_diag_and_fill(self):
        self.assert_array_equal(ht.diagflat(ht.array(V[:4], split=0)), np.diagflat(V[:4]))
        b = ht.array(X.copy(), split=0)
        ht.fill_diagonal(b, 9.0)
        xb = X.copy()
        np.fill_diagonal(xb, 9.0)
        self.assert_array_equal(b, xb)


class TestSetOps(TestCase):
    def test_all_set_ops(self):
        i1 = np.array([1, 2, 3, 4], np.int32)
        i2 = np.array([3, 4, 5], np.int32)
        a1, a2 = ht.array(i1, split=0), ht.array(i2)
        self.assert_array_equal(ht.union1d(a1, a2), np.union1d(i1, i2))
        self.assert_array_equal(ht.intersect1d(a1, a2), np.intersect1d(i1, i2))
        self.assert_array_equal(ht.setdiff1d(a1, a2), np.setdiff1d(i1, i2))
        self.assert_array_equal(ht.setxor1d(a1, a2), np.setxor1d(i1, i2))
        self.assert_array_equal(ht.isin(a1, i2), np.isin(i1, i2))
        self.assert_array_equal(ht.in1d(a1, i2), np.isin(i1, i2))


class TestNumericalOps(TestCase):
    @pytest.mark.parametrize("split", [None, 0])
    def test_elementwise(self, split):
        a = ht.array(X, split=split)
        self.assert_array_equal(ht.reciprocal(a), np.reciprocal(X))
        self.assert_array_equal(ht.nextafter(a, a + 1), np.nextafter(X, X + 1))
        self.assert_array_equal(ht.fix(a * 3), np.fix(X * 3))
        self.assert_array_equal(ht.around(a * 3), np.around(X * 3))
        self.assert_array_equal(ht.i0(ht.array(V, split=split)), np.i0(V), rtol=1e-3)

    def test_gradient_interp_ediff1d(self):
        a = ht.array(X, split=0)
        av = ht.array(V, split=0)
        self.assert_array_equal(ht.gradient(a, axis=0), np.gradient(X, axis=0))
        for g, w in zip(ht.gradient(a, axis=(0, 1)), np.gradient(X, axis=(0, 1))):
            self.assert_array_equal(g, w)
        with pytest.raises(NotImplementedError):
            ht.gradient(a, axis=0, edge_order=2)
        xp = np.sort(rng.standard_normal(10)).astype(np.float32)
        fp = rng.standard_normal(10).astype(np.float32)
        self.assert_array_equal(ht.interp(av, ht.array(xp), ht.array(fp)), np.interp(V, xp, fp).astype(np.float32))
        self.assert_array_equal(ht.ediff1d(a), np.ediff1d(X))

    def test_nan_cums_and_quantiles(self):
        xn = X.copy()
        xn[2, 1] = np.nan
        an = ht.array(xn, split=0)
        self.assert_array_equal(ht.nancumsum(an, axis=0), np.nancumsum(xn, axis=0))
        self.assert_array_equal(ht.nancumprod(an, axis=0), np.nancumprod(xn, axis=0), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(float(ht.nanmedian(an).numpy()), np.nanmedian(xn), rtol=1e-4)
        np.testing.assert_allclose(float(ht.nanpercentile(an, 30).numpy()), np.nanpercentile(xn, 30), rtol=1e-3)
        np.testing.assert_allclose(float(ht.nanquantile(an, 0.7).numpy()), np.nanquantile(xn, 0.7), rtol=1e-3)
        self.assert_array_equal(ht.fmax(an, ht.array(X, split=0)), np.fmax(xn, X))
        self.assert_array_equal(ht.fmin(an, ht.array(X, split=0)), np.fmin(xn, X))

    def test_histograms(self):
        av = ht.array(V, split=0)
        self.assert_array_equal(ht.histogram_bin_edges(av, 8), np.histogram_bin_edges(V, 8).astype(np.float32), rtol=1e-4)
        h2, _, _ = ht.histogram2d(av, ht.array(V[::-1].copy(), split=0), bins=5)
        wh, _, _ = np.histogram2d(V, V[::-1], bins=5)
        self.assert_array_equal(h2, wh)
        hd, _ = ht.histogramdd(ht.array(X[:, :2], split=0), bins=4)
        whd, _ = np.histogramdd(X[:, :2], bins=4)
        self.assert_array_equal(hd, whd)

    def test_predicates(self):
        a = ht.array(X, split=0)
        assert ht.array_equal(a, ht.array(X)) and not ht.array_equal(a, a[1:])
        assert ht.array_equiv(ht.array(np.ones((1, 6), np.float32)), ht.array(np.ones((3, 6), np.float32)))
        assert not ht.iscomplexobj(a) and ht.isrealobj(a)
        assert not ht.isscalar(a) and ht.isscalar(3.0)
        assert ht.amax(a, axis=None).numpy() == np.amax(X)


class TestFactoriesBatch(TestCase):
    def test_structured(self):
        self.assert_array_equal(ht.identity(5), np.identity(5, np.float32))
        self.assert_array_equal(ht.geomspace(1, 256, 9), np.geomspace(1, 256, 9).astype(np.float32), rtol=1e-4)
        self.assert_array_equal(ht.tri(4, 6, 1), np.tri(4, 6, 1).astype(np.float32))
        self.assert_array_equal(ht.vander(ht.array(V[:5], split=0)), np.vander(V[:5]), rtol=1e-3)
        self.assert_array_equal(ht.indices((3, 4)), np.indices((3, 4)))

    def test_index_helpers(self):
        r, _ = ht.diag_indices(4)
        np.testing.assert_array_equal(r.numpy(), np.diag_indices(4)[0])
        a = ht.array(X[:6, :6], split=0)
        r2, c2 = ht.tril_indices_from(a)
        er2, ec2 = np.tril_indices_from(X[:6, :6])
        np.testing.assert_array_equal(r2.numpy(), er2)
        np.testing.assert_array_equal(c2.numpy(), ec2)
        u = ht.unravel_index(ht.array(np.array([7, 13], np.int32)), (4, 6))
        eu = np.unravel_index(np.array([7, 13]), (4, 6))
        np.testing.assert_array_equal(u[0].numpy(), eu[0])
        rm = ht.ravel_multi_index((ht.array(np.array([1, 2], np.int32)), ht.array(np.array([3, 4], np.int32))), (4, 6))
        np.testing.assert_array_equal(rm.numpy(), np.ravel_multi_index((np.array([1, 2]), np.array([3, 4])), (4, 6)))
        ix = ht.ix_(ht.array(np.array([0, 2], np.int32)), ht.array(np.array([1, 3], np.int32)))
        np.testing.assert_array_equal(ix[0].numpy(), np.ix_(np.array([0, 2]), np.array([1, 3]))[0])

    def test_windows(self):
        for name in ("bartlett", "blackman", "hamming", "hanning"):
            self.assert_array_equal(getattr(ht, name)(16), getattr(np, name)(16).astype(np.float32), rtol=1e-4)
        self.assert_array_equal(ht.kaiser(16, 8.6), np.kaiser(16, 8.6).astype(np.float32), rtol=1e-3)


class TestLinalgBatch(TestCase):
    @pytest.mark.parametrize("split", [None, 0])
    def test_inner_tensordot_vecdot(self, split):
        a = ht.array(X, split=split)
        self.assert_array_equal(ht.inner(a, a), np.inner(X, X), rtol=1e-3, atol=1e-3)
        td = ht.tensordot(a, ht.array(X.T), axes=1)
        self.assert_array_equal(td, np.tensordot(X, X.T, 1), rtol=1e-3, atol=1e-2)
        if split == 0:
            assert td.split == 0  # a's free split axis survives the contraction
        self.assert_array_equal(ht.vecdot(a, a), np.sum(X * X, -1), rtol=1e-3)

    def test_tensordot_contracted_split(self):
        a = ht.array(X, split=1)  # split axis IS contracted
        td = ht.tensordot(a, ht.array(X.T), axes=1)
        assert td.split is None
        self.assert_array_equal(td, np.tensordot(X, X.T, 1), rtol=1e-3, atol=1e-2)


class TestCorrelate(TestCase):
    @pytest.mark.parametrize("mode", ["full", "same", "valid"])
    def test_matches_numpy(self, mode):
        a = rng.standard_normal(40).astype(np.float32)
        v = rng.standard_normal(5).astype(np.float32)
        got = ht.correlate(ht.array(a, split=0), ht.array(v), mode=mode)
        self.assert_array_equal(got, np.correlate(a, v, mode=mode), rtol=1e-4, atol=1e-4)


class TestMopUp(TestCase):
    """Final parity batch: append/astype/copyto, in-place mutators, apply
    helpers, array-API unique quartet and bitwise aliases."""

    def test_append_astype_layout(self):
        a = ht.array(X, split=0)
        self.assert_array_equal(ht.append(a, ht.array(X[:2], split=0), axis=0), np.append(X, X[:2], axis=0))
        self.assert_array_equal(ht.append(a, [1.0, 2.0]), np.append(X, [1.0, 2.0]).astype(np.float32))
        assert ht.astype(a, ht.int32).dtype == ht.int32
        assert ht.ascontiguousarray(a) is a
        assert isinstance(ht.array2string(a), str)
        assert isinstance(ht.array_str(a), str) and isinstance(ht.array_repr(a), str)

    def test_mutators(self):
        b = ht.array(X.copy(), split=0)
        idx = np.argsort(X, axis=0)[:1]
        ht.put_along_axis(b, ht.array(idx.astype(np.int32)), 0.0, 0)
        xb = X.copy()
        np.put_along_axis(xb, idx, 0.0, 0)
        self.assert_array_equal(b, xb)
        c = ht.array(X.copy(), split=0)
        ht.put(c, [0, 5], [9.0, 8.0])
        xc = X.copy()
        np.put(xc, [0, 5], [9.0, 8.0])
        self.assert_array_equal(c, xc)
        d = ht.array(X.copy(), split=0)
        vals = np.array([7.0, 6.0], np.float32)
        ht.place(d, X > 0.5, vals)
        xd = X.copy()
        np.place(xd, X > 0.5, vals)
        self.assert_array_equal(d, xd)
        e = ht.array(X.copy(), split=0)
        ht.putmask(e, X > 0.5, ht.array(X * 10, split=0))
        xe = X.copy()
        np.putmask(xe, X > 0.5, X * 10)
        self.assert_array_equal(e, xe)
        f = ht.array(X.copy(), split=0)
        ht.copyto(f, 0.0, where=ht.array(X > 0, split=0))
        xf = X.copy()
        np.copyto(xf, 0.0, where=X > 0)
        self.assert_array_equal(f, xf)

    def test_apply_helpers(self):
        import jax.numpy as jnp

        a = ht.array(X, split=0)
        self.assert_array_equal(
            ht.apply_along_axis(lambda r: r - r.mean(), 0, a),
            np.apply_along_axis(lambda r: r - r.mean(), 0, X), rtol=1e-5, atol=1e-6,
        )
        self.assert_array_equal(ht.apply_over_axes(jnp.sum, a, [0]), np.apply_over_axes(np.sum, X, [0]), rtol=1e-5, atol=1e-4)
        self.assert_array_equal(
            ht.piecewise(a, [a < 0, a >= 0], [lambda v: -v, lambda v: v]),
            np.piecewise(X, [X < 0, X >= 0], [lambda v: -v, lambda v: v]),
        )

    def test_unique_quartet_and_bitwise(self):
        iv = ht.array(np.array([3, 1, 2, 1, 3], np.int32), split=0)
        nua = np.unique_all(np.array([3, 1, 2, 1, 3], np.int32))
        ua = ht.unique_all(iv)
        np.testing.assert_array_equal(ua.values.numpy(), nua.values)
        np.testing.assert_array_equal(ua.inverse_indices.numpy(), nua.inverse_indices)
        np.testing.assert_array_equal(ua.counts.numpy(), nua.counts)
        np.testing.assert_array_equal(ht.unique_counts(iv).counts.numpy(), nua.counts)
        np.testing.assert_array_equal(ht.unique_inverse(iv).inverse_indices.numpy(), nua.inverse_indices)
        np.testing.assert_array_equal(ht.unique_values(iv).numpy(), nua.values)
        bc = np.array([7, 8], np.int32)
        self.assert_array_equal(ht.bitwise_count(ht.array(bc)), np.bitwise_count(bc))
        assert ht.bitwise_invert is ht.invert
        r, _ = ht.mask_indices(4, np.triu, 1)
        np.testing.assert_array_equal(r.numpy(), np.mask_indices(4, np.triu, 1)[0])
        assert ht.isdtype(ht.float32, "real floating") and not ht.isdtype(ht.int32, "real floating")

    def test_full_coverage_scripted(self):
        """The scripts/ coverage table reports 100% of the in-scope surface."""
        import subprocess
        import sys
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # pin the subprocess to CPU: it only lists names, and a child must
        # never claim the chip its parent may hold
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, os.path.join(repo, "scripts", "numpy_coverage.py")],
            capture_output=True, text=True, timeout=240, env=env,
        )
        assert out.returncode == 0, out.stderr[-500:]
        assert "(100.0%)" in out.stdout, out.stdout[-300:]

    def test_raise_modes_and_cycling(self):
        """Regression: numpy 'raise' contracts + put value cycling."""
        with pytest.raises(ValueError):
            ht.ravel_multi_index((ht.array(np.array([5], np.int32)), ht.array(np.array([0], np.int32))), (3, 3))
        with pytest.raises(ValueError):
            ht.choose(ht.array(np.array([0, 3], np.int32)), [ht.zeros((2,)), ht.ones((2,))])
        x = np.arange(12, dtype=np.float32)
        p = ht.array(x.copy(), split=0)
        ht.put(p, [0, 1, 2], [10.0, 20.0])  # short list cycles
        xe = x.copy()
        np.put(xe, [0, 1, 2], [10.0, 20.0])
        self.assert_array_equal(p, xe)
        with pytest.raises(IndexError):
            ht.put(ht.array(x.copy()), [99], [1.0])
        p2 = ht.array(x.copy(), split=0)
        ht.put(p2, [13], [5.0], mode="wrap")
        x2 = x.copy()
        np.put(x2, [13], [5.0], mode="wrap")
        self.assert_array_equal(p2, x2)
        with pytest.raises(TypeError):
            ht.lexsort([np.array([1, 2]), np.array([3, 4])])

    def test_copyto_keeps_sharding(self):
        c = ht.arange(16, dtype=ht.float32, split=0)
        ht.copyto(c, np.ones(16, np.float32))
        self.assert_distributed(c)
        self.assert_array_equal(c, np.ones(16, np.float32))

    def test_complex_correlate_conjugates(self):
        a = np.array([1 + 2j, 2 - 1j, 0.5 + 0j], np.complex64)
        v = np.array([0 + 1j, 1 + 0j], np.complex64)
        got = ht.correlate(ht.array(a), ht.array(v), mode="full")
        np.testing.assert_allclose(got.numpy(), np.correlate(a, v, mode="full"), rtol=1e-5)
