"""I/O, FFT, sparse, signal, tiling tests (reference: test_io.py,
heat/fft/tests, heat/sparse/tests, test_signal.py, test_tiling.py)."""

import numpy as np
import pytest

import heat_tpu as ht

from test_suites.basic_test import TestCase


class TestSignal(TestCase):
    def test_convolve_modes(self):
        rng = np.random.default_rng(0)
        a = rng.normal(size=37).astype(np.float32)
        v = np.array([1.0, 2.0, 3.0], dtype=np.float32)
        for split in [None, 0]:
            for mode in ("full", "same", "valid"):
                got = ht.convolve(ht.array(a, split=split), ht.array(v), mode=mode)
                np.testing.assert_allclose(got.numpy(), np.convolve(a, v, mode=mode), atol=1e-4)

    def test_convolve_int_and_swap(self):
        a = np.array([1, 2, 3], dtype=np.int32)
        v = np.array([0, 1, 0, 0, 0], dtype=np.int32)
        got = ht.convolve(ht.array(a), ht.array(v), mode="full")
        np.testing.assert_array_equal(got.numpy(), np.convolve(a, v))
        assert got.dtype == ht.int32

    def test_convolve_errors(self):
        with pytest.raises(ValueError):
            ht.convolve(ht.ones((2, 2)), ht.ones(3))
        with pytest.raises(ValueError):
            ht.convolve(ht.ones(5), ht.ones(3), mode="bogus")

    def test_convolve2d(self):
        from scipy.signal import convolve2d as sconv

        rng = np.random.default_rng(1)
        a = rng.normal(size=(9, 9)).astype(np.float32)
        v = rng.normal(size=(3, 3)).astype(np.float32)
        for mode in ("full", "same", "valid"):
            got = ht.core.signal.convolve2d(ht.array(a, split=0), ht.array(v), mode=mode)
            np.testing.assert_allclose(got.numpy(), sconv(a, v, mode=mode), atol=1e-3)


class TestFFT(TestCase):
    def setup_method(self, method):
        self.x = np.random.default_rng(2).normal(size=(8, 16)).astype(np.float32)

    def test_fft_family(self):
        for split in [None, 0, 1]:
            a = ht.array(self.x, split=split)
            np.testing.assert_allclose(ht.fft.fft(a).numpy(), np.fft.fft(self.x), atol=1e-3)
            np.testing.assert_allclose(ht.fft.rfft(a).numpy(), np.fft.rfft(self.x), atol=1e-3)
            np.testing.assert_allclose(
                ht.fft.fft(a, axis=0).numpy(), np.fft.fft(self.x, axis=0), atol=1e-3
            )

    def test_roundtrips(self):
        a = ht.array(self.x, split=0)
        np.testing.assert_allclose(ht.fft.ifft(ht.fft.fft(a)).numpy().real, self.x, atol=1e-4)
        np.testing.assert_allclose(ht.fft.irfft(ht.fft.rfft(a), n=16).numpy(), self.x, atol=1e-4)
        np.testing.assert_allclose(
            ht.fft.ifftn(ht.fft.fftn(a)).numpy().real, self.x, atol=1e-4
        )

    def test_freq_shift(self):
        np.testing.assert_allclose(ht.fft.fftfreq(16).numpy(), np.fft.fftfreq(16), atol=1e-6)
        np.testing.assert_allclose(ht.fft.rfftfreq(16).numpy(), np.fft.rfftfreq(16), atol=1e-6)
        a = ht.array(self.x, split=0)
        np.testing.assert_allclose(ht.fft.fftshift(a).numpy(), np.fft.fftshift(self.x))

    def test_split_preserved(self):
        a = ht.array(self.x, split=1)
        assert ht.fft.fft(a).split == 1


class TestHermitianN(TestCase):
    """hfftn/ihfftn (+ hfft2/ihfft2 with explicit shape) against the
    torch.fft oracle — the reference inherits these whole from torch
    (SURVEY §2.2 fft row); ours composes them per axis (VERDICT r4
    missing #2)."""

    def setup_method(self, method):
        rng = np.random.default_rng(7)
        self.real = rng.normal(size=(6, 10)).astype(np.float32)
        self.cplx = (rng.normal(size=(6, 9)) + 1j * rng.normal(size=(6, 9))).astype(np.complex64)

    @pytest.mark.parametrize("norm", [None, "ortho", "forward"])
    def test_hfftn_matches_torch(self, norm):
        import torch

        want = torch.fft.hfftn(torch.from_numpy(self.cplx), norm=norm).numpy()
        for split in [None, 0, 1]:
            got = ht.fft.hfftn(ht.array(self.cplx, split=split), norm=norm)
            np.testing.assert_allclose(got.numpy(), want, atol=1e-3)
            assert got.split == split

    @pytest.mark.parametrize("norm", [None, "ortho", "forward"])
    def test_ihfftn_matches_torch(self, norm):
        import torch

        want = torch.fft.ihfftn(torch.from_numpy(self.real), norm=norm).numpy()
        got = ht.fft.ihfftn(ht.array(self.real, split=0), norm=norm)
        np.testing.assert_allclose(got.numpy(), want, atol=1e-4)

    def test_hfftn_with_shape_and_axes(self):
        import torch

        want = torch.fft.hfftn(torch.from_numpy(self.cplx), s=(8, 12), dim=(0, 1)).numpy()
        got = ht.fft.hfftn(ht.array(self.cplx), s=(8, 12), axes=(0, 1))
        np.testing.assert_allclose(got.numpy(), want, atol=1e-3)
        # s given, axes omitted: the last len(s) axes are transformed
        want = torch.fft.hfftn(torch.from_numpy(self.cplx), s=(12,)).numpy()
        got = ht.fft.hfftn(ht.array(self.cplx), s=(12,))
        np.testing.assert_allclose(got.numpy(), want, atol=1e-3)

    def test_hfft2_shape_no_longer_raises(self):
        import torch

        want = torch.fft.hfft2(torch.from_numpy(self.cplx), s=(6, 12)).numpy()
        got = ht.fft.hfft2(ht.array(self.cplx), s=(6, 12))
        np.testing.assert_allclose(got.numpy(), want, atol=1e-3)
        want = torch.fft.ihfft2(torch.from_numpy(self.real), s=(8, 10)).numpy()
        got = ht.fft.ihfft2(ht.array(self.real), s=(8, 10))
        np.testing.assert_allclose(got.numpy(), want, atol=1e-4)

    def test_roundtrip(self):
        """ihfftn(hfftn-sized real signal) recovers the one-sided spectrum."""
        spec = ht.fft.ihfftn(ht.array(self.real, split=0))
        back = ht.fft.hfftn(spec, s=self.real.shape)
        np.testing.assert_allclose(back.numpy(), self.real, atol=1e-4)

    def test_validation(self):
        with pytest.raises(ValueError, match="same length"):
            ht.fft.hfftn(ht.array(self.cplx), s=(4,), axes=(0, 1))
        # default (-2, -1) axes alias on a 1-D input — torch raises too;
        # a silent double transform on axis 0 would be wrong
        with pytest.raises(ValueError, match="unique"):
            ht.fft.hfft2(ht.array(self.cplx[0]))
        with pytest.raises(ValueError, match="unique"):
            ht.fft.hfftn(ht.array(self.cplx), axes=(0, 0))


@pytest.mark.mp  # IO round-trips cross the process seam via token-ring /
# per-chunk writers (conftest redirects tmp_path to a rank-shared directory)
class TestIO(TestCase):
    def test_hdf5_roundtrip(self, tmp_path):
        pytest.importorskip("h5py")
        p = str(tmp_path / "x.h5")
        a = ht.random.randn(16, 4, split=0)
        ht.save(a, p, "data")
        for split in [None, 0, 1]:
            b = ht.load(p, "data", split=split)
            np.testing.assert_allclose(b.numpy(), a.numpy(), atol=1e-6)
            assert b.split == split

    def test_csv_roundtrip(self, tmp_path):
        p = str(tmp_path / "x.csv")
        a = ht.random.randn(10, 3, split=0)
        ht.save(a, p)
        b = ht.load(p, split=0)
        np.testing.assert_allclose(b.numpy(), a.numpy(), atol=1e-5)

    @pytest.mark.mp_unsafe  # raw open() write: every rank would write the
    # same path unsynchronized (the token-ring writers exist for this)
    def test_csv_header(self, tmp_path):
        p = str(tmp_path / "h.csv")
        with open(p, "w") as f:
            f.write("col1,col2\n1.0,2.0\n3.0,4.0\n")
        b = ht.load_csv(p, header_lines=1)
        np.testing.assert_allclose(b.numpy(), [[1, 2], [3, 4]])

    @pytest.mark.mp_unsafe  # raw np.save + mkdir from every rank
    def test_npy(self, tmp_path):
        p = str(tmp_path / "x.npy")
        data = np.arange(20.0, dtype=np.float32).reshape(5, 4)
        np.save(p, data)
        b = ht.load(p, split=0)
        np.testing.assert_array_equal(b.numpy(), data)
        # directory of npy files
        d = tmp_path / "dir"
        d.mkdir()
        np.save(str(d / "a.npy"), data)
        np.save(str(d / "b.npy"), data + 20)
        c = ht.core.io.load_npy_from_path(str(d), split=0)
        assert c.shape == (10, 4)

    def test_netcdf_roundtrip(self, tmp_path):
        p = str(tmp_path / "t.nc")
        x = ht.random.randn(12, 5, split=0)
        ht.save_netcdf(x, p, "temp")
        y = ht.load_netcdf(p, "temp", split=0)
        np.testing.assert_allclose(y.numpy(), x.numpy(), rtol=1e-6)
        # extension dispatch and resplit-on-load
        z = ht.load(p, "temp", split=1)
        assert z.split == 1
        np.testing.assert_allclose(z.numpy(), x.numpy(), rtol=1e-6)
        assert ht.supports_netcdf()
        # the h5py-backed writer must attach netCDF-style dimension scales
        import h5py

        with h5py.File(p, "r") as f:
            assert "temp_dim0" in f and "temp_dim1" in f

    def test_unsupported_ext(self, tmp_path):
        with pytest.raises(ValueError):
            ht.load(str(tmp_path / "x.xyz"))

    def test_checkpoint_pytree(self, tmp_path):
        p = str(tmp_path / "ck.npz")
        tree = {"layer": {"w": np.ones((3, 2), np.float32)}, "step": np.asarray(7)}
        ht.core.io.save_checkpoint(tree, p)
        back = ht.core.io.load_checkpoint(tree, p)
        np.testing.assert_array_equal(np.asarray(back["layer"]["w"]), tree["layer"]["w"])
        assert int(back["step"]) == 7

    def test_zarr_roundtrip(self, tmp_path):
        """zarr v2 directory format (VERDICT r4 missing #3): per-device
        chunk files, ragged extents stored as fill-padded edge chunks."""
        import json
        import os

        d = str(tmp_path / "x.zarr")
        a = ht.reshape(ht.arange(101 * 3, dtype=ht.float32, split=0), (101, 3))
        ht.save(a, d)
        meta = json.load(open(os.path.join(d, ".zarray")))
        assert meta["zarr_format"] == 2 and meta["compressor"] is None
        assert meta["shape"] == [101, 3]
        p = a.comm.size
        chunk = -(-101 // p)
        assert meta["chunks"] == [chunk, 3]
        # every chunk file is the full nominal size (zarr edge convention)
        for f in os.listdir(d):
            if f != ".zarray":
                assert os.path.getsize(os.path.join(d, f)) == chunk * 3 * 4
        for split in [0, 1, None]:
            b = ht.load(d, split=split)
            assert b.split == split and b.shape == (101, 3)
            np.testing.assert_array_equal(b.numpy(), a.numpy())

    def test_zarr_replicated_int_and_dispatch(self, tmp_path):
        d = str(tmp_path / "i.zarr")
        x = ht.array(np.arange(24, dtype=np.int32).reshape(4, 6))
        ht.save(x, d)
        b = ht.load(d, split=0)
        assert b.dtype == ht.int32
        np.testing.assert_array_equal(b.numpy(), x.numpy())

    @pytest.mark.mp_unsafe  # hand-rolled .zarray writes from every rank
    def test_zarr_validation(self, tmp_path):
        import json
        import os

        with pytest.raises(ValueError, match="zarr v2 representation"):
            ht.save(ht.ones(8, dtype=ht.bfloat16, split=0), str(tmp_path / "b.zarr"))
        d = str(tmp_path / "c.zarr")
        os.makedirs(d)
        meta = {"zarr_format": 2, "shape": [4], "chunks": [4], "dtype": "<f4",
                "compressor": {"id": "blosc"}, "fill_value": 0, "order": "C",
                "filters": None}
        json.dump(meta, open(os.path.join(d, ".zarray"), "w"))
        with pytest.raises(ValueError, match="compressed"):
            ht.load(d)
        # absent chunk files read as fill_value (zarr convention)
        meta["compressor"] = None
        json.dump(meta, open(os.path.join(d, ".zarray"), "w"))
        np.testing.assert_array_equal(ht.load(d).numpy(), np.zeros(4, np.float32))
        # "fill_value": null is legal v2 metadata — read as 0, even for ints
        meta["fill_value"] = None
        meta["dtype"] = "<i4"
        json.dump(meta, open(os.path.join(d, ".zarray"), "w"))
        np.testing.assert_array_equal(ht.load(d).numpy(), np.zeros(4, np.int32))


class TestSparse(TestCase):
    def setup_method(self, method):
        import scipy.sparse as sp

        self.scipy_mat = sp.random(16, 8, density=0.25, format="csr", random_state=0, dtype=np.float32)

    def test_factory_and_todense(self):
        s = ht.sparse.sparse_csr_matrix(self.scipy_mat, split=0)
        assert s.shape == (16, 8)
        assert s.nnz == self.scipy_mat.nnz
        assert s.split == 0
        np.testing.assert_allclose(s.todense().numpy(), self.scipy_mat.toarray())

    def test_from_dense(self):
        dense = np.array([[1.0, 0.0], [0.0, 2.0]], dtype=np.float32)
        s = ht.sparse.sparse_csr_matrix(dense)
        assert s.nnz == 2
        np.testing.assert_allclose(s.todense().numpy(), dense)

    def test_csr_attributes(self):
        s = ht.sparse.sparse_csr_matrix(self.scipy_mat)
        np.testing.assert_array_equal(np.asarray(s.indptr), self.scipy_mat.indptr)

    def test_arithmetic(self):
        s1 = ht.sparse.sparse_csr_matrix(self.scipy_mat)
        s2 = ht.sparse.sparse_csr_matrix(self.scipy_mat * 2)
        np.testing.assert_allclose((s1 + s2).todense().numpy(), 3 * self.scipy_mat.toarray(), atol=1e-5)
        np.testing.assert_allclose(
            (s1 * s2).todense().numpy(), 2 * self.scipy_mat.toarray() ** 2, atol=1e-5
        )

    def test_spmm(self):
        s = ht.sparse.sparse_csr_matrix(self.scipy_mat, split=0)
        v = ht.random.randn(8, 3)
        np.testing.assert_allclose(
            (s @ v).numpy(), self.scipy_mat.toarray() @ v.numpy(), atol=1e-4
        )

    def test_matmul_distributed_dense(self):
        """DCSR(split=0) @ dense → split=0 dense, physically row-parallel
        (each shard computes from its own nonzeros only), scipy oracle."""
        import scipy.sparse as sp

        A = sp.random(37, 23, density=0.15, format="csr", random_state=1, dtype=np.float32)
        B = np.random.default_rng(0).standard_normal((23, 5)).astype(np.float32)
        s = ht.sparse.sparse_csr_matrix(A, split=0)
        r = ht.sparse.matmul(s, ht.array(B))
        assert r.split == 0
        self.assert_array_equal(r, A @ B, rtol=1e-4, atol=1e-4)
        # the per-shard nnz buffers are mesh-sharded, not replicated
        data, rows, cols, m, rps = s._row_sharded_parts()
        comm = s.comm
        if comm.is_distributed():
            assert len(data.sharding.device_set) >= comm.size
            for shard in data.addressable_shards:
                assert shard.data.shape[1] == m and shard.data.shape[0] * comm.size == data.shape[0]

    def test_matmul_vector_and_split_dense(self):
        import scipy.sparse as sp

        A = sp.random(37, 23, density=0.15, format="csr", random_state=1, dtype=np.float32)
        s = ht.sparse.sparse_csr_matrix(A, split=0)
        v = np.random.default_rng(1).standard_normal(23).astype(np.float32)
        rv = s @ ht.array(v)
        assert rv.shape == (37,) and rv.split == 0
        self.assert_array_equal(rv, A @ v, rtol=1e-4, atol=1e-4)
        # split dense RHS is resplit to None first (needs full columns)
        B = np.random.default_rng(2).standard_normal((23, 4)).astype(np.float32)
        r = s @ ht.array(B, split=0)
        self.assert_array_equal(r, A @ B, rtol=1e-4, atol=1e-4)

    def test_matmul_nonfinite_dense_matches_scipy(self):
        """Regression: nnz-pad entries use out-of-range indices (dropped by
        BCOO), not explicit zeros at (0,0) — explicit zeros would turn an
        inf/NaN in dense row 0 into NaN on every under-full shard's first
        row (0·inf = NaN)."""
        import scipy.sparse as sp

        A = sp.random(37, 23, density=0.15, format="csr", random_state=1, dtype=np.float32)
        B = np.random.default_rng(0).standard_normal((23, 5)).astype(np.float32)
        B[0, 0] = np.inf
        B[1, 2] = np.nan
        s = ht.sparse.sparse_csr_matrix(A, split=0)
        ours = (s @ ht.array(B)).numpy()
        want = A @ B
        mask = np.isfinite(want)
        np.testing.assert_allclose(ours[mask], want[mask], rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.isfinite(ours), mask)

    def test_matmul_sparse_sparse(self):
        """DCSR @ DCSR: pure sparse BCOO product (no dense intermediate),
        result keeps the left operand's row split."""
        import scipy.sparse as sp

        A = sp.random(24, 16, density=0.2, format="csr", random_state=3, dtype=np.float32)
        C = sp.random(16, 9, density=0.2, format="csr", random_state=4, dtype=np.float32)
        s1 = ht.sparse.sparse_csr_matrix(A, split=0)
        s2 = ht.sparse.sparse_csr_matrix(C)
        rs = s1 @ s2
        assert isinstance(rs, ht.sparse.DCSR_matrix)
        assert rs.split == 0 and rs.shape == (24, 9)
        np.testing.assert_allclose(rs.todense().numpy(), (A @ C).toarray(), rtol=1e-4, atol=1e-4)

    def test_matmul_edge_shapes_and_errors(self):
        import pytest as _pytest
        import scipy.sparse as sp

        # fewer rows than devices: pad shards carry zero nnz
        A3 = sp.random(3, 23, density=0.3, format="csr", random_state=5, dtype=np.float32)
        B = np.random.default_rng(3).standard_normal((23, 2)).astype(np.float32)
        s3 = ht.sparse.sparse_csr_matrix(A3, split=0)
        r3 = s3 @ ht.array(B)
        self.assert_array_equal(r3, A3 @ B, rtol=1e-4, atol=1e-4)
        s = ht.sparse.sparse_csr_matrix(A3, split=0)
        with _pytest.raises(ValueError):
            ht.sparse.matmul(s, ht.array(B[:5]))  # shape mismatch
        with _pytest.raises(TypeError):
            ht.sparse.matmul(s, B)  # raw numpy is not a DNDarray

    def test_sub_neg_scalar_ops(self):
        d = self.scipy_mat.toarray()
        s1 = ht.sparse.sparse_csr_matrix(self.scipy_mat)
        s2 = ht.sparse.sparse_csr_matrix(self.scipy_mat * 0.5)
        np.testing.assert_allclose((s1 - s2).todense().numpy(), 0.5 * d, atol=1e-5)
        np.testing.assert_allclose((-s1).todense().numpy(), -d, atol=1e-6)
        np.testing.assert_allclose((s1 * 3.0).todense().numpy(), 3 * d, atol=1e-5)
        np.testing.assert_allclose((2.0 * s1).todense().numpy(), 2 * d, atol=1e-5)
        np.testing.assert_allclose((s1 / 2.0).todense().numpy(), d / 2, atol=1e-5)

    def test_to_sparse_roundtrip(self):
        d = self.scipy_mat.toarray()
        x = ht.array(d, split=0)
        s = ht.sparse.to_sparse(x)
        assert s.split == 0
        assert s.nnz == self.scipy_mat.nnz
        back = s.todense()
        assert back.split == 0
        self.assert_array_equal(back, d)
        # factory accepts a dense DNDarray and inherits its split
        s2 = ht.sparse.sparse_csr_matrix(x)
        assert s2.split == 0
        np.testing.assert_allclose(s2.todense().numpy(), d)

    def test_invalid_operands_raise(self):
        import pytest as _pytest

        s = ht.sparse.sparse_csr_matrix(self.scipy_mat)
        with _pytest.raises(TypeError):
            s * np.full(2, 3.0)  # array is not a scalar
        with _pytest.raises(TypeError):
            s - 2.0  # sparse - scalar is not defined
        with _pytest.raises(ValueError):
            ht.sparse.to_sparse(ht.array(self.scipy_mat.toarray(), split=1))
        with _pytest.raises(ValueError):
            ht.sparse.sparse_csr_matrix(
                ht.array(self.scipy_mat.toarray(), split=0), split=1
            )

    def test_transpose(self):
        d = self.scipy_mat.toarray()
        s = ht.sparse.sparse_csr_matrix(self.scipy_mat, split=0)
        st = ht.sparse.transpose(s)
        assert st.shape == (8, 16)
        assert st.split is None  # CSR-rows-only: transposed split unrepresentable
        np.testing.assert_allclose(st.todense().numpy(), d.T, atol=1e-6)


class TestTiling(TestCase):
    def test_split_tiles(self):
        a = ht.array(np.arange(64.0, dtype=np.float32).reshape(16, 4), split=0)
        t = ht.core.tiling.SplitTiles(a)
        assert sum(t.tile_dimensions[0]) == 16
        # tile 0 spans the first shard's rows (ceil-div chunk convention)
        rows = -(-16 // a.comm.size)
        first = np.asarray(t[0])
        np.testing.assert_array_equal(first, a.numpy()[:rows])
        t[0] = np.zeros_like(first)
        assert float(a.numpy()[:rows].sum()) == 0.0

    def test_square_diag_tiles(self):
        a = ht.array(np.arange(64.0, dtype=np.float32).reshape(8, 8), split=0)
        t = ht.core.tiling.SquareDiagTiles(a, tiles_per_proc=1)
        assert t.tile_rows >= 1 and t.tile_columns >= 1
        blk = np.asarray(t[0, 0])
        assert blk.shape[0] == blk.shape[1]  # square diagonal tile
        t[0, 0] = np.zeros_like(blk)
        assert float(a.numpy()[: blk.shape[0], : blk.shape[1]].sum()) == 0.0


class TestProfiler(TestCase):
    def test_timer(self):
        holder = {}
        x = ht.random.randn(64, 64)
        with ht.utils.profiler.timer("mm", holder, sync_on=None):
            y = x @ x
        ht.utils.profiler.sync(y)
        assert "mm" in holder and holder["mm"] >= 0.0


class TestFFTTransposeMethod(TestCase):
    """Transforms hitting the split axis use the explicit transpose method
    (resplit → local FFT → resplit back), the reference's own scheme —
    never a gather (r4)."""

    def _mod(self):
        import importlib

        return importlib.import_module("heat_tpu.fft.fft")

    def test_split_axis_fft_rides_transpose(self):
        F = self._mod()
        comm = ht.communication.get_comm()
        if not comm.is_distributed():
            pytest.skip("needs a multi-device mesh")
        x = np.random.default_rng(0).standard_normal((1000, 2 * comm.size)).astype(np.float32)
        hx = ht.array(x, split=0)
        before = dict(F.fft_paths)
        y = ht.fft.fft(hx, axis=0)
        assert F.fft_paths["transpose"] == before["transpose"] + 1
        np.testing.assert_allclose(y.numpy(), np.fft.fft(x, axis=0), rtol=1e-4, atol=1e-3)
        assert y.split == 0
        # rfft halves the split-axis extent: bookkeeping survives resplit-back
        yr = ht.fft.rfft(hx, axis=0)
        assert yr.shape == (501, 2 * comm.size) and yr.split == 0
        np.testing.assert_allclose(yr.numpy(), np.fft.rfft(x, axis=0), rtol=1e-4, atol=1e-3)
        # 2-D fft2 transforms EVERY axis — no free reshard target, so it
        # takes the direct path (still exact)
        before = dict(F.fft_paths)
        y2 = ht.fft.fft2(hx)
        assert F.fft_paths["transpose"] == before["transpose"]
        np.testing.assert_allclose(y2.numpy(), np.fft.fft2(x), rtol=1e-4, atol=1e-2)

    def test_fftn_partial_axes_reshards(self):
        """3-D fftn over axes (0, 2) with split=0: axis 1 is free and
        divisible → the _fftn_op transpose branch engages."""
        F = self._mod()
        comm = ht.communication.get_comm()
        if not comm.is_distributed():
            pytest.skip("needs a multi-device mesh")
        p = comm.size
        x = np.random.default_rng(2).standard_normal((8 * p, 2 * p, 6)).astype(np.float32)
        hx = ht.array(x, split=0)
        before = dict(F.fft_paths)
        y = ht.fft.fftn(hx, axes=(0, 2))
        assert F.fft_paths["transpose"] == before["transpose"] + 1
        np.testing.assert_allclose(y.numpy(), np.fft.fftn(x, axes=(0, 2)), rtol=1e-4, atol=1e-2)
        assert y.split == 0
        # numpy rule: s given + axes omitted transforms only the LAST
        # len(s) axes — axis 0 (the split) is then untouched: direct path
        before = dict(F.fft_paths)
        y2 = ht.fft.fftn(hx, s=(2 * p, 6))
        assert F.fft_paths["transpose"] == before["transpose"]
        np.testing.assert_allclose(y2.numpy(), np.fft.fftn(x, s=(2 * p, 6)), rtol=1e-4, atol=1e-2)

    def test_local_axis_stays_direct(self):
        F = self._mod()
        x = np.random.default_rng(1).standard_normal((64, 8)).astype(np.float32)
        hx = ht.array(x, split=0)
        before = dict(F.fft_paths)
        y = ht.fft.fft(hx, axis=1)
        assert F.fft_paths["transpose"] == before["transpose"]
        np.testing.assert_allclose(y.numpy(), np.fft.fft(x, axis=1), rtol=1e-4, atol=1e-3)
