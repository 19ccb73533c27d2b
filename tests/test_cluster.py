"""Clustering estimator tests (reference: heat/cluster/tests/)."""

import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.cluster._kcluster import _KCluster
from heat_tpu.cluster.kmeans import KMeans

from test_suites.basic_test import TestCase


@pytest.fixture(scope="module")
def blobs():
    return ht.utils.data.create_spherical_dataset(128)


class TestKMeans(TestCase):
    def test_fit_quality(self, blobs):
        km = ht.cluster.KMeans(n_clusters=4, random_state=0).fit(blobs)
        centers = np.sort(km.cluster_centers_.numpy().mean(axis=1))
        np.testing.assert_allclose(centers, [-6, -2, 2, 6], atol=0.5)
        assert km.labels_.shape == (blobs.shape[0],)
        assert km.labels_.split == 0
        assert km.inertia_ > 0
        assert km.n_iter_ >= 1

    def test_predict(self, blobs):
        km = ht.cluster.KMeans(n_clusters=4, random_state=0).fit(blobs)
        pred = km.predict(blobs)
        np.testing.assert_array_equal(pred.numpy(), km.labels_.numpy())

    def test_init_variants(self, blobs):
        for init in ["random", "kmeans++"]:
            km = ht.cluster.KMeans(n_clusters=4, init=init, random_state=1).fit(blobs)
            assert km.cluster_centers_.shape == (4, 3)
        arr_init = blobs.numpy()[:4]
        km = ht.cluster.KMeans(n_clusters=4, init=ht.array(arr_init)).fit(blobs)
        assert km.cluster_centers_.shape == (4, 3)
        with pytest.raises(ValueError):
            ht.cluster.KMeans(n_clusters=4, init="bogus").fit(blobs)

    def test_get_set_params(self):
        km = ht.cluster.KMeans(n_clusters=4)
        p = km.get_params()
        assert p["n_clusters"] == 4
        km.set_params(n_clusters=8)
        assert km.n_clusters == 8


class TestKMediansMedoids(TestCase):
    def test_kmedians(self, blobs):
        km = ht.cluster.KMedians(n_clusters=4, random_state=1).fit(blobs)
        centers = np.sort(km.cluster_centers_.numpy().mean(axis=1))
        np.testing.assert_allclose(centers, [-6, -2, 2, 6], atol=0.5)

    def test_kmedoids(self, blobs):
        km = ht.cluster.KMedoids(n_clusters=4, random_state=1).fit(blobs)
        centers = km.cluster_centers_.numpy()
        # medoids must be actual data points
        data = blobs.numpy()
        for c in centers:
            assert np.min(np.sum((data - c) ** 2, axis=1)) < 1e-10


class TestBatchParallel(TestCase):
    def test_bp_kmeans(self, blobs):
        bp = ht.cluster.BatchParallelKMeans(n_clusters=4, random_state=1).fit(blobs)
        centers = np.sort(bp.cluster_centers_.numpy().mean(axis=1))
        np.testing.assert_allclose(centers, [-6, -2, 2, 6], atol=0.8)
        assert bp.labels_.shape == (blobs.shape[0],)

    def test_bp_kmedians(self, blobs):
        bp = ht.cluster.BatchParallelKMedians(n_clusters=4, random_state=1).fit(blobs)
        assert bp.cluster_centers_.shape == (4, 3)


class TestSpectral(TestCase):
    def test_spectral(self):
        data = ht.utils.data.create_spherical_dataset(24)
        sp = ht.cluster.Spectral(n_clusters=4, gamma=0.1, n_lanczos=48).fit(data)
        labels = sp.labels_.numpy()
        # clusters of 24 points each must be internally consistent
        n = 24
        for b in range(4):
            blk = labels[b * n : (b + 1) * n]
            vals, counts = np.unique(blk, return_counts=True)
            assert counts.max() >= n * 0.75


# ---------------------------------------------------------------------- #
# The jnp Lloyd programs against a plain NumPy reference at float64, from
# the same initial centres, called as the benchmark's cell calls them
# (``init=<array>``, ``tol=-1.0``, a fixed ``max_iter``).  ``split=None`` is
# ``_KCluster._fit_program``; ``split=0`` on the 8-device mesh is
# ``kmeans._fit_sharded_program``.
# ---------------------------------------------------------------------- #
K, D, ITERS = 4, 6, 5
PATHS = pytest.mark.parametrize("split", [None, 0], ids=["global", "sharded"])
# 1003 rows are ragged over 8 shards (126 a shard, 5 pad rows) and no
# multiple of either block
ROWS = pytest.mark.parametrize("n", [1024, 1003])
BLOCKS = pytest.mark.parametrize("blocked", [False, True], ids=["oneblock", "blocked"])


def _block_rows(monkeypatch, split, blocked):
    """Eight 128-row blocks in whichever program runs (a shard holds an eighth
    of the rows, so its block is an eighth too): 1024 rows fill the blocks,
    1003 leave a tail block that is clamped back over its neighbour."""
    if blocked:
        monkeypatch.setattr(_KCluster, "_ASSIGN_BLOCK", 128 if split is None else 16)


def _blob_centres(seed=3):
    return np.random.default_rng(seed).normal(size=(K, D)) * 6


def _blob_rows(n, seed=3):
    rng = np.random.default_rng(seed + 1)
    x = _blob_centres(seed)[rng.integers(0, K, size=n)] + rng.normal(size=(n, D))
    return x.astype(np.float32)


def _np_assign(x, c):
    d2 = ((x[:, None, :].astype(np.float64) - c[None, :, :].astype(np.float64)) ** 2).sum(-1)
    return d2.argmin(1), d2.min(1), d2


def _np_lloyd(x, c, iters):
    """Assign, average, keep the centre of a cluster that got no row."""
    c = c.astype(np.float64)
    for _ in range(iters):
        lab, _, _ = _np_assign(x, c)
        for j in range(c.shape[0]):
            if (lab == j).any():
                c[j] = x[lab == j].astype(np.float64).mean(0)
    lab, d2min, d2 = _np_assign(x, c)
    part = np.partition(d2, 1, axis=1)
    return c, lab, d2min.sum(), (part[:, 1] - part[:, 0]).min()


@BLOCKS
@ROWS
@PATHS
def test_fit_matches_numpy_lloyd(monkeypatch, split, n, blocked):
    _block_rows(monkeypatch, split, blocked)
    x = _blob_rows(n)
    init = x[:: n // K][:K] + 0.5  # off the data, so five iterations all move
    want_c, want_lab, want_inertia, margin = _np_lloyd(x, init, ITERS)
    assert margin > 1e-3  # no row sits on a boundary float32 could flip
    km = ht.cluster.KMeans(n_clusters=K, init=init, max_iter=ITERS, tol=-1.0)
    km.fit(ht.array(x, split=split))
    np.testing.assert_allclose(km.cluster_centers_.numpy(), want_c, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(km.labels_.numpy(), want_lab)
    assert km.labels_.shape == (n,)
    assert abs(km.inertia_ - want_inertia) / want_inertia < 1e-5
    assert km.n_iter_ == ITERS
    assert km.cluster_centers_.split is None and km.labels_.split == split
    assert km.predict(ht.array(x, split=split)).split == split


@BLOCKS
@ROWS
@PATHS
def test_predict_matches_numpy(monkeypatch, split, n, blocked):
    _block_rows(monkeypatch, split, blocked)
    km = ht.cluster.KMeans(n_clusters=K, init="random", max_iter=2, random_state=0)
    km.fit(ht.array(_blob_rows(512, seed=5), split=split))
    y = _blob_rows(n, seed=11)  # rows the fit never saw
    want_lab, _, d2 = _np_assign(y, km.cluster_centers_.numpy())
    part = np.partition(d2, 1, axis=1)
    assert (part[:, 1] - part[:, 0]).min() > 1e-3
    got = km.predict(ht.array(y, split=split))
    assert got.shape == (n,) and got.split == split
    np.testing.assert_array_equal(got.numpy(), want_lab)


@pytest.mark.parametrize("base,n", [(0, 1987), (4000, 5987), (2000, 2000), (0, 2000)],
                         ids=["tail_pad", "offset_tail_pad", "all_pad", "no_pad"])
def test_local_em_stats_pad_rows_contribute_nothing(monkeypatch, base, n):
    """A shard's rows at ``base + i >= n`` are pad: whatever they hold, they
    add to no sum and no count (blocked, with a clamped tail block)."""
    monkeypatch.setattr(_KCluster, "_ASSIGN_BLOCK", 512)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2000, 16)).astype(np.float32)
    c = rng.standard_normal((8, 16)).astype(np.float32)
    s, cnt = KMeans._local_em_stats(jnp.asarray(x), jnp.asarray(c), base, n)
    live = x[: max(n - base, 0)]
    lab, _, _ = _np_assign(live, c)
    want_s = np.zeros((8, 16))
    np.add.at(want_s, lab, live.astype(np.float64))
    np.testing.assert_array_equal(np.asarray(cnt), np.bincount(lab, minlength=8))
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=1e-4, atol=1e-3)


BLK = 64


@pytest.mark.parametrize("n", [BLK + 1, 2 * BLK - 1, 2 * BLK, 3 * BLK + 7])
def test_blocked_stats_count_each_row_once(monkeypatch, n):
    """The tail block is clamped back over rows the block before it already
    counted; on all-ones rows the sums and counts are exact, so a row counted
    twice or never shows as a whole number."""
    monkeypatch.setattr(_KCluster, "_ASSIGN_BLOCK", BLK)
    k, d = 5, 3
    s, cnt = KMeans._blocked_stats(
        jnp.ones((n, d), jnp.float32), k,
        lambda xb, start, blk: (start + jnp.arange(blk)) % k)
    want = np.bincount(np.arange(n) % k, minlength=k)
    np.testing.assert_array_equal(np.asarray(cnt), want)
    np.testing.assert_array_equal(np.asarray(s), np.repeat(want[:, None], d, axis=1))


@PATHS
def test_empty_cluster_keeps_its_centre(split):
    x = _blob_rows(1003)
    init = np.concatenate([x[:K - 1], np.full((1, D), 1e3, np.float32)])
    want_c, want_lab, _, _ = _np_lloyd(x, init, 3)
    assert not (want_lab == K - 1).any()
    km = ht.cluster.KMeans(n_clusters=K, init=init, max_iter=3, tol=-1.0)
    km.fit(ht.array(x, split=split))
    got = km.cluster_centers_.numpy()
    np.testing.assert_array_equal(got[K - 1], init[K - 1])
    np.testing.assert_allclose(got, want_c, rtol=1e-5, atol=1e-5)


@PATHS
def test_fit_program_cached_per_class_and_block(monkeypatch, split):
    """The E+M step runs only while a program is traced: a second fit of the
    same shapes finds the program, another block size does not."""
    step = "_em_step" if split is None else "_local_em_stats"
    raw, calls = KMeans.__dict__[step], []  # a classmethod, a staticmethod

    def counted(*args):
        calls.append(1)
        return raw.__func__(*args)

    monkeypatch.setattr(KMeans, step, type(raw)(counted))
    monkeypatch.setattr(_KCluster, "_ASSIGN_BLOCK", 96)  # no other test's program
    x = ht.array(_blob_rows(1003), split=split)

    def fit():
        ht.cluster.KMeans(n_clusters=K, init="random", max_iter=2, random_state=0).fit(x)

    fit()
    traced = len(calls)
    assert traced >= 1
    fit()
    assert len(calls) == traced
    monkeypatch.setattr(_KCluster, "_ASSIGN_BLOCK", 80)
    fit()
    assert len(calls) > traced
    if split is None:
        assert KMeans._fit_program() is KMeans._fit_program()
        assert KMeans._fit_program() is not ht.cluster.KMedians._fit_program()
    else:
        assert KMeans._fit_program_sharded(x.comm) is KMeans._fit_program_sharded(x.comm)


@PATHS
def test_bfloat16_fit_tracks_float32(split):
    """bfloat16 rows and centres against the float64 reference on the same
    (rounded) values, from centres one off the blobs' own so that no row is
    near a boundary.  float32 is held to 1e-5 above; bfloat16 read 2.52e-3 of
    the largest coordinate in the centres and 5.54e-2 in the inertia on both
    paths (CPU mesh, PR 30), and is held to three times that."""
    def rounded(a):
        return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))

    x, init = rounded(_blob_rows(1003)), rounded(_blob_centres() + 1.0)
    want_c, want_lab, want_inertia, margin = _np_lloyd(x, init, ITERS)
    assert margin > 10.0
    km = ht.cluster.KMeans(n_clusters=K, init=ht.array(init, dtype=ht.bfloat16),
                           max_iter=ITERS, tol=-1.0)
    km.fit(ht.array(x, split=split, dtype=ht.bfloat16))
    assert km.cluster_centers_.dtype == ht.bfloat16
    err = np.abs(km.cluster_centers_.numpy().astype(np.float64) - want_c).max()
    assert err / np.abs(want_c).max() < 7.6e-3
    np.testing.assert_array_equal(km.labels_.numpy(), want_lab)
    assert abs(km.inertia_ - want_inertia) / want_inertia < 0.17


def test_assign_kernel_is_not_an_argument():
    with pytest.raises(TypeError):
        ht.cluster.KMeans(assign_kernel="jnp")
    assert "assign_kernel" not in ht.cluster.KMeans().get_params()
