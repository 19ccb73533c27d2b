"""Every job kind end to end at a tiny size on the CPU mesh against its plain
reference, ``work()`` against counts made by hand, the seeded data, the
references against numpy, and checks that do fail on a wrong result."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import heat_tpu as ht  # noqa: E402
from chipbench.harness import data, manifest, runner  # noqa: E402
from chipbench.harness.window import Window, quantiles  # noqa: E402
from chipbench.references import dense as dense_ref  # noqa: E402
from chipbench.references import lloyd as lloyd_ref  # noqa: E402
from chipbench.references import rel_err  # noqa: E402
from heat_tpu.core.communication import Communication  # noqa: E402

BENCH = manifest.Manifest(REPO)
BLOBS = {"rows": 1 << 13, "features": 32, "clusters": 64, "dtype": "float32",
         "blob_spread": 4.0, "check_rows": 2048}
DENSE = {"n": 256, "dtype": "bfloat16"}
MINIBATCH = {"job": "lloyd_eager", "batch_rows": 512, "steps": 6, "check_steps": 5,
             "warmup_jobs": 1, "traced_jobs": 1}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _comm(chips):
    return Communication(Mesh(np.asarray(jax.devices()[:chips]), ("x",)), "x")


# the manifest's four cells, each with its sizes cut to a toy and nothing else
CELLS = {
    "kmeans_fit_n2e26": dict(config=BLOBS),
    "lloyd_eager_n2e26": dict(config=BLOBS, traffic=MINIBATCH),
    "matmul_n40960": dict(config=DENSE),
    "matmul_resplit_n40960_4chip": dict(config=DENSE),
}


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("name", list(CELLS))
def test_cell_tiny_end_to_end(name, trace):
    assert set(CELLS) == {c["name"] for c in BENCH.data["workloads"]}
    chips = BENCH.cell(name)["chips"]
    lines = []
    result = runner.run_cell(BENCH, name, seed=5, seconds=0.2, trace=trace, comm=_comm(chips),
                             say=lines.append, **CELLS[name])
    # the last line's keys are the contract's: a CPU trace has no device
    # plane, so there is no breakdown to add here
    assert set(result) == RESULT_KEYS
    assert json.loads(json.dumps(result)) == result
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": chips,
                                "memory_peak_bytes": 0}
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in BENCH.metrics(group, name)}
    assert result["metrics"] and set(result["metrics"]) <= set(allowed)
    for metric, entry in result["metrics"].items():
        assert set(entry) == {"value", "unit"} and entry["unit"] == allowed[metric]
        assert isinstance(entry["value"], (int, float))
    if trace:
        assert result["metrics"]["recompiles_in_window"]["value"] == 0
        # the driver refuses a traced line that lacks a metric of the cell: what
        # no trace is needed for is always there, the rest is named as left out
        left_out = {line.split()[6] for line in lines if line.startswith("# left out:")}
        assert set(result["metrics"]) | left_out == set(allowed)
        assert not left_out & {"job_p90_over_p50", "recompiles_in_window", "strong_scaling_eff"}
        if chips == 4:
            assert result["metrics"]["strong_scaling_eff"]["value"] > 0
    else:
        assert set(result["metrics"]) == {"job_s", "setup_s"}  # no HBM on the CPU
    assert sum(line.startswith("# window n=") for line in lines) == 1
    assert any(line.startswith("# check correct=True") for line in lines)


@pytest.mark.parametrize("traffic, config, chips", [
    ({"job": "qr", "mode": "r", "warmup_jobs": 1, "traced_jobs": 1},
     {"rows": 4096, "cols": 16, "dtype": "float32"}, 4),
    ({"job": "qr", "mode": "reduced", "warmup_jobs": 1, "traced_jobs": 1},
     {"rows": 4000, "cols": 16, "dtype": "float32"}, 1),
    ({"job": "matmul_resplit", "matmul": False, "resplit": True, "resplit_to": 1,
      "warmup_jobs": 1, "traced_jobs": 1}, DENSE, 4),
    ({"job": "matmul_resplit", "matmul": True, "resplit": False, "resplit_to": 1,
      "warmup_jobs": 1, "traced_jobs": 1}, DENSE, 4),
], ids=["qr_r_4dev", "qr_reduced_1dev", "resplit_only_4dev", "matmul_only_4dev"])
def test_job_kinds_no_first_cell_uses(traffic, config, chips):
    """Shipped for the cells that come next (S2's TSQR, D3(d)'s resplit)."""
    result = runner.run_cell(BENCH, "matmul_n40960", seed=2, seconds=0.1, trace=False,
                             comm=_comm(chips), config=config, traffic=traffic,
                             say=lambda line: None)
    assert result["correct"] is True and result["failed"] == 0


# ---------------------------------------------------------------------- #
# work(): operations and bytes by hand
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("kind, config, traffic, chips, flop, nbytes", [
    # 6 passes of a (2^26 x 32) x (32 x 64) GEMM, 5 of them also add each row once
    ("kmeans_fit", {"rows": 2 ** 26, "features": 32, "clusters": 64, "dtype": "float32"},
     {"iterations": 5}, 1, 6 * 2 * 2 ** 26 * 32 * 64 + 5 * 2 ** 26 * 32,
     6 * 2 ** 33 + 2 ** 28),
    # 100 steps, two (16384 x 32 x 64) GEMMs each; a batch is 2 MiB
    ("lloyd_eager", {"features": 32, "clusters": 64, "dtype": "float32"},
     {"batch_rows": 16384, "steps": 100}, 1, 100 * 2 * 2 * 16384 * 32 * 64, 100 * 2 ** 21),
    # 2 n^3 and three matrices of 40960^2 * 2 bytes; on one chip the resplit moves nothing
    ("matmul_resplit", {"n": 40960, "dtype": "bfloat16"},
     {"matmul": True, "resplit": True}, 1, 137_438_953_472_000, 3 * 3_355_443_200),
    # across chips the resplit reads and writes the product once more
    ("matmul_resplit", {"n": 40960, "dtype": "bfloat16"},
     {"matmul": True, "resplit": True}, 4, 137_438_953_472_000, 5 * 3_355_443_200),
    ("matmul_resplit", {"n": 40960, "dtype": "bfloat16"},
     {"matmul": False, "resplit": True}, 4, 0, 2 * 3_355_443_200),
    # Householder's 2 m n^2 - 2 n^3 / 3; A read once, R written
    ("qr", {"rows": 1_000_000, "cols": 256, "dtype": "float32"}, {"mode": "r"}, 1,
     2 * 1_000_000 * 256 ** 2 - 2 * 256 ** 3 // 3, (1_000_000 * 256 + 256 ** 2) * 4),
    ("qr", {"rows": 1_000_000, "cols": 256, "dtype": "float32"}, {"mode": "reduced"}, 1,
     2 * (2 * 1_000_000 * 256 ** 2 - 2 * 256 ** 3 // 3), (2 * 1_000_000 * 256 + 256 ** 2) * 4),
], ids=["kmeans_fit", "lloyd_eager", "matmul_1chip", "matmul_4chip", "resplit_only", "qr_r",
        "qr_reduced"])
def test_work_by_hand(kind, config, traffic, chips, flop, nbytes):
    work = BENCH.job(kind).work(config, traffic, chips)
    assert work["flop"] == flop and work["bytes"] == nbytes


def test_roofline_of_the_first_cells():
    """The bounds PERF.md quotes: 63.3 ms of reads for a fit, 698 ms of MXU
    for a product (174 ms a chip on four), on the v5e's published peaks."""
    from chipbench.harness import device, roofline

    peaks = device.peaks_for("TPU v5 lite")
    for name, seconds, bound in (("kmeans_fit_n2e26", 0.06324, "memory"),
                                 ("matmul_n40960", 0.69766, "compute"),
                                 ("matmul_resplit_n40960_4chip", 0.17441, "compute")):
        cell = BENCH.cell(name)
        traffic = BENCH.traffic(cell)
        work = BENCH.job(traffic["job"]).work(BENCH.config(cell), traffic, cell["chips"])
        least, which = roofline.least_seconds(work, peaks, cell["chips"])
        assert which == bound and least == pytest.approx(seconds, rel=1e-3)


# ---------------------------------------------------------------------- #
# the seeded data
# ---------------------------------------------------------------------- #
def test_data_same_seed_same_values_on_any_number_of_chips():
    one, four = _comm(1), _comm(4)
    x1, c1 = data.blobs(one.mesh, "x", 7, 4096, 32, 64, 4.0, block_rows=256)
    x4, c4 = data.blobs(four.mesh, "x", 7, 4096, 32, 64, 4.0, block_rows=256)
    assert np.array_equal(np.asarray(x1), np.asarray(x4)) and np.array_equal(c1, c4)
    assert len(x4.sharding.device_set) == 4 and x4.sharding.spec[0] == "x"
    a1 = data.dense(one.mesh, "x", 7, 512, 64, 0.5, jnp.bfloat16, block_rows=32)
    a4 = data.dense(four.mesh, "x", 7, 512, 64, 0.5, jnp.bfloat16, block_rows=32)
    assert a1.dtype == jnp.bfloat16 and np.array_equal(np.asarray(a1), np.asarray(a4))
    other = data.dense(one.mesh, "x", 8, 512, 64, 0.5, jnp.bfloat16, block_rows=32)
    assert not np.array_equal(np.asarray(a1), np.asarray(other))


def test_blobs_are_blobs():
    comm = _comm(1)
    x, centers = data.blobs(comm.mesh, "x", 1, 1 << 14, 32, 64, 4.0)
    x, centers = np.asarray(x), np.asarray(centers)
    nearest = np.argmin(((x[:, None, :] - centers[None]) ** 2).sum(-1), axis=1)
    # unit noise around centres ~4 apart per coordinate: every blob is drawn
    # from, and the rows lie about sqrt(32) from their centre
    assert len(np.unique(nearest)) == 64
    radius = np.sqrt(((x - centers[nearest]) ** 2).sum(-1)).mean()
    assert 5.0 < radius < 6.2
    assert abs(np.asarray(data.dense(comm.mesh, "x", 1, 1024, 256, 2.0)).astype(np.float32).std()
               - 2.0) < 0.05


def test_block_divides_any_row_count():
    assert data._blocks(1_000_000, 1024) == (1000, 1000)
    assert data._blocks(250_000, 1024) == (1000, 250)
    assert data._blocks(100, 1024) == (100, 1)


# ---------------------------------------------------------------------- #
# the references against numpy written out longhand
# ---------------------------------------------------------------------- #
def _np_assign(x, c):
    return np.argmin(((x[:, None, :] - c[None]) ** 2).sum(-1), axis=1)


def test_lloyd_reference_against_numpy():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(600, 5)).astype(np.float32) + rng.integers(0, 3, (600, 1)) * 4
    c = x[:4].copy()
    got, inertia = lloyd_ref.lloyd(jnp.asarray(x), jnp.asarray(c), 3)
    for _ in range(3):
        lab = _np_assign(x, c)
        c = np.stack([x[lab == j].mean(0) if (lab == j).any() else c[j] for j in range(4)])
    assert np.allclose(got, c, atol=1e-5)
    assert float(inertia) == pytest.approx(((x - c[_np_assign(x, c)]) ** 2).sum(), rel=1e-4)


def test_minibatch_reference_against_numpy():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(400, 3)).astype(np.float32) * 3
    c, seen = x[:5].copy(), np.zeros(5)
    offsets = [100, 0, 300]
    got, got_seen = lloyd_ref.minibatch(jnp.asarray(x), jnp.asarray(c), jnp.asarray(offsets), 100)
    for o in offsets:
        xb = x[o:o + 100]
        lab = _np_assign(xb, c)
        for j in range(5):
            n_j = (lab == j).sum()
            if n_j:
                seen[j] += n_j
                c[j] += n_j / seen[j] * (xb[lab == j].mean(0) - c[j])
    assert np.allclose(got, c, atol=1e-5) and np.array_equal(got_seen, seen)


def test_dense_reference_against_numpy():
    rng = np.random.default_rng(2)
    a, b = rng.normal(size=(8, 64)), rng.normal(size=(64, 8))
    block = dense_ref.product_block(jnp.asarray(a, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16))
    exact = np.asarray(jnp.asarray(a, jnp.bfloat16), np.float64) @ np.asarray(
        jnp.asarray(b, jnp.bfloat16), np.float64)
    assert block.dtype == jnp.float32 and np.allclose(block, exact, atol=1e-4)
    q, r = np.linalg.qr(rng.normal(size=(200, 6)).astype(np.float32))
    assert float(dense_ref.gram_gap(jnp.asarray(q @ r), jnp.asarray(r))) < 1e-5
    assert float(dense_ref.gram_gap(jnp.asarray(q @ r), jnp.asarray(1.01 * r))) > 1e-2
    assert float(dense_ref.orthogonality_gap(jnp.asarray(q))) < 1e-5
    assert rel_err(np.array([1.0, -4.0]), np.array([1.0, -4.1])) == pytest.approx(
        0.1 / 4.1, rel=1e-4)


# ---------------------------------------------------------------------- #
# a wrong result is not `correct`
# ---------------------------------------------------------------------- #
def test_matmul_check_fails_on_a_wrong_product():
    job = BENCH.job("matmul_resplit")
    traffic = BENCH.traffic(BENCH.cell("matmul_n40960"))
    state = job.setup(DENSE, traffic, 3, _comm(1))
    c, c1 = job.job(state)
    assert job.check(state, (c, c1))[0] is True
    # a product whose accumulation lost bits: off by 3% of the largest entry
    off = c + 0.03 * float(jnp.max(jnp.abs(c._jarray.astype(jnp.float32))))
    ok, facts = job.check(state, (off, c1))
    assert ok is False and facts["corner_rel_err"] > 2.0 ** -7
    ok, facts = job.check(state, (c, ht.resplit(c, 0)))
    assert ok is False and facts["resplit_split"] == 0


def test_kmeans_check_fails_on_centres_rounded_to_bfloat16(monkeypatch):
    job = BENCH.job("kmeans_fit")
    state = job.setup(BLOBS, {"iterations": 2, "tol": -1.0}, 3, _comm(1))
    out = job.job(state)
    ok, facts = job.check(state, out)
    assert ok is True and facts["centers_err"] < 1e-5
    # a reference whose centres carry bfloat16's 8 bits stands for a fit that kept its sums so
    lloyd = lloyd_ref.lloyd

    def rounded(x, c, iters):
        centers, inertia = lloyd(x, c, iters)
        return centers.astype(jnp.bfloat16).astype(jnp.float32), inertia

    monkeypatch.setattr(job.reference, "lloyd", rounded)
    ok, facts = job.check(state, out)
    assert ok is False and facts["centers_err"] > job.CENTER_TOL


def test_kmeans_job_raises_when_the_fit_stops_early():
    job = BENCH.job("kmeans_fit")
    state = job.setup(BLOBS, {"iterations": 50, "tol": 1e30}, 3, _comm(1))
    with pytest.raises(RuntimeError, match="not 50"):
        job.job(state)


# ---------------------------------------------------------------------- #
# the window
# ---------------------------------------------------------------------- #
def test_window_counts_a_job_that_raises():
    calls = iter(range(100))

    def job(_):
        if next(calls) == 1:
            raise ValueError("one bad job")
        return (jnp.ones(4),)

    w = Window().run(job, None, jobs=5)
    assert (len(w.samples), w.failed, w.attempted) == (4, 1, 5)
    assert all(s > 0 for s in w.samples)


def test_quantiles():
    q = quantiles([5.0, 1.0, 3.0, 2.0, 4.0])
    assert q == {"n": 5, "min": 1.0, "p25": 2.0, "p50": 3.0, "p75": 4.0,
                 "p90": pytest.approx(4.6), "max": 5.0}
