"""Continuous-benchmarking harness (reference: ``benchmarks/cb/main.py``).

The reference decorates per-domain benchmark callables with perun (runtime +
energy) and tracks regressions per PR.  Here each benchmark is timed with
``utils.profiler.timeit_min`` and results are printed as JSON lines — one per
benchmark — for the same regression-tracking purpose.

Run: ``python benchmarks/main.py [linalg|cluster|manipulations|preprocessing|nn|all]``
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# run on the default accelerator; HEAT_BENCH_PLATFORM=cpu forces the host mesh
if os.environ.get("HEAT_BENCH_PLATFORM") == "cpu":
    import jax

    jax.config.update("jax_platforms", "cpu")


def _run(name: str, fn, reps: int = 3) -> None:
    import heat_tpu as ht

    best = ht.utils.profiler.timeit_min(fn, reps=reps)
    print(json.dumps({"benchmark": name, "seconds": round(best, 5), "reps": reps}))


def bench_linalg() -> None:
    import heat_tpu as ht

    n = 2048
    a = ht.random.randn(n, n, split=ht.axisspec.named(0))
    b = ht.random.randn(n, n, split=ht.axisspec.named(1))
    _run("matmul_2048_s0xs1", lambda: a @ b)
    ts = ht.random.randn(2**16, 64, split=ht.axisspec.named(0))
    _run("tsqr_65536x64", lambda: ht.linalg.qr(ts).R)
    _run("hsvd_rank10_65536x64", lambda: ht.linalg.svdtools.hsvd_rank(ts, 10))
    spd = ht.random.randn(512, 512, split=ht.axisspec.named(0))
    M = spd @ spd.T + ht.eye(512) * 512.0
    v = ht.random.randn(512)
    _run("cg_512", lambda: ht.linalg.solver.cg(M, v, maxit=50))


def bench_cluster() -> None:
    import heat_tpu as ht

    X = ht.random.randn(2**16, 32, split=ht.axisspec.named(0))
    _run("kmeans_65536x32_k16_10it",
         lambda: ht.cluster.KMeans(n_clusters=16, max_iter=10, tol=0.0, init="random", random_state=0).fit(X).inertia_)
    _run("cdist_4096x4096", lambda: ht.spatial.cdist(X[:4096], X[:4096], quadratic_expansion=True))


def bench_manipulations() -> None:
    import heat_tpu as ht

    x = ht.random.randn(2**20, split=ht.axisspec.named(0))
    _run("sort_1M", lambda: ht.sort(x)[0])
    m = ht.random.randn(2048, 2048, split=ht.axisspec.named(0))
    _run("resplit_2048sq_0to1", lambda: m.resplit(1))
    _run("reshape_1M", lambda: x.reshape(1024, 1024))


def bench_preprocessing() -> None:
    import heat_tpu as ht

    X = ht.random.randn(2**18, 64, split=ht.axisspec.named(0))
    _run("standard_scaler_262kx64", lambda: ht.preprocessing.StandardScaler().fit(X).transform(X))
    _run("robust_scaler_262kx64", lambda: ht.preprocessing.RobustScaler().fit(X).transform(X))


def bench_nn() -> None:
    import jax

    import heat_tpu as ht

    ds = ht.utils.data.MNISTDataset(root="./data", synthetic_n=8192)
    model = ht.nn.Sequential(
        ht.nn.Flatten(), ht.nn.Linear(784, 256), ht.nn.ReLU(), ht.nn.Linear(256, 10)
    )
    opt = ht.optim.DataParallelOptimizer("sgd", lr=0.05)
    dp = ht.nn.DataParallel(model, optimizer=opt)
    params = dp.init(jax.random.key(0))
    state = opt.init_state(params)
    step = dp.make_train_step(ht.nn.functional.cross_entropy)
    xb, yb = ds[0:1024]
    params, state, _ = step(params, state, xb._jarray, yb._jarray)  # compile

    def run_epoch():
        nonlocal params, state
        for lo in range(0, len(ds), 1024):
            xb, yb = ds[lo : lo + 1024]
            params, state, l = step(params, state, xb._jarray, yb._jarray)
        return l

    _run("mlp_mnist_epoch_8192", run_epoch, reps=2)


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    table = {
        "linalg": bench_linalg,
        "cluster": bench_cluster,
        "manipulations": bench_manipulations,
        "preprocessing": bench_preprocessing,
        "nn": bench_nn,
    }
    if which == "all":
        import gc

        for fn in table.values():
            fn()
            gc.collect()  # drop dead device buffers between domains (the
            # forced-host-device CPU collectives are flaky when old buffers
            # pile up across domains)
    else:
        table[which]()


if __name__ == "__main__":
    main()
