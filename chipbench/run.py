"""One run of one cell of ``BENCHMARK.json`` on the TPU this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``); the lines before it start with ``#`` and give the window's
quartiles, the numbers derived from ``job_s``, both memory peaks, the
counters and the facts of the correctness check.  ``setup_s`` runs from the
start of this process to the first timed job and leaves out the seconds in
which the TPU runtime started (``chip_init`` on the ``# start`` line): they
are the machine's, not the program's, and vary by seconds from one process to
the next.  With no TPU, another
number of chips than the cell names, or a ``device_kind`` that
``peaks.json`` does not list, it raises before printing anything.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--keep-trace", metavar="FILE.json.gz",
                        help="also keep the reduced trace of a --trace 1 run")
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "heat_tpu")):
        raise SystemExit(f"{ROOT} holds no heat_tpu: the benchmark runs the program of its checkout")
    sys.path.insert(0, ROOT)

    import jax

    # a missing chip is an error here, not jax's quiet fall to the CPU; pinned
    # before heat_tpu is imported, whose import already touches the backend
    jax.config.update("jax_platforms", "tpu")

    from chipbench.harness import device, manifest, runner

    bench = manifest.Manifest(ROOT)
    t_jax = time.perf_counter()
    peaks = device.require(bench.cell(args.workload)["chips"])
    t_chip = time.perf_counter()

    from heat_tpu.utils import compile_cache

    chip_init = t_chip - t_jax
    print(f"# start: import_jax={t_jax - T_START:.3f} chip_init={chip_init:.3f} (not in setup_s) "
          f"import_heat_tpu={time.perf_counter() - t_chip:.3f}", flush=True)
    result = runner.run_cell(
        bench, args.workload, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        peaks=peaks, cache_dir=compile_cache.configure(), t_start=T_START + chip_init,
        keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
