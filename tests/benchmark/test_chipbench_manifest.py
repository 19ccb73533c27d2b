"""``BENCHMARK.json`` against the files it names and the rules it is held to,
the peaks table, the device check, ``run.py``'s refusals, and discovery: a
cell made of new files only runs with no edit to a file that is there."""

import json
import os
import re
import shutil
import subprocess
import sys
import types

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench.harness import device, manifest, runner  # noqa: E402
from heat_tpu.core.communication import Communication  # noqa: E402

BENCH = manifest.Manifest(REPO)
DATA = BENCH.data
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _names(group):
    return [e["name"] for e in DATA[group]]


def test_manifest_has_exactly_the_contracts_keys():
    assert set(DATA) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= DATA["run_seconds"] <= 51 and isinstance(DATA["run_seconds"], int)
    assert DATA["command"] == ["python3", "chipbench/run.py"]
    assert all(os.path.isdir(os.path.join(REPO, p)) for p in DATA["paths"])
    every = [n for g in ("configs", "workloads", "end_to_end", "per_layer") for n in _names(g)]
    assert len(every) == len(set(every)) and all(NAME.match(n) for n in every)


def test_cells_and_chips():
    cells = DATA["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(pairs) == len(set(pairs)), "a pair of configuration and traffic appears once"
    assert {c["config"] for c in cells} == set(_names("configs"))
    four = [c for c in cells if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in cells)
    assert len(four) <= max(1, len(cells) // 4)
    assert all(len(c["why"]) <= 200 for c in cells + DATA["configs"])


def test_end_to_end_metrics():
    e2e = {m["name"]: m for m in DATA["end_to_end"]}
    assert set(e2e) == {"job_s", "peak_hbm_gib", "setup_s"}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("entry", DATA["configs"], ids=_names("configs"))
def test_configuration_file(entry):
    path = os.path.join(REPO, entry["file"])
    assert any(entry["file"].startswith(p + "/") for p in DATA["paths"])
    config = json.load(open(path))
    assert config["name"] == entry["name"] and config["source"] == entry["source"]
    assert entry["source"].startswith("https://")
    # every reduced key is a size the file holds, with its reason, and no width
    assert set(entry["reduced"]) == set(config["reduced"]) <= set(config)
    assert not [k for k in entry["reduced"] if k.endswith(("_dim", "_rank")) or k == "features"]
    assert os.path.isfile(os.path.join(REPO, config["reference"]))
    assert config["guarantees"] and config["deployment"] and config["assumed"]


@pytest.mark.parametrize("cell", DATA["workloads"], ids=_names("workloads"))
def test_cell_names_files_that_exist(cell):
    config, traffic = BENCH.config(cell), BENCH.traffic(cell)
    job = BENCH.job(traffic["job"])
    for export in ("setup", "job", "check", "work"):
        assert callable(getattr(job, export))
    work = job.work(config, traffic, cell["chips"])
    assert work["flop"] > 0 and work["bytes"] > 0
    assert traffic["warmup_jobs"] >= 1 and traffic["traced_jobs"] >= 1
    # every cell reports setup_s, another end-to-end metric and a layer metric
    assert {"setup_s", "job_s"} <= {m["name"] for m in BENCH.metrics("end_to_end", cell["name"])}
    assert BENCH.metrics("per_layer", cell["name"])


@pytest.mark.parametrize("metric", DATA["per_layer"], ids=_names("per_layer"))
def test_layer_metric_has_its_reader(metric):
    assert callable(BENCH.reader(metric["name"]))
    assert metric["moves"] in _names("end_to_end")
    assert metric["source"] in SOURCES and metric["layer"] and metric["unit"]
    assert set(metric.get("workloads", [])) <= set(_names("workloads"))
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"
    # a reader that finds nothing to read returns nothing
    empty = runner.Context({}, {}, {}, 1, {"flop": 1, "bytes": 1}, [],
                           counters={"program_cache_misses": 0, "compile_cache_files_added": 0})
    assert BENCH.reader(metric["name"])(empty) in (None, 0)


def test_unknown_names_are_errors():
    with pytest.raises(KeyError, match="not one of the manifest's workloads"):
        BENCH.cell("no_such_cell")
    with pytest.raises(FileNotFoundError):
        BENCH.job("no_such_job")
    with pytest.raises(FileNotFoundError):
        BENCH.reader("no_such_metric")


# ---------------------------------------------------------------------- #
# peaks and the device check
# ---------------------------------------------------------------------- #
def test_peaks_exact_key():
    v5e = device.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2 ** 30 and "Google Cloud" in v5e["source"]


@pytest.mark.parametrize("kind", ["TPU v5", "tpu v5 lite", "TPU v5p", "cpu", ""])
def test_peaks_unknown_device_raises(kind):
    with pytest.raises(KeyError, match="is not in"):
        device.peaks_for(kind)


def _fake(platform, kind, n):
    return [types.SimpleNamespace(platform=platform, device_kind=kind) for _ in range(n)]


@pytest.mark.parametrize("devices, chips, message", [
    (None, 1, "runs on a TPU"),  # this sandbox: JAX finds the CPU
    (_fake("tpu", "TPU v5 lite", 1), 4, r"asks for 4 chip\(s\), JAX found 1"),
    (_fake("tpu", "TPU v5 lite", 4), 1, r"asks for 1 chip\(s\), JAX found 4"),
    (_fake("tpu", "TPU v9", 1), 1, "is not in"),
], ids=["cpu", "too_few", "too_many", "unlisted_kind"])
def test_device_check_refuses(devices, chips, message):
    with pytest.raises((RuntimeError, KeyError), match=message):
        device.require(chips, devices)


def test_device_check_accepts_the_listed_chip():
    assert device.require(4, _fake("tpu", "TPU v5 lite", 4))["bf16_flops_per_s"] == 197e12


# ---------------------------------------------------------------------- #
# run.py's refusals: no chip, and a directory without the program
# ---------------------------------------------------------------------- #
def _run_py(root, cell="matmul_n40960"):
    return subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=root,
        capture_output=True, text=True, timeout=240)


def test_run_py_refuses_the_cpu():
    done = _run_py(REPO)
    assert done.returncode != 0
    assert done.stdout == "", done.stdout[-300:]
    assert "tpu" in done.stderr.lower(), done.stderr[-500:]


def test_run_py_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in DATA["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = _run_py(str(tmp_path))
    assert done.returncode != 0 and done.stdout == ""
    assert "holds no heat_tpu" in done.stderr


# ---------------------------------------------------------------------- #
# discovery: new files and one manifest entry each, nothing edited
# ---------------------------------------------------------------------- #
NEW_JOB = '''
import types
import heat_tpu as ht

def setup(config, traffic, seed, comm):
    ht.random.seed(seed)
    return types.SimpleNamespace(x=ht.random.randn(config["rows"], 4, split=0, comm=comm))

def job(s):
    return (s.x.sum(axis=0),)

def check(s, out):
    return out[0].shape == (4,), {"columns": out[0].shape[0]}

def work(config, traffic, chips):
    return {"flop": config["rows"] * 4, "bytes": config["rows"] * 16, "derived": {}}
'''

NEW_READER = '''
def read(ctx):
    return float(len(ctx.samples))
'''


def test_a_cell_of_new_files_runs_with_no_edit(tmp_path):
    root = tmp_path / "tree"
    shutil.copytree(os.path.join(REPO, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*") if p.is_file()}
    (root / "chipbench/configs/columns.json").write_text(json.dumps({"rows": 64}))
    (root / "chipbench/traffic/sum_once.json").write_text(
        json.dumps({"job": "column_sum", "warmup_jobs": 1, "traced_jobs": 1}))
    (root / "chipbench/jobs/column_sum.py").write_text(NEW_JOB)
    (root / "chipbench/layer_metrics/jobs_in_window.py").write_text(NEW_READER)
    data = json.loads(json.dumps(DATA))
    data["configs"].append({"name": "columns", "source": "https://example.org", "reduced": [],
                            "file": "chipbench/configs/columns.json", "why": "test"})
    data["workloads"].append({"name": "column_sum_tiny", "config": "columns",
                              "traffic": "sum_once", "chips": 1, "why": "test"})
    data["per_layer"].append({"name": "jobs_in_window", "unit": "count", "better": "higher",
                              "source": "host_clock", "layer": "test", "moves": "job_s",
                              "workloads": ["column_sum_tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))

    grown = manifest.Manifest(str(root))
    comm = Communication(Mesh(np.asarray(jax.devices()[:1]), ("x",)), "x")
    lines = []
    result = runner.run_cell(grown, "column_sum_tiny", seed=1, seconds=0.05, trace=True,
                             comm=comm, say=lines.append)
    assert result["correct"] and result["failed"] == 0
    assert result["metrics"]["jobs_in_window"]["value"] >= runner.MIN_WINDOW_JOBS
    assert result["metrics"]["recompiles_in_window"]["value"] == 0
    assert any(line.startswith("# check correct=True") for line in lines)
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"
