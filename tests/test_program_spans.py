"""Program spans (ISSUE 25): a span is a ``jax.profiler.TraceAnnotation`` named
``ht.<layer>.<what>``, made only while a profile records.  With nothing
recording an eager op makes no annotation; under a profile recorded here on
the CPU the dispatch helpers, ``ht.matmul``, ``ht.spatial.cdist`` (a library
function as one program, ISSUE 29) and a real ``resplit`` give their named spans, properly nested; telemetry's ring still gets its records."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import heat_tpu as ht
from heat_tpu.core import _cache, _operations
from heat_tpu.core.communication import Communication
from heat_tpu.utils import profiler, telemetry

KINDS = ("local", "binary", "reduce", "cum", "matmul", "program")


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


@pytest.fixture(scope="module")
def comm():
    return Communication(Mesh(np.asarray(jax.devices()[:4]), ("x",)), "x")


@pytest.fixture(scope="module")
def a(comm):
    return ht.arange(64, dtype=ht.float32, split=0, comm=comm).reshape(8, 8)


# one call of each dispatch helper, of the product and of its 1-D branch
OPS = {
    "local": lambda a: _operations._local_op(jnp.sin, a),
    "binary": lambda a: _operations._binary_op(jnp.add, a, a),
    "reduce": lambda a: _operations._reduce_op(jnp.sum, a, axis=0),
    "cum": lambda a: _operations._cum_op(jnp.cumsum, a, 0),
    "matmul": lambda a: ht.matmul(a, a),
    "dot": lambda a: ht.dot(a[0], a[1]),
    "program": lambda a: ht.spatial.cdist(a, quadratic_expansion=True),
}


def _record(tmp_path, work):
    """``(name, start, end, stats)`` of every ``ht.`` annotation of ``work()``,
    by start, from a profile recorded as the benchmark records its own."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                           for e in line.events if e.name.startswith("ht.")]
    return sorted(events, key=lambda e: (e[1], -e[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


class _Counting(jax.profiler.TraceAnnotation):
    made = 0

    def __init__(self, *args, **kwargs):
        type(self).made += 1
        super().__init__(*args, **kwargs)


# ---------------------------------------------------------------------- #
# nothing records
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("kind", list(OPS))
def test_no_annotation_while_nothing_records(kind, a, monkeypatch):
    OPS[kind](a)  # compiled before the count
    monkeypatch.setattr(_Counting, "made", 0)
    monkeypatch.setattr(_cache, "TraceAnnotation", _Counting)
    assert not _cache.recording()
    OPS[kind](a)
    assert _Counting.made == 0


def test_span_is_the_null_span_while_nothing_listens():
    assert telemetry.span("comm.resplit", split=0) is telemetry._NULL_SPAN
    assert telemetry.span("x").set(n=1) is telemetry._NULL_SPAN

    @telemetry.traced("io.save")
    def save(x):
        return x + 1

    assert save(1) == 2 and not telemetry._ring


# ---------------------------------------------------------------------- #
# a profile records, telemetry disabled
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def recorded(a, comm, tmp_path_factory):
    """One profile of every op of ``OPS``, a resplit in one piece and a tiled
    one, and the paths that reach no cached program."""
    b = ht.zeros_like(a)
    deep = ht.arange(1 << 10, dtype=ht.float32, split=0, comm=comm).reshape(8, 8, 16)

    def work():
        assert _cache.recording()
        for op in OPS.values():
            op(a)
        _operations._local_op(lambda x: jnp.clip(x, 0, 1), a)  # a per-call lambda: eager
        _operations._local_op(jnp.sin, a, out=b)
        jax.block_until_ready(ht.resplit(a, 1)._jarray)
        jax.block_until_ready(comm.resplit(deep._jarray, 1, memory_budget=1 << 10))

    for op in OPS.values():
        op(a)
    assert not telemetry.enabled()
    events = _record(tmp_path_factory.mktemp("profile"), work)
    assert not _cache.recording()
    return events


@pytest.mark.parametrize("kind", KINDS)
def test_dispatch_span_holds_its_launch(recorded, kind):
    name = f"ht.dispatch.{kind}"
    spans = [e for e in recorded if e[0] == name]
    want = {"local": 3, "matmul": 2}.get(kind, 1)  # local: cached, lambda, out=; matmul: and dot
    assert len(spans) == want
    launches = [e for e in recorded if e[0] == _cache.LAUNCH_SPAN]
    first = spans[0]
    assert first[3]["op"] == {"local": "sin", "binary": "add", "reduce": "sum", "cum": "cumsum",
                              "matmul": "matmul", "program": "_cdist_quadratic"}[kind]
    assert sum(_inside(l, first) for l in launches) == 1
    if kind == "matmul":
        assert spans[1][3]["op"] == "dot" and sum(_inside(l, spans[1]) for l in launches) == 1


def test_paths_with_no_cached_program_have_the_span_and_no_launch(recorded):
    launches = [e for e in recorded if e[0] == _cache.LAUNCH_SPAN]
    eager = [e for e in recorded if e[0] == "ht.dispatch.local"][1:]
    assert [e[3]["op"] for e in eager] == ["<lambda>", "sin"]
    assert not any(_inside(l, e) for l in launches for e in eager)


def test_spans_are_properly_nested(recorded):
    stack = []
    for ev in recorded:
        while stack and stack[-1][2] <= ev[1]:
            stack.pop()
        assert not stack or _inside(ev, stack[-1]), (ev, stack[-1])
        stack.append(ev)
    # every launch of a dispatch helper lies in a kind span; the tile
    # programs' launches lie in the resplit's span
    kinds = [e for e in recorded if e[0].startswith("ht.dispatch.") and e[0] != _cache.LAUNCH_SPAN]
    resplits = [e for e in recorded if e[0] == "ht.comm.resplit"]
    for launch in (e for e in recorded if e[0] == _cache.LAUNCH_SPAN):
        assert any(_inside(launch, e) for e in kinds + resplits)


def test_resplit_span_with_telemetry_disabled(recorded):
    whole, tiled = [e for e in recorded if e[0] == "ht.comm.resplit"]
    assert whole[3]["split"] == 1 and whole[3]["tiles"] == 1 and whole[3]["nbytes"] == 256
    assert tiled[3]["tiles"] == 4  # 4 KiB along the free third axis, 1 KiB a tile
    launches = [e for e in recorded if e[0] == _cache.LAUNCH_SPAN]
    # the monolithic resplit is one cached program on the multi-device mesh
    assert whole[3]["path"] == "program"
    assert sum(_inside(l, whole) for l in launches) == 1
    # init, then slice, move and update for every tile
    assert sum(_inside(l, tiled) for l in launches) == 1 + 3 * tiled[3]["tiles"]
    assert not telemetry._ring


# ---------------------------------------------------------------------- #
# telemetry enabled
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("kind", [k for k in KINDS if k != "matmul"])
def test_ring_still_gets_its_dispatch_record(kind, a):
    OPS[kind](a)
    telemetry.enable()
    OPS[kind](a)
    (rec,) = [r for r in telemetry._ring if r[0] == f"dispatch.{kind}"]
    assert rec[5]["cache"] == "hit" and rec[5]["op"]


def test_both_listen(a, tmp_path):
    OPS["binary"](a)
    telemetry.enable()

    def work():
        OPS["binary"](a)
        with telemetry.span("comm.resplit", split=1) as sp:
            sp.set(nbytes=4)
        with telemetry.span("quiet", xprof=False):
            pass

    events = _record(tmp_path, work)
    assert [e[0] for e in events] == ["ht.dispatch.binary", "ht.dispatch.launch", "ht.comm.resplit"]
    ring = {r[0]: r for r in telemetry._ring}
    assert set(ring) == {"dispatch.binary", "comm.resplit", "quiet"}
    assert ring["comm.resplit"][5] == {"split": 1, "nbytes": 4}


def test_profile_span_keeps_set_working_and_carries_its_attributes(tmp_path):
    def work():
        with telemetry.span("optim.step", sync="bucketed") as sp:
            assert sp.set(late=1) is sp
        assert telemetry.span("quiet", xprof=False) is telemetry._NULL_SPAN

    (event,) = _record(tmp_path, work)
    assert event[0] == "ht.optim.step" and event[3] == {"sync": "bucketed"}
    assert not telemetry._ring


def test_operator_trace_turns_the_spans_on(a, tmp_path):
    """``ht.utils.profiler.trace`` is ``jax.profiler.trace``: any profile arms."""
    OPS["reduce"](a)
    with profiler.trace(str(tmp_path)):
        assert _cache.recording()
        OPS["reduce"](a)
    assert not _cache.recording()
    assert glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
