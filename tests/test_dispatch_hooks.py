"""The one dispatch launch (``core._operations._launch``) and the observer
slots it reads (``core._hooks``).

Every cached program of the five dispatch helpers (local, binary, reduce,
cum, program) runs in one place, so each observer is checked on each kind:
the flight recorder's ring receives the op's name, a program that raises
reaches the memory ledger's ``note_oom`` with the kind's ``where`` and is
re-raised, and the sanitizer validates the result.  Then each tool is armed
by its environment variable before ``import heat_tpu`` in a fresh
interpreter, and must see the first eager op: no module keeps a copy of a
slot that its own import could overwrite.
"""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import _cache, _hooks, _operations, sanitation
from heat_tpu.utils import flightrec, memledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _double(a):
    # module-level: its identity keys _program_op's cache
    return a * 2


# kind -> (the helper called on x, the op name the observers are given)
KINDS = {
    "local": (lambda x: _operations._local_op(jnp.exp, x), "exp"),
    "binary": (lambda x: _operations._binary_op(jnp.add, x, x), "add"),
    "reduce": (lambda x: _operations._reduce_op(jnp.sum, x, axis=0), "sum"),
    "cum": (lambda x: _operations._cum_op(jnp.cumsum, x, 0), "cumsum"),
    "program": (lambda x: _operations._program_op(_double, (x,), 0), "_double"),
}


def _operand():
    # divisible by the mesh: no pad, so every kind takes its cached program
    return ht.arange(8 * ht.communication.get_comm().size, dtype=ht.float32, split=0)


def _observe_flightrec(kind, run, name, tmp_path, monkeypatch):
    path = flightrec.enable(str(tmp_path), rank=0)
    try:
        run(_operand())
        flightrec.sync()
        ops = {}
        for rec in flightrec.read_ring(path)["records"]:
            if rec["k"] == "d":
                ops.update(rec["ops"])
    finally:
        flightrec.disable()
    assert name in ops


class _PlantedOOM(RuntimeError):
    pass


def _observe_memledger(kind, run, name, tmp_path, monkeypatch):
    real = _cache.cached_program

    def raising_program(comm, key, builder):
        entry = real(comm, key, builder)
        if entry is _cache.SLOW:
            return entry

        def prog(*args):
            raise _PlantedOOM("RESOURCE_EXHAUSTED: planted")

        return (prog,) + tuple(entry[1:])

    seen = []
    monkeypatch.setattr(_cache, "cached_program", raising_program)
    monkeypatch.setattr(memledger, "note_oom", lambda e, where, nbytes: seen.append((type(e), where)))
    x = _operand()
    was_on = memledger.enabled()
    memledger.enable()
    try:
        with pytest.raises(_PlantedOOM):
            run(x)
    finally:
        if not was_on:
            memledger.disable()
    assert seen == [(_PlantedOOM, f"dispatch.{kind}")]


def _observe_checks(kind, run, name, tmp_path, monkeypatch):
    seen = []

    def recording_check(ret, where):
        seen.append(where)
        return sanitation.validate_dispatch(ret, where)

    was_on = sanitation.checks_enabled()
    sanitation.enable_checks()
    try:
        monkeypatch.setattr(_hooks, "checks", recording_check)
        run(_operand())
    finally:
        monkeypatch.undo()
        if not was_on:
            sanitation.disable_checks()
    assert seen == [f"dispatch.{kind}"]


OBSERVERS = {
    "flightrec": _observe_flightrec,
    "memledger": _observe_memledger,
    "checks": _observe_checks,
}


@pytest.mark.parametrize("observer", sorted(OBSERVERS))
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_one_launch_feeds_every_observer(kind, observer, tmp_path, monkeypatch):
    run, name = KINDS[kind]
    OBSERVERS[observer](kind, run, name, tmp_path, monkeypatch)


def test_a_consumed_donation_leaves_the_ledger_at_once():
    """``z += 1.0`` donates z's buffer; the launch tells the ledger, so the
    dead buffer leaves it even while a handle to it is still held."""
    was_on = memledger.enabled()
    memledger.enable()
    prev = memledger.set_dispatch_threshold(0)
    try:
        z = ht.zeros((8 * ht.communication.get_comm().size,), dtype=ht.float32, split=0)
        old = z._parray
        base = memledger.live_bytes()
        z += 1.0
        assert old.is_deleted()
        assert memledger.live_bytes() == base
    finally:
        memledger.set_dispatch_threshold(prev)
        if not was_on:
            memledger.disable()


def test_launch_results_match_numpy():
    """The five kinds through their cached programs give numpy's values."""
    x = _operand()
    ref = np.arange(x.shape[0], dtype=np.float32)
    want = {
        "local": np.exp(ref),
        "binary": ref + ref,
        "reduce": ref.sum(axis=0),
        "cum": np.cumsum(ref),
        "program": ref * 2,
    }
    for kind, (run, _name) in KINDS.items():
        np.testing.assert_allclose(run(x).numpy(), want[kind], rtol=1e-6, err_msg=kind)


# each tool armed by its environment variable before ``import heat_tpu``;
# the script checks that the slot holds the tool and that ``x + x``, the
# first eager op, is seen
ENV_ARMED = {
    "flightrec": (
        "HEAT_TPU_FLIGHTREC_DIR",
        "{tmp}",
        "from heat_tpu.utils import flightrec\n"
        "assert _hooks.flightrec is flightrec\n"
        "y = x + x\n"
        "flightrec.sync()\n"
        "ops = {}\n"
        "for rec in flightrec.read_ring(flightrec.recorder().path)['records']:\n"
        "    if rec['k'] == 'd':\n"
        "        ops.update(rec['ops'])\n"
        "assert ops.get('add') == 1, ops\n",
    ),
    "memledger": (
        "HEAT_TPU_MEMLEDGER",
        "1",
        "from heat_tpu.utils import memledger\n"
        "assert _hooks.memledger is memledger\n"
        "memledger.set_dispatch_threshold(0)\n"
        "base = memledger.live_bytes()\n"
        "y = x + x\n"
        "assert memledger.live_bytes() - base == y._parray.nbytes\n",
    ),
    "checks": (
        "HEAT_TPU_CHECKS",
        "1",
        "from heat_tpu.core import sanitation\n"
        "assert _hooks.checks is sanitation.validate_dispatch\n"
        "seen = []\n"
        "real = _hooks.checks\n"
        "_hooks.checks = lambda r, w: (seen.append(w), real(r, w))[1]\n"
        "y = x + x\n"
        "assert seen == ['dispatch.binary'], seen\n",
    ),
}


@pytest.mark.parametrize("tool", sorted(ENV_ARMED))
def test_env_armed_tool_sees_the_first_op(tool, tmp_path):
    var, value, body = ENV_ARMED[tool]
    code = (
        "import heat_tpu as ht\n"
        "from heat_tpu.core import _hooks\n"
        "x = ht.arange(16, dtype=ht.float32, split=0)\n" + body + "print('SEEN-OK')\n"
    )
    env = {k: v for k, v in os.environ.items() if not k.startswith("HEAT_TPU_")}
    env.update({var: value.format(tmp=tmp_path), "JAX_PLATFORMS": "cpu"})
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=env, capture_output=True, text=True, timeout=240, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SEEN-OK" in out.stdout
