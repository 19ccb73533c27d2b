"""``ht.spatial`` as cached programs (ISSUE 29): a call of ``cdist``, ``rbf``
or ``manhattan`` builds its whole ``jnp`` expression once as one program out
of the dispatch layer's cache and launches it once.  The values, the result's
split and dtype are what the op-by-op expression gave; what selects the path
is read off the operands: padded operands and tracers run the same compute
function un-jitted.  (That one call is one ``ht.dispatch.program`` span
holding one ``ht.dispatch.launch``: ``tests/test_program_spans.py``, whose
tables took the new kind.)"""

import jax
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import _cache
from heat_tpu.spatial import distance

N, M, D = 64, 16, 8  # both row counts divide the 8-device mesh
SIGMA = 1.7
SPLITS = [(None, None), (0, None), (None, 0), (0, 0)]


def _np_sq(x, y):
    return ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)


# name -> (the public call, the plain numpy formula, the compute function un-jitted)
FORMS = {
    "cdist_quadratic": (
        lambda x, y: ht.spatial.cdist(x, y, quadratic_expansion=True),
        lambda x, y: np.sqrt(_np_sq(x, y)),
        distance._cdist_quadratic,
    ),
    "cdist_direct": (
        lambda x, y: ht.spatial.cdist(x, y),
        lambda x, y: np.sqrt(_np_sq(x, y)),
        distance._cdist_direct,
    ),
    "cdist_small": (
        lambda x, y: ht.spatial.cdist_small(x, y, quadratic_expansion=True),
        lambda x, y: np.sqrt(_np_sq(x, y)),
        distance._cdist_quadratic,
    ),
    "rbf_quadratic": (
        lambda x, y: ht.spatial.rbf(x, y, sigma=SIGMA, quadratic_expansion=True),
        lambda x, y: np.exp(-_np_sq(x, y) / (2.0 * SIGMA * SIGMA)),
        lambda x, y: distance._rbf(x, y, 2.0 * SIGMA * SIGMA, True),
    ),
    "rbf_direct": (
        lambda x, y: ht.spatial.rbf(x, y, sigma=SIGMA),
        lambda x, y: np.exp(-_np_sq(x, y) / (2.0 * SIGMA * SIGMA)),
        lambda x, y: distance._rbf(x, y, 2.0 * SIGMA * SIGMA, False),
    ),
    "manhattan": (
        lambda x, y: ht.spatial.manhattan(x, y),
        lambda x, y: np.abs(x[:, None, :] - y[None, :, :]).sum(-1),
        distance._manhattan,
    ),
}


def _rows(n, seed=0):
    return np.random.default_rng(seed).normal(size=(n, D)).astype(np.float32)


def _want_split(sx, sy):
    return 0 if sx == 0 else 1 if sy == 0 else None


@pytest.fixture(autouse=True)
def _fresh_stats():
    _cache.reset_cache_stats()


# ---------------------------------------------------------------------- #
# (a) values, split and dtype over the four combinations of operand splits
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("sx,sy", SPLITS)
@pytest.mark.parametrize("form", list(FORMS))
def test_agrees_with_numpy_and_with_the_unjitted_expression(form, sx, sy):
    call, formula, compute = FORMS[form]
    xn, yn = _rows(N), _rows(M, 1)
    x, y = ht.array(xn, split=sx), ht.array(yn, split=sy)
    got = call(x, y)
    assert got.split == _want_split(sx, sy) and got.dtype == ht.float32 and got.shape == (N, M)
    assert not got._pad and got._jarray.sharding == x.comm.sharding(2, got.split)
    np.testing.assert_allclose(got.numpy(), formula(xn, yn), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got.numpy(), np.asarray(compute(x._jarray, y._jarray)), rtol=2e-6, atol=2e-6)
    assert _cache.cache_stats()["hits"] + _cache.cache_stats()["misses"] == 1  # it was a program


@pytest.mark.parametrize("form", list(FORMS))
def test_y_defaults_to_x(form):
    call, formula, _ = FORMS[form]
    xn = _rows(N)
    got = call(ht.array(xn, split=0), None)
    assert got.split == 0 and got.shape == (N, N)
    # the quadratic form's diagonal is the root of a rounding error, not 0
    np.testing.assert_allclose(got.numpy(), formula(xn, xn), rtol=2e-5, atol=5e-3)


# ---------------------------------------------------------------------- #
# (b) one program a signature, whatever the data and whatever sigma.  The
# loops split x alone, so their program holds no collective: on the CPU mesh
# a hundred programs in flight with an all-gather each starve XLA's
# in-process rendezvous, which aborts the process
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("form", list(FORMS))
def test_one_miss_then_hits_on_fresh_data_of_the_same_shapes(form):
    call = FORMS[form][0]
    # a signature no other test of this process uses, so the first call builds
    n = N + 8 * (1 + list(FORMS).index(form))
    y = ht.array(_rows(M, 1))
    call(ht.array(_rows(n), split=0), y)
    assert _cache.cache_stats() == {"hits": 0, "misses": 1, "slow": 0}
    _cache.reset_cache_stats()
    for seed in range(100):
        call(ht.array(_rows(n, seed + 2), split=0), y)
    assert _cache.cache_stats() == {"hits": 100, "misses": 0, "slow": 0}


@pytest.mark.parametrize("quadratic", [True, False])
def test_a_new_sigma_compiles_nothing(quadratic):
    xn, yn = _rows(N + 56), _rows(M, 1)
    x, y = ht.array(xn, split=0), ht.array(yn)
    ht.spatial.rbf(x, y, sigma=0.5, quadratic_expansion=quadratic)
    _cache.reset_cache_stats()
    for sigma in np.linspace(0.6, 3.0, 100):
        got = ht.spatial.rbf(x, y, sigma=float(sigma), quadratic_expansion=quadratic)
    assert _cache.cache_stats() == {"hits": 100, "misses": 0, "slow": 0}
    np.testing.assert_allclose(got.numpy(), np.exp(-_np_sq(xn, yn) / 18.0), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------- #
# (c) what cannot key a program runs the same expression op by op
# ---------------------------------------------------------------------- #
RAGGED = {
    "ragged_x": (N - 3, 0, M, None),   # result rows ragged
    "ragged_y": (N, None, M - 3, 0),   # result columns ragged
    "both": (N - 3, 0, M - 3, 0),
    "padded_y_whole_result": (N, 0, M - 3, 0),  # the result is pad-free, an operand is not
}


@pytest.mark.parametrize("case", list(RAGGED))
@pytest.mark.parametrize("form", list(FORMS))
def test_padded_operands_take_the_fallback_and_agree(form, case):
    call, formula, _ = FORMS[form]
    n, sx, m, sy = RAGGED[case]
    xn, yn = _rows(n), _rows(m, 1)
    x, y = ht.array(xn, split=sx), ht.array(yn, split=sy)
    assert x._pad or y._pad
    for _ in range(2):
        got = call(x, y)
        assert got.split == _want_split(sx, sy) and got.dtype == ht.float32 and got.shape == (n, m)
        np.testing.assert_allclose(got.numpy(), formula(xn, yn), rtol=2e-5, atol=2e-5)
    stats = _cache.cache_stats()
    assert stats["hits"] == 0 and stats["misses"] + stats["slow"] == 2  # the negative entry, then found


@pytest.mark.parametrize("form", list(FORMS))
def test_under_the_callers_own_jit_the_expression_is_traced_into_it(form):
    call, formula, _ = FORMS[form]
    xn, yn = _rows(N), _rows(M, 1)

    @jax.jit
    def f(a, b):
        return call(ht.array(a, split=0), ht.array(b))._jarray

    np.testing.assert_allclose(np.asarray(f(xn, yn)), formula(xn, yn), rtol=2e-5, atol=2e-5)
    assert _cache.cache_stats() == {"hits": 0, "misses": 0, "slow": 0}


def test_a_sigma_that_is_no_python_scalar_takes_the_fallback():
    xn = _rows(N)
    got = ht.spatial.rbf(ht.array(xn, split=0), sigma=np.float32(SIGMA), quadratic_expansion=True)
    assert got.split == 0
    np.testing.assert_allclose(got.numpy(), FORMS["rbf_quadratic"][1](xn, xn), rtol=2e-5, atol=2e-5)
    assert _cache.cache_stats() == {"hits": 0, "misses": 0, "slow": 0}


def test_cdist_ring_falls_back_to_the_program():
    xn = _rows(N - 3)
    x = ht.array(_rows(N), split=0)
    ht.spatial.cdist_ring(x, ht.array(_rows(M, 1)))  # y unsplit: not the ring's case
    assert _cache.cache_stats()["misses"] + _cache.cache_stats()["hits"] == 1
    got = ht.spatial.cdist_ring(ht.array(xn, split=0))  # ragged rows: cdist's own fallback
    np.testing.assert_allclose(got.numpy(), np.sqrt(_np_sq(xn, xn)), rtol=2e-5, atol=5e-3)
