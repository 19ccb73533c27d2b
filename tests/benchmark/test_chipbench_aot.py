"""The benchmark's own programs compiled for the v5e that is described, not
attached: the seeded generators must not outgrow what they make (the peak
counter cannot be reset, so set-up would otherwise be what ``peak_hbm_gib``
measures), and the four-chip product must hold what PERF.md says it holds.
Nothing runs; these are bytes, not times.

Every compile against the described topology lives in this one file, behind
a fixture: only one process at a time may load the TPU's library.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench.harness import data, manifest  # noqa: E402

GIB = 2 ** 30
BENCH = manifest.Manifest(REPO)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        described = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache but cannot be read
    # back without a chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _mesh(topo, chips):
    return Mesh(np.asarray(topo.devices[:chips]), ("x",))


def _key(mesh):
    return jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=NamedSharding(mesh, P()))


@pytest.mark.parametrize("chips", [1, 4])
def test_dense_generator_is_no_larger_than_its_result(topo, chips):
    config = BENCH.config(BENCH.cell("matmul_n40960"))
    n = config["n"]
    mesh = _mesh(topo, chips)
    program = data._dense_program(mesh, "x", n, n, 1.0, jnp.dtype(config["dtype"]), 1024)
    memory = program.lower(_key(mesh)).compile().memory_analysis()
    assert memory.output_size_in_bytes == n * n * 2 // chips == 3_355_443_200 // chips
    assert memory.temp_size_in_bytes < 0.05 * memory.output_size_in_bytes


def test_blobs_generator_is_no_larger_than_its_result(topo):
    from jax.experimental.layout import Format, Layout

    config = BENCH.config(BENCH.cell("kmeans_fit_n2e26"))
    n, d, k = config["rows"], config["features"], config["clusters"]
    mesh = _mesh(topo, 1)
    program = data._blobs_program(mesh, "x", n, d, k, config["blob_spread"],
                                  jnp.dtype(config["dtype"]), 1 << 20)
    # the attached chip lays (n, 32) out feature-major by itself (PR 21 held
    # the 8 GiB); the described one has to be asked, or it pads 32 to 128
    compact = Format(Layout(major_to_minor=(1, 0)), NamedSharding(mesh, P("x", None)))
    lowered = jax.jit(program.__wrapped__, out_shardings=(compact, NamedSharding(mesh, P())))
    memory = lowered.lower(_key(mesh)).compile().memory_analysis()
    assert 8 * GIB <= memory.output_size_in_bytes < 8 * GIB + 2 ** 20
    assert memory.temp_size_in_bytes < 0.05 * GIB


def test_four_chip_product_and_a_compiled_resplit(topo):
    """The program ``ht.matmul`` builds for split 0 x split 0
    (``_operations._build_binary``: the product constrained to split 0), and
    the program a compiled resplit of C would be.  ``Communication.resplit``
    builds none: it hands the change of sharding to ``jax.device_put``, which
    PR 22 measured at 4.15 s for 2 GiB on four chips with no program on the
    device; the all-to-all below is what a later PR can put in its place."""
    n = BENCH.config(BENCH.cell("matmul_resplit_n40960_4chip"))["n"]
    mesh = _mesh(topo, 4)
    rows, cols = NamedSharding(mesh, P("x", None)), NamedSharding(mesh, P(None, "x"))
    a = jax.ShapeDtypeStruct((n, n), jnp.bfloat16, sharding=rows)
    product = jax.jit(jnp.matmul, out_shardings=rows).lower(a, a).compile()
    memory = product.memory_analysis()
    # each chip: its rows of A and B (a quarter matrix each), its rows of C, all of B
    quarter = n * n * 2 // 4
    assert memory.argument_size_in_bytes == 2 * quarter and memory.output_size_in_bytes == quarter
    assert memory.temp_size_in_bytes == pytest.approx(4 * quarter, rel=0.05)
    assert "all-gather" in product.as_text()
    resplit = jax.jit(lambda x: x, out_shardings=cols).lower(a).compile()
    assert "all-to-all" in resplit.as_text()
    assert resplit.memory_analysis().temp_size_in_bytes <= quarter * 1.05
