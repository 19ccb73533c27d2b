"""heatlint framework + rule tests (ISSUE 4 tentpole).

Every rule gets positive fixtures (known-bad snippet IS flagged) and
negative fixtures (the sanctioned idiom is NOT flagged), plus framework
tests for suppressions, the baseline workflow, and the CLI — and the
repo-wide gate itself: ``scripts/heatlint.py heat_tpu/`` must be clean
against the committed baseline.
"""

import importlib.util
import json
import os
import textwrap

import pytest

from heat_tpu.analysis import (
    LintContext,
    all_rules,
    lint_paths,
    load_baseline,
    split_by_baseline,
    write_baseline,
)
from heat_tpu.analysis.rules import (
    CollectiveAccountingRule,
    FederationJournaledMutationRule,
    HostSyncRule,
    MetadataMutationRule,
    NakedBlockingWaitRule,
    RankConditionalCollectiveRule,
    RawEntropyRule,
    SeqStampBypassRule,
    TraceIdentityRule,
    UnknownFaultSiteRule,
    UnledgeredDeviceBufferRule,
    UseAfterDonateRule,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "heatlint_cli", os.path.join(REPO, "scripts", "heatlint.py")
)
heatlint_cli = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(heatlint_cli)


def run_rule(rule, source, path="heat_tpu/somelib.py"):
    ctx = LintContext(path, textwrap.dedent(source))
    return list(rule.check(ctx))


# ---------------------------------------------------------------------- #
# HT101 — host sync in library code
# ---------------------------------------------------------------------- #
class TestHT101:
    def test_item_flagged(self):
        fs = run_rule(HostSyncRule(), """
            def f(x):
                return x.sum().item()
        """)
        assert [f.detail for f in fs] == ["item"]
        assert fs[0].rule == "HT101" and fs[0].qualname == "f"

    def test_device_get_flagged(self):
        fs = run_rule(HostSyncRule(), """
            import jax
            def f(x):
                return jax.device_get(x)
        """)
        assert [f.detail for f in fs] == ["device_get"]

    def test_float_cast_of_device_value_flagged(self):
        fs = run_rule(HostSyncRule(), """
            import jax.numpy as jnp
            def f(x):
                return float(jnp.sum(x._jarray))
        """)
        assert [f.detail for f in fs] == ["float-cast"]

    def test_np_asarray_of_device_value_flagged(self):
        fs = run_rule(HostSyncRule(), """
            import numpy as np
            def f(x):
                return np.asarray(x._jarray)
        """)
        assert [f.detail for f in fs] == ["np.asarray"]

    def test_np_asarray_of_host_data_not_flagged(self):
        fs = run_rule(HostSyncRule(), """
            import numpy as np
            def f(sections):
                return np.asarray(sections).ravel()
        """)
        assert fs == []

    def test_materialization_api_sanctioned(self):
        fs = run_rule(HostSyncRule(), """
            class DNDarray:
                def item(self):
                    return self._jarray.reshape(()).item()
                def numpy(self):
                    import jax
                    return jax.device_get(self._jarray)
                def __bool__(self):
                    return bool(self.item())
        """)
        assert fs == []

    def test_sanctioned_modules_skipped(self):
        src = """
            def render(x):
                return x.sum().item()
        """
        assert run_rule(HostSyncRule(), src, path="heat_tpu/core/printing.py") == []
        assert run_rule(HostSyncRule(), src, path="heat_tpu/core/io.py") == []
        assert len(run_rule(HostSyncRule(), src, path="heat_tpu/core/statistics.py")) == 1

    def test_inline_suppression(self):
        fs = run_rule(HostSyncRule(), """
            def f(x):
                return x.sum().item()  # heatlint: disable=HT101
        """)
        assert fs == []


# ---------------------------------------------------------------------- #
# HT102 — collective inside rank-conditional branch
# ---------------------------------------------------------------------- #
class TestHT102:
    def test_rank_conditional_collective_flagged(self):
        fs = run_rule(RankConditionalCollectiveRule(), """
            def f(comm, x):
                if comm.rank == 0:
                    comm.Bcast(x)
        """)
        assert [f.detail for f in fs] == ["Bcast"]
        assert fs[0].rule == "HT102"

    def test_process_index_conditional_flagged(self):
        fs = run_rule(RankConditionalCollectiveRule(), """
            import jax
            def f(comm, x):
                if jax.process_index() == 0:
                    return x.numpy()
        """)
        assert [f.detail for f in fs] == ["numpy"]

    def test_while_loop_flagged(self):
        fs = run_rule(RankConditionalCollectiveRule(), """
            def f(comm, x, n):
                while comm.rank < n:
                    comm.Allreduce(x)
        """)
        assert [f.detail for f in fs] == ["Allreduce"]

    def test_collective_in_both_arms_sanctioned(self):
        # the save_zarr idiom: every rank attends the collective fetch,
        # only the use of the result is rank-conditional
        fs = run_rule(RankConditionalCollectiveRule(), """
            def f(data, rank):
                if rank == 0:
                    arr = data.numpy()
                    arr.tofile("out")
                else:
                    data.numpy()  # the fetch is collective: every rank attends
        """)
        assert fs == []

    def test_local_work_in_rank_branch_not_flagged(self):
        fs = run_rule(RankConditionalCollectiveRule(), """
            import os
            def f(comm, path):
                if comm.rank == 0:
                    os.makedirs(path, exist_ok=True)
        """)
        assert fs == []

    def test_uniform_condition_not_flagged(self):
        fs = run_rule(RankConditionalCollectiveRule(), """
            def f(comm, x, n):
                if n > 2:
                    comm.Bcast(x)
        """)
        assert fs == []


# ---------------------------------------------------------------------- #
# HT103 — use after donate
# ---------------------------------------------------------------------- #
class TestHT103:
    def test_use_after_donate_kwarg_flagged(self):
        fs = run_rule(UseAfterDonateRule(), """
            import jax
            def f(x, sh):
                y = jax.device_put(x, sh, donate=True)
                return x + y
        """)
        assert [f.detail for f in fs] == ["x"]
        assert fs[0].rule == "HT103"

    def test_use_after_donate_argnums_flagged(self):
        fs = run_rule(UseAfterDonateRule(), """
            import jax
            def f(fn, a, b):
                prog = jax.jit(fn, donate_argnums=(0,))
                out = prog(a, b)
                return a, out
        """)
        assert [f.detail for f in fs] == ["a"]

    def test_rebind_clears_taint(self):
        fs = run_rule(UseAfterDonateRule(), """
            import jax
            def f(x, sh):
                x = jax.device_put(x, sh, donate=True)
                return x
        """)
        assert fs == []

    def test_donation_in_return_not_flagged(self):
        fs = run_rule(UseAfterDonateRule(), """
            import jax
            def f(x, sh, cond):
                if cond:
                    return jax.device_put(x, sh, donate=True)
                return x
        """)
        assert fs == []

    def test_exclusive_branches_not_flagged(self):
        # the Communication.resplit idiom: the donate attempt and its
        # TypeError fallback / the non-donate arm are mutually exclusive
        fs = run_rule(UseAfterDonateRule(), """
            import jax
            def f(self, array, sh, split, ok):
                if ok:
                    try:
                        out = jax.device_put(array, sh, donate=True)
                    except TypeError:
                        out = jax.device_put(array, sh)
                else:
                    out = self.shard(array, split)
                return out
        """)
        assert fs == []

    def test_second_positional_donate_position(self):
        fs = run_rule(UseAfterDonateRule(), """
            import jax
            def f(fn, a, b):
                prog = jax.jit(fn, donate_argnums=(1,))
                out = prog(a, b)
                return b
        """)
        assert [f.detail for f in fs] == ["b"]


# ---------------------------------------------------------------------- #
# HT104 — unaccounted public collective in communication.py
# ---------------------------------------------------------------------- #
class TestHT104:
    PATH = "heat_tpu/core/communication.py"

    def test_unaccounted_collective_flagged(self):
        fs = run_rule(CollectiveAccountingRule(), """
            from jax import lax
            class Communication:
                def Bcast(self, x, root=0):
                    return lax.psum(x, "x")
        """, path=self.PATH)
        assert [f.detail for f in fs] == ["Bcast"]
        assert fs[0].rule == "HT104"

    def test_accounted_collective_not_flagged(self):
        fs = run_rule(CollectiveAccountingRule(), """
            from jax import lax
            class Communication:
                def Bcast(self, x, root=0):
                    self._account("Bcast", x, 1.0)
                    return lax.psum(x, "x")
        """, path=self.PATH)
        assert fs == []

    def test_derived_collective_delegates(self):
        fs = run_rule(CollectiveAccountingRule(), """
            from jax import lax
            class Communication:
                def Allreduce(self, x, op="sum"):
                    self._account("Allreduce", x, 2.0)
                    return lax.psum(x, "x")
                def Reduce(self, x, root=0):
                    red = self.Allreduce(x)
                    return red
        """, path=self.PATH)
        assert fs == []

    def test_exempt_names(self):
        fs = run_rule(CollectiveAccountingRule(), """
            import jax
            class Communication:
                def Wait(self, x):
                    return jax.block_until_ready(x)
                def Barrier(self):
                    pass
        """, path=self.PATH)
        assert fs == []

    def test_other_files_not_in_scope(self):
        fs = run_rule(CollectiveAccountingRule(), """
            from jax import lax
            class Communication:
                def Bcast(self, x):
                    return lax.psum(x, "x")
        """, path="heat_tpu/parallel/ring.py")
        assert fs == []

    def test_tiled_entry_delegating_to_executor_not_flagged(self):
        # ISSUE 6: the tiled-resplit entry accounts PER TILE inside
        # core.redistribution.execute_plan (via _account_bytes) — delegating
        # to the executor IS accounting, not invisible traffic
        fs = run_rule(CollectiveAccountingRule(), """
            class Communication:
                def resplit_tiled(self, array, split, memory_budget=None):
                    from . import redistribution as _redist
                    plan = _redist.make_plan(self, array, split, memory_budget)
                    return _redist.execute_plan(self, array, plan)
        """, path=self.PATH)
        assert fs == []

    def test_tiled_entry_without_accounting_flagged(self):
        # a resplit* entry that neither accounts, delegates to an accounted
        # collective, nor routes through the executor IS flagged
        fs = run_rule(CollectiveAccountingRule(), """
            import jax
            class Communication:
                def resplit_tiled(self, array, split):
                    return jax.device_put(array, self.sharding(array.ndim, split))
        """, path=self.PATH)
        assert [f.detail for f in fs] == ["resplit_tiled"]

    def test_executor_delegation_scoped_to_resplit_entries(self):
        # the execute_plan exemption must NOT leak to other collectives: a
        # public collective delegating to something named execute_plan still
        # has invisible traffic unless it accounts its own
        fs = run_rule(CollectiveAccountingRule(), """
            class Communication:
                def Alltoallw(self, x):
                    from . import helper
                    return helper.execute_plan(self, x)
        """, path=self.PATH)
        assert [f.detail for f in fs] == ["Alltoallw"]

    def test_account_bytes_counts_as_accounting(self):
        fs = run_rule(CollectiveAccountingRule(), """
            from jax import lax
            class Communication:
                def Alltoall(self, x):
                    self._account_bytes("Alltoall", 128)
                    return lax.all_to_all(x, "x", 0, 0)
        """, path=self.PATH)
        assert fs == []

    def test_resplit_variant_delegating_to_resplit_not_flagged(self):
        # delegation among the resplit entries (resplit_tiled degenerates to
        # resplit for K=1 plans) carries the callee's accounting
        fs = run_rule(CollectiveAccountingRule(), """
            class Communication:
                def resplit(self, array, split):
                    self._account("resplit", array, 1.0)
                    return self.shard(array, split)
                def resplit_tiled(self, array, split):
                    return self.resplit(array, split)
        """, path=self.PATH)
        assert fs == []

    # -- ISSUE 16: the hierarchical/bucketed staging layer ------------- #
    COLL_PATH = "heat_tpu/core/collectives.py"

    def test_hierarchical_method_accounting_via_stage_accountant(self):
        # hierarchical_allreduce stages through _account_stages (which loops
        # comm._account_bytes) — delegation to the choke point IS accounting
        fs = run_rule(CollectiveAccountingRule(), """
            from jax import lax
            class Communication:
                def hierarchical_allreduce(self, x, op="sum"):
                    from . import collectives as _coll
                    _account_stages(self, _coll._Telescope(), 128, (0.5, 0.5), x=x)
                    return lax.psum(x, "x")
        """, path=self.PATH)
        assert fs == []

    def test_hierarchical_method_without_accounting_flagged(self):
        fs = run_rule(CollectiveAccountingRule(), """
            from jax import lax
            class Communication:
                def hierarchical_allreduce(self, x, op="sum"):
                    return lax.psum(x, "x")
        """, path=self.PATH)
        assert [f.detail for f in fs] == ["hierarchical_allreduce"]

    def test_staging_function_accounting_via_stage_accountant(self):
        fs = run_rule(CollectiveAccountingRule(), """
            def dispatch_bucket_averages(comm, leaves, plan, k, tele):
                _account_stages(comm, tele, 1024.0, (0.5, 0.5))
                return [leaves[j] for j in plan.buckets[k]]
        """, path=self.COLL_PATH)
        assert fs == []

    def test_staging_function_delegating_to_dispatch_half_not_flagged(self):
        # the lookahead pipelines account through their dispatch_* half
        fs = run_rule(CollectiveAccountingRule(), """
            def dispatch_bucket_averages(comm, leaves, plan, k, tele):
                comm._account_bytes("allreduce", tele.wire(128.0))
                return list(leaves)

            def bucketed_param_sync(comm, params, w, plan=None):
                avgs = dispatch_bucket_averages(comm, params, plan, 0, None)
                return avgs
        """, path=self.COLL_PATH)
        assert fs == []

    def test_staging_function_without_accounting_flagged(self):
        fs = run_rule(CollectiveAccountingRule(), """
            from jax import lax
            def bucketed_param_sync(comm, params, w):
                return lax.psum(params, "dcn")
        """, path=self.COLL_PATH)
        assert [f.detail for f in fs] == ["bucketed_param_sync"]

    def test_staging_helpers_not_in_scope(self):
        # underscore-private helpers (stage math, traced bodies) are not
        # staging entries — the traced body deliberately never accounts
        fs = run_rule(CollectiveAccountingRule(), """
            from jax import lax
            def _hierarchical_body(x, axis, p, d):
                return lax.psum(x, axis)
            def consume_bucket_averages(comm, leaves, avgs, plan, k, w):
                return leaves
        """, path=self.COLL_PATH)
        assert fs == []

    def test_repo_collectives_is_fully_accounted(self):
        # the live invariant: the real collectives.py has NO findings
        fs = lint_paths(
            [os.path.join(REPO, "heat_tpu", "core", "collectives.py")],
            select=["HT104"],
        )
        assert fs == []

    def test_repo_communication_is_fully_accounted(self):
        # the live invariant: the real communication.py has NO findings
        fs = lint_paths(
            [os.path.join(REPO, "heat_tpu", "core", "communication.py")],
            select=["HT104"],
        )
        assert fs == []


# ---------------------------------------------------------------------- #
# HT105 — raw process entropy
# ---------------------------------------------------------------------- #
class TestHT105:
    def test_np_random_flagged(self):
        fs = run_rule(RawEntropyRule(), """
            import numpy as np
            def f(n):
                return np.random.randint(0, n)
        """)
        assert [f.detail for f in fs] == ["np.random.randint"]
        assert fs[0].rule == "HT105"

    def test_stdlib_random_flagged(self):
        fs = run_rule(RawEntropyRule(), """
            import random
            def f():
                return random.random()
        """)
        assert [f.detail for f in fs] == ["random.random"]

    def test_os_urandom_flagged(self):
        fs = run_rule(RawEntropyRule(), """
            import os
            def f():
                return os.urandom(8)
        """)
        assert [f.detail for f in fs] == ["os.urandom"]

    def test_ht_random_module_sanctioned(self):
        fs = run_rule(RawEntropyRule(), """
            import numpy as np
            def seed(s=None):
                if s is None:
                    s = int(np.random.SeedSequence().entropy % (2**63))
                return s
        """, path="heat_tpu/core/random.py")
        assert fs == []

    def test_jax_random_not_flagged(self):
        fs = run_rule(RawEntropyRule(), """
            import jax
            def f(key, shape):
                return jax.random.normal(key, shape)
        """)
        assert fs == []

    def test_heat_own_random_module_not_confused_with_stdlib(self):
        # `from . import random` is heat's broadcast-state module, not the
        # stdlib: calls through it must NOT be flagged
        fs = run_rule(RawEntropyRule(), """
            from . import random
            def f(n):
                return random.randn(n)
        """)
        assert fs == []


# ---------------------------------------------------------------------- #
# HT106 — metadata mutation
# ---------------------------------------------------------------------- #
class TestHT106:
    def test_mangled_write_flagged(self):
        fs = run_rule(MetadataMutationRule(), """
            def f(x):
                x._DNDarray__split = 1
        """)
        assert [f.detail for f in fs] == ["_DNDarray__split"]
        assert fs[0].rule == "HT106"

    def test_unmangled_write_outside_class_flagged(self):
        fs = run_rule(MetadataMutationRule(), """
            def f(x, shape):
                x.__gshape = shape
        """)
        assert [f.detail for f in fs] == ["__gshape"]

    def test_foreign_class_own_private_not_flagged(self):
        # inside a class body the name mangles to the ENCLOSING class's
        # private (DCSR_matrix.__gshape), which is legal
        fs = run_rule(MetadataMutationRule(), """
            class DCSR_matrix:
                def __init__(self, shape):
                    self.__gshape = shape
        """)
        assert fs == []

    def test_dndarray_module_sanctioned(self):
        fs = run_rule(MetadataMutationRule(), """
            def f(x):
                x._DNDarray__split = 1
        """, path="heat_tpu/core/dndarray.py")
        assert fs == []

    def test_jarray_setter_not_flagged(self):
        fs = run_rule(MetadataMutationRule(), """
            def f(out, result):
                out._jarray = result
        """)
        assert fs == []


# ---------------------------------------------------------------------- #
# HT107 — naked blocking collective wait bypassing comm.deadline
# ---------------------------------------------------------------------- #
class TestHT107:
    def test_naked_barrier_flagged(self):
        fs = run_rule(NakedBlockingWaitRule(), """
            def f(comm):
                comm.Barrier()
        """)
        assert [f.detail for f in fs] == ["Barrier"]
        assert fs[0].rule == "HT107"

    def test_naked_wait_and_block_until_ready_flagged(self):
        fs = run_rule(NakedBlockingWaitRule(), """
            import jax
            def f(comm, x):
                comm.Wait(x)
                jax.block_until_ready(x)
        """)
        assert sorted(f.detail for f in fs) == ["Wait", "block_until_ready"]

    def test_sync_global_devices_flagged(self):
        fs = run_rule(NakedBlockingWaitRule(), """
            from jax.experimental import multihost_utils
            def f():
                multihost_utils.sync_global_devices("tag")
        """)
        assert [f.detail for f in fs] == ["sync_global_devices"]

    def test_under_deadline_not_flagged(self):
        fs = run_rule(NakedBlockingWaitRule(), """
            def f(comm, x):
                with comm.deadline(30.0):
                    comm.Wait(x)
                    comm.Barrier()
        """)
        assert fs == []

    def test_health_deadline_context_not_flagged(self):
        fs = run_rule(NakedBlockingWaitRule(), """
            from heat_tpu.utils import health
            def f(comm, x):
                with health.deadline(5.0) as dl:
                    comm.Wait(x)
        """)
        assert fs == []

    def test_wrapper_modules_sanctioned(self):
        src = """
            import jax
            def Wait(x):
                return jax.block_until_ready(x)
        """
        assert run_rule(
            NakedBlockingWaitRule(), src, path="heat_tpu/core/communication.py"
        ) == []
        assert run_rule(
            NakedBlockingWaitRule(), src, path="heat_tpu/utils/health.py"
        ) == []

    def test_foreign_barrier_api_not_flagged(self):
        # threading.Barrier(3) etc: Barrier WITH arguments is not the fence
        fs = run_rule(NakedBlockingWaitRule(), """
            import threading
            def f():
                b = threading.Barrier(3)
        """)
        assert fs == []

    def test_suppression_works(self):
        fs = run_rule(NakedBlockingWaitRule(), """
            def f(comm):
                comm.Barrier()  # heatlint: disable=HT107 teardown fence
        """)
        assert fs == []


# ---------------------------------------------------------------------- #
# HT108 — collective staging bypassing the seq-stamp choke point
# ---------------------------------------------------------------------- #
class TestHT108:
    def test_direct_execute_plan_flagged(self):
        fs = run_rule(SeqStampBypassRule(), """
            from heat_tpu.core import redistribution
            def f(comm, array, plan):
                return redistribution.execute_plan(comm, array, plan)
        """)
        assert [f.detail for f in fs] == ["execute_plan"]
        assert fs[0].rule == "HT108"

    def test_resharding_device_put_flagged(self):
        fs = run_rule(SeqStampBypassRule(), """
            import jax
            def f(comm, x):
                return jax.device_put(x._jarray, comm.sharding(x.ndim, 1))
        """)
        assert [f.detail for f in fs] == ["device_put"]

    def test_named_sharding_target_flagged(self):
        fs = run_rule(SeqStampBypassRule(), """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            def f(mesh, x):
                return jax.device_put(x._parray, NamedSharding(mesh, P("dcn")))
        """)
        assert [f.detail for f in fs] == ["device_put"]

    def test_host_upload_not_flagged(self):
        # device_put of HOST data onto a sharding is placement (an upload
        # scatter), not collective traffic staged around the choke point
        fs = run_rule(SeqStampBypassRule(), """
            import jax
            import jax.numpy as jnp
            def f(comm, host, new, sh):
                a = jax.device_put(host, comm.sharding(2, 0))
                b = jax.device_put(jnp.asarray(new), sh)
                return a, b
        """)
        assert fs == []

    def test_single_device_put_not_flagged(self):
        fs = run_rule(SeqStampBypassRule(), """
            import jax
            def f(x, d):
                return jax.device_put(x._jarray, d)
        """)
        assert fs == []

    def test_accounting_layer_sanctioned(self):
        src = """
            import jax
            def resplit_tiled(self, array, split, plan):
                from . import redistribution
                return redistribution.execute_plan(self, array, plan)
        """
        assert run_rule(
            SeqStampBypassRule(), src, path="heat_tpu/core/communication.py"
        ) == []
        assert run_rule(
            SeqStampBypassRule(), src, path="heat_tpu/core/redistribution.py"
        ) == []

    def test_suppression_works(self):
        fs = run_rule(SeqStampBypassRule(), """
            from heat_tpu.core import redistribution
            def f(comm, array, plan):
                return redistribution.execute_plan(comm, array, plan)  # heatlint: disable=HT108 bench harness
        """)
        assert fs == []

    def test_collectives_staging_layer_sanctioned(self):
        # ISSUE 16: the hierarchical/bucketed staging layer routes every
        # stage through _account_stages → comm._account_bytes (HT104 proves
        # that), so its sharded program staging is the choke point, not a
        # bypass of it
        fs = run_rule(SeqStampBypassRule(), """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            def dispatch_bucket_averages(comm, leaves, mesh):
                return jax.device_put(leaves[0]._jarray, NamedSharding(mesh, P("dcn")))
        """, path="heat_tpu/core/collectives.py")
        assert fs == []

    def test_same_staging_outside_sanctioned_layer_still_flagged(self):
        fs = run_rule(SeqStampBypassRule(), """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            def dispatch_bucket_averages(comm, leaves, mesh):
                return jax.device_put(leaves[0]._jarray, NamedSharding(mesh, P("dcn")))
        """, path="heat_tpu/optim/helper.py")
        assert [f.detail for f in fs] == ["device_put"]


# ---------------------------------------------------------------------- #
# HT111 — device buffers minted around the memory-ledger choke points
# ---------------------------------------------------------------------- #
class TestHT111:
    def test_raw_make_array_from_callback_flagged(self):
        fs = run_rule(UnledgeredDeviceBufferRule(), """
            import jax
            def f(host, sh):
                return jax.make_array_from_callback(host.shape, sh, lambda i: host[i])
        """)
        assert [f.detail for f in fs] == ["make_array_from_callback"]
        assert fs[0].rule == "HT111"

    def test_sharded_device_put_flagged(self):
        fs = run_rule(UnledgeredDeviceBufferRule(), """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            def f(mesh, p):
                return jax.device_put(p, NamedSharding(mesh, P("dcn")))
        """)
        assert [f.detail for f in fs] == ["device_put"]

    def test_comm_sharding_target_flagged(self):
        fs = run_rule(UnledgeredDeviceBufferRule(), """
            import jax
            def f(comm, host):
                return jax.device_put(host, comm.sharding(2, 0))
        """)
        assert [f.detail for f in fs] == ["device_put"]

    def test_device_kwarg_spelling_flagged(self):
        # device_put(x, device=NamedSharding(...)) mints the same buffer
        # as the positional form — the kwarg spelling must not slip through
        fs = run_rule(UnledgeredDeviceBufferRule(), """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            def f(mesh, p):
                return jax.device_put(p, device=NamedSharding(mesh, P("dcn")))
        """)
        assert [f.detail for f in fs] == ["device_put"]

    def test_plain_device_placement_not_flagged(self):
        # device_put onto a DEVICE (the hosted-complex transport commit)
        # is placement, not a mesh buffer the ledger needs to see
        fs = run_rule(UnledgeredDeviceBufferRule(), """
            import jax
            def f(arr, dev):
                return jax.device_put(arr, dev)
        """)
        assert fs == []

    def test_registrar_function_exempt(self):
        # a function that registers its result with the ledger IS a
        # registration choke point (the DASO.init shape)
        fs = run_rule(UnledgeredDeviceBufferRule(), """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from heat_tpu.utils import memledger
            def f(mesh, p):
                placed = jax.device_put(p, NamedSharding(mesh, P("dcn")))
                memledger.register(placed, op="init", category="param")
                return placed
        """)
        assert fs == []

    @pytest.mark.parametrize("register", [
        "_hooks.memledger.register(placed, op='init', category='param')",
        "ml = _hooks.memledger\n                ml.register(placed, op='init', category='param')",
    ])
    def test_slot_registrar_function_exempt(self, register):
        # the runtime reaches the ledger through its observer slot
        # (``core._hooks.memledger``), read in place or into a local
        fs = run_rule(UnledgeredDeviceBufferRule(), f"""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from heat_tpu.core import _hooks
            def f(mesh, p):
                placed = jax.device_put(p, NamedSharding(mesh, P("dcn")))
                {register}
                return placed
        """)
        assert fs == []

    def test_registration_layer_sanctioned(self):
        src = """
            import jax
            def _finalize(host, sh):
                return jax.make_array_from_callback(host.shape, sh, lambda i: host[i])
        """
        for path in (
            "heat_tpu/core/factories.py",
            "heat_tpu/core/communication.py",
            "heat_tpu/core/io.py",
        ):
            assert run_rule(UnledgeredDeviceBufferRule(), src, path=path) == []

    def test_suppression_works(self):
        fs = run_rule(UnledgeredDeviceBufferRule(), """
            import jax
            def f(host, sh):
                return jax.make_array_from_callback(host.shape, sh, lambda i: host[i])  # heatlint: disable=HT111 ingest shim
        """)
        assert fs == []


# ---------------------------------------------------------------------- #
# HT112 — federation mutations outside the journaled append path
# ---------------------------------------------------------------------- #
FED_PATH = "heat_tpu/parallel/federation.py"


class TestHT112:
    def test_foreign_scheduler_private_mutation_flagged(self):
        # reaching into another scheduler's _queue bypasses ITS journal —
        # flagged even from a function that journals federation-side
        fs = run_rule(FederationJournaledMutationRule(), """
            class Federation:
                def steal(self, sched, job):
                    self.journal.append({"type": "requeue"})
                    sched._queue.append(job)
        """, path=FED_PATH)
        assert [f.detail for f in fs] == ["_queue.append"]
        assert fs[0].rule == "HT112"

    def test_unjournaled_self_queue_mutation_flagged(self):
        fs = run_rule(FederationJournaledMutationRule(), """
            class Federation:
                def fast_path(self, job):
                    self._queue.append(job)
        """, path=FED_PATH)
        assert [f.detail for f in fs] == ["self._queue.append"]

    def test_unjournaled_subscript_store_flagged(self):
        fs = run_rule(FederationJournaledMutationRule(), """
            class Federation:
                def stash(self, job):
                    self._jobs[job.job_id] = job
        """, path=FED_PATH)
        assert [f.detail for f in fs] == ["self._jobs ="]

    def test_unjournaled_state_write_flagged(self):
        fs = run_rule(FederationJournaledMutationRule(), """
            def mark_failed(job):
                job.state = "failed"
        """, path=FED_PATH)
        assert [f.detail for f in fs] == ["state ="]

    def test_journaled_function_not_flagged(self):
        # the sanctioned shape: append the record FIRST, then mutate —
        # submit/_shed/_steal/_transition in federation.py all look like this
        fs = run_rule(FederationJournaledMutationRule(), """
            class Federation:
                def submit(self, job):
                    self.journal.append({"type": "submitted"})
                    self._jobs[job.job_id] = job
                    self._queue.append(job)
                    job.state = "submitted"
        """, path=FED_PATH)
        assert fs == []

    def test_init_constructing_fresh_state_not_flagged(self):
        fs = run_rule(FederationJournaledMutationRule(), """
            class Federation:
                def __init__(self):
                    self._jobs = {}
                    self._queue = []
        """, path=FED_PATH)
        assert fs == []

    def test_non_federation_module_not_flagged(self):
        # the rule scopes to federation code; the scheduler mutating its
        # OWN privates is governed by its journal-first convention, not HT112
        fs = run_rule(FederationJournaledMutationRule(), """
            def steal(sched, job):
                sched._queue.append(job)
        """, path="heat_tpu/parallel/scheduler.py")
        assert fs == []

    def test_suppression_works(self):
        fs = run_rule(FederationJournaledMutationRule(), """
            class Federation:
                def steal(self, sched, job):
                    sched._queue.append(job)  # heatlint: disable=HT112 recovery shim
        """, path=FED_PATH)
        assert fs == []

    def test_real_federation_module_clean(self):
        # the shipped federation layer must satisfy its own contract
        src = open(os.path.join(REPO, "heat_tpu", "parallel", "federation.py")).read()
        ctx = LintContext("heat_tpu/parallel/federation.py", src)
        assert list(FederationJournaledMutationRule().check(ctx)) == []


# ---------------------------------------------------------------------- #
# HT113 — fault-site literals must be catalog members
# ---------------------------------------------------------------------- #
class TestHT113:
    def test_misspelled_fire_site_flagged(self):
        fs = run_rule(UnknownFaultSiteRule(), """
            from heat_tpu.utils import faults
            def save(path):
                faults.fire("io.wrte", path=path)
        """)
        assert [f.detail for f in fs] == ["fire('io.wrte')"]
        assert fs[0].rule == "HT113"

    def test_unregistered_inject_site_flagged(self):
        fs = run_rule(UnknownFaultSiteRule(), """
            from heat_tpu.utils.faults import inject
            def test_x():
                with inject("bogus.site", fail=1):
                    pass
        """)
        assert [f.detail for f in fs] == ["inject('bogus.site')"]

    def test_trip_count_and_faultspec_literals_checked(self):
        fs = run_rule(UnknownFaultSiteRule(), """
            from heat_tpu.utils import faults
            def audit():
                spec = faults.FaultSpec("io.wrte", fail=1)
                return faults.trip_count("bogus.site"), spec
        """)
        assert sorted(f.detail for f in fs) == [
            "FaultSpec('io.wrte')", "trip_count('bogus.site')",
        ]

    def test_catalog_members_not_flagged(self):
        fs = run_rule(UnknownFaultSiteRule(), """
            from heat_tpu.utils import faults
            def save(path):
                faults.fire("io.write", path=path)
                with faults.inject("sched.dispatch", fail=1):
                    pass
                return faults.trip_count("mem.alloc")
        """)
        assert fs == []

    def test_variable_site_out_of_scope(self):
        # a variable site is someone's abstraction — only literals are
        # lexically checkable
        fs = run_rule(UnknownFaultSiteRule(), """
            from heat_tpu.utils import faults
            def fire_all(sites):
                for site in sites:
                    faults.fire(site)
        """)
        assert fs == []

    def test_call_with_retries_pseudo_site_exempt(self):
        # call_with_retries' site parameter names retry COUNTERS, not
        # armed fault sites — the chaos harness uses pseudo-sites there
        fs = run_rule(UnknownFaultSiteRule(), """
            from heat_tpu.utils import faults
            def submit(fn):
                return faults.call_with_retries(fn, "chaos.submit", retries=2)
        """)
        assert fs == []

    def test_suppression_works(self):
        fs = run_rule(UnknownFaultSiteRule(), """
            from heat_tpu.utils import faults
            def probe():
                faults.fire("io.wrte")  # heatlint: disable=HT113 negative fixture
        """)
        assert fs == []


# ---------------------------------------------------------------------- #
# HT109 — trace identity owned by the tracing choke points
# ---------------------------------------------------------------------- #
class TestHT109:
    def test_manual_trace_id_subscript_write_flagged(self):
        fs = run_rule(TraceIdentityRule(), """
            def f(attrs, job):
                attrs["trace_id"] = job.job_id + "-trace"
                return attrs
        """)
        assert [f.detail for f in fs] == ["trace_id"]
        assert fs[0].rule == "HT109"

    def test_parent_and_span_id_writes_flagged(self):
        fs = run_rule(TraceIdentityRule(), """
            def f(rec):
                rec["span_id"] = "s1"
                rec["parent_id"] = "s0"
        """)
        assert sorted(f.detail for f in fs) == ["parent_id", "span_id"]

    def test_trace_kwarg_smuggled_into_span_flagged(self):
        fs = run_rule(TraceIdentityRule(), """
            from heat_tpu.utils import telemetry
            def f(tid):
                with telemetry.span("work", trace_id=tid):
                    pass
        """)
        assert [f.detail for f in fs] == ["span:trace_id"]

    def test_record_event_trace_kwarg_flagged(self):
        fs = run_rule(TraceIdentityRule(), """
            from heat_tpu.utils import telemetry
            def f(tid):
                telemetry.record_event("e", 0.1, trace_id=tid)
        """)
        assert [f.detail for f in fs] == ["record_event:trace_id"]

    def test_direct_contextvar_set_flagged(self):
        fs = run_rule(TraceIdentityRule(), """
            from heat_tpu.utils.telemetry import _TRACE
            def f(tid):
                _TRACE.set((tid, None))
        """)
        assert len(fs) == 1 and "_TRACE" in fs[0].detail

    def test_tracing_helper_is_the_sanctioned_idiom(self):
        fs = run_rule(TraceIdentityRule(), """
            from heat_tpu.utils import telemetry
            def f(tid):
                with telemetry.tracing(trace_id=tid):
                    with telemetry.span("work"):
                        pass
        """)
        assert fs == []

    def test_reading_trace_identity_not_flagged(self):
        fs = run_rule(TraceIdentityRule(), """
            def f(attrs):
                tid = attrs.get("trace_id")
                other = {"unrelated": 1}
                other["tid"] = tid  # a foreign key name is not the triple
                return tid
        """)
        assert fs == []

    def test_owner_modules_sanctioned(self):
        src = """
            def submit(job, attrs):
                attrs["trace_id"] = "abc123"
        """
        assert run_rule(
            TraceIdentityRule(), src, path="heat_tpu/utils/telemetry.py"
        ) == []
        assert run_rule(
            TraceIdentityRule(), src, path="heat_tpu/parallel/scheduler.py"
        ) == []

    def test_suppression_works(self):
        fs = run_rule(TraceIdentityRule(), """
            def f(attrs):
                attrs["trace_id"] = "x"  # heatlint: disable=HT109 migration shim
        """)
        assert fs == []


# ---------------------------------------------------------------------- #
# framework: suppressions, baseline, discovery, CLI
# ---------------------------------------------------------------------- #
class TestFramework:
    BAD = "def f(x):\n    return x.sum().item()\n"

    def test_file_level_suppression(self):
        src = "# heatlint: disable-file=HT101\n" + self.BAD
        ctx = LintContext("heat_tpu/lib.py", src)
        assert list(HostSyncRule().check(ctx)) == []

    def test_suppression_with_trailing_reason(self):
        # a free-text reason after the code must not corrupt the code token
        src = "def f(x):\n    return x.sum().item()  # heatlint: disable=HT101 tolerated debug path\n"
        ctx = LintContext("heat_tpu/lib.py", src)
        assert list(HostSyncRule().check(ctx)) == []

    def test_multi_code_suppression_with_reason(self):
        src = "def f(x):\n    return x.sum().item()  # heatlint: disable=HT103, HT101 both fine here\n"
        ctx = LintContext("heat_tpu/lib.py", src)
        assert list(HostSyncRule().check(ctx)) == []

    def test_docstring_mentioning_syntax_does_not_suppress(self):
        # only REAL comments suppress — a docstring documenting the syntax
        # (like the framework's own module docstring) must not disable rules
        src = (
            '"""Docs: use ``# heatlint: disable-file=HT101`` for file scope\n'
            'or ``# heatlint: disable=HT101`` on a line."""\n'
            "def f(x):\n"
            "    return x.sum().item()\n"
        )
        ctx = LintContext("heat_tpu/lib.py", src)
        assert [f.detail for f in HostSyncRule().check(ctx)] == ["item"]

    def test_all_rules_registered(self):
        codes = [r.code for r in all_rules()]
        assert codes == [
            "HT101", "HT102", "HT103", "HT104", "HT105", "HT106", "HT107",
            "HT108", "HT109", "HT110", "HT111", "HT112", "HT113", "HT201",
            "HT202", "HT203", "HT204", "HT301", "HT302", "HT303", "HT304",
        ]

    def test_select_unknown_rule_raises(self):
        with pytest.raises(ValueError, match="unknown rule"):
            all_rules(select=["HT999"])

    def test_lint_paths_and_syntax_error(self, tmp_path):
        (tmp_path / "ok.py").write_text(self.BAD)
        (tmp_path / "broken.py").write_text("def f(:\n")
        fs = lint_paths([str(tmp_path)])
        rules = sorted({f.rule for f in fs})
        assert rules == ["HT000", "HT101"]

    def test_baseline_roundtrip_and_counts(self, tmp_path):
        src_dir = tmp_path / "pkg"
        src_dir.mkdir()
        (src_dir / "lib.py").write_text(self.BAD)
        findings = lint_paths([str(src_dir)])
        assert len(findings) == 1
        bl_path = str(tmp_path / "baseline.json")
        write_baseline(bl_path, findings)
        baseline = load_baseline(bl_path)
        new, old = split_by_baseline(findings, baseline)
        assert new == [] and len(old) == 1
        # a SECOND identical finding in the same function exceeds the
        # baselined count and is reported as new
        (src_dir / "lib.py").write_text(
            "def f(x):\n    a = x.sum().item()\n    return x.max().item() + a\n"
        )
        findings2 = lint_paths([str(src_dir)])
        assert len(findings2) == 2
        new2, old2 = split_by_baseline(findings2, baseline)
        assert len(new2) == 1 and len(old2) == 1

    def test_baseline_survives_line_drift(self, tmp_path):
        src_dir = tmp_path / "pkg"
        src_dir.mkdir()
        (src_dir / "lib.py").write_text(self.BAD)
        bl_path = str(tmp_path / "baseline.json")
        write_baseline(bl_path, lint_paths([str(src_dir)]))
        # unrelated edit shifts every line; the fingerprint still matches
        (src_dir / "lib.py").write_text("# comment\n\n\n" + self.BAD)
        new, old = split_by_baseline(lint_paths([str(src_dir)]), load_baseline(bl_path))
        assert new == [] and len(old) == 1

    def test_cli_exit_codes_and_json(self, tmp_path, capsys):
        src_dir = tmp_path / "pkg"
        src_dir.mkdir()
        (src_dir / "lib.py").write_text(self.BAD)
        bl = str(tmp_path / "bl.json")
        out_json = str(tmp_path / "out.json")
        # no baseline yet: the finding is new -> exit 1
        assert heatlint_cli.main([str(src_dir), "--baseline", bl, "--json", out_json]) == 1
        data = json.loads(open(out_json).read())
        assert data["counts"]["new"] == 1 and data["new"][0]["rule"] == "HT101"
        # write the baseline -> gate goes green
        assert heatlint_cli.main([str(src_dir), "--baseline", bl, "--write-baseline"]) == 0
        assert heatlint_cli.main([str(src_dir), "--baseline", bl]) == 0
        # --no-baseline reports it as new again
        assert heatlint_cli.main([str(src_dir), "--baseline", bl, "--no-baseline"]) == 1
        capsys.readouterr()

    def test_write_baseline_preserves_out_of_scope_entries(self, tmp_path, capsys):
        # grandfathered findings in files OUTSIDE the linted paths survive a
        # narrow --write-baseline run instead of being silently dropped
        a = tmp_path / "a"
        b = tmp_path / "b"
        a.mkdir(); b.mkdir()
        (a / "liba.py").write_text(self.BAD)
        (b / "libb.py").write_text(self.BAD)
        bl = str(tmp_path / "bl.json")
        assert heatlint_cli.main([str(a), str(b), "--baseline", bl, "--write-baseline"]) == 0
        assert heatlint_cli.main([str(a), str(b), "--baseline", bl]) == 0
        # re-write from only `a`: b's entry must be preserved
        assert heatlint_cli.main([str(a), "--baseline", bl, "--write-baseline"]) == 0
        assert heatlint_cli.main([str(a), str(b), "--baseline", bl]) == 0
        # fixing a's finding then re-writing from `a` drops a's entry only
        (a / "liba.py").write_text("def f(x):\n    return x\n")
        assert heatlint_cli.main([str(a), "--baseline", bl, "--write-baseline"]) == 0
        baseline = load_baseline(bl)
        assert len(baseline) == 1 and any("libb.py" in fp for fp in baseline)
        capsys.readouterr()

    def test_overlapping_paths_lint_once(self, tmp_path, capsys):
        # `heatlint pkg/ pkg/sub pkg/sub/lib.py` must not double-count
        # findings past the baseline's per-fingerprint budget
        sub = tmp_path / "pkg" / "sub"
        sub.mkdir(parents=True)
        (sub / "lib.py").write_text(self.BAD)
        fs = lint_paths([str(tmp_path / "pkg"), str(sub), str(sub / "lib.py")])
        assert len(fs) == 1
        bl = str(tmp_path / "bl.json")
        assert heatlint_cli.main([str(tmp_path / "pkg"), "--baseline", bl, "--write-baseline"]) == 0
        assert heatlint_cli.main(
            [str(tmp_path / "pkg"), str(sub), "--baseline", bl]
        ) == 0
        capsys.readouterr()

    def test_write_baseline_refuses_select(self, tmp_path, capsys):
        src_dir = tmp_path / "pkg"
        src_dir.mkdir()
        (src_dir / "lib.py").write_text(self.BAD)
        bl = str(tmp_path / "bl.json")
        rc = heatlint_cli.main(
            [str(src_dir), "--baseline", bl, "--select", "HT101", "--write-baseline"]
        )
        assert rc == 2
        assert not os.path.exists(bl)
        capsys.readouterr()

    def test_cli_list_rules(self, capsys):
        assert heatlint_cli.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for code in ("HT101", "HT102", "HT103", "HT104", "HT105", "HT106"):
            assert code in out


# ---------------------------------------------------------------------- #
# the repo gate itself
# ---------------------------------------------------------------------- #
class TestRepoGate:
    def test_repo_clean_against_committed_baseline(self, capsys):
        """The acceptance criterion: scripts/heatlint.py heat_tpu/ exits 0."""
        rc = heatlint_cli.main([os.path.join(REPO, "heat_tpu")])
        capsys.readouterr()
        assert rc == 0

    def test_svdtools_host_sync_is_fixed(self):
        """ISSUE 4 satellite: the `.item()` at linalg/svdtools.py:74 is gone —
        HT101 finds nothing in svdtools (and the baseline carries no
        grandfathered entry for it either)."""
        fs = lint_paths(
            [os.path.join(REPO, "heat_tpu", "linalg", "svdtools.py")], select=["HT101"]
        )
        assert fs == []
        baseline = load_baseline(os.path.join(REPO, ".heatlint-baseline.json"))
        assert not any("svdtools" in fp for fp in baseline)

    def test_committed_baseline_loads(self):
        baseline = load_baseline(os.path.join(REPO, ".heatlint-baseline.json"))
        assert len(baseline) > 0
