"""The observers' slots: one home for every hook on the dispatch layer.

Three tools watch the runtime when armed: the flight recorder
(``utils.flightrec``), the device-memory ledger (``utils.memledger``) and
the metadata sanitizer (``core.sanitation``, ``HEAT_TPU_CHECKS=1``).  Each
tool's ``enable``/``disable`` sets its own slot here, and every consumer
reads ``_hooks.<slot>`` at the moment of use.  No consumer keeps a copy, so
a tool armed by its environment variable while ``import heat_tpu`` is still
running is seen like one armed later.  Disarmed, a site costs one attribute
load; what the whole dispatch layer costs on the chip is
``dispatch_py_ms_per_job`` (``chipbench``).

Imports nothing: the stdlib-only tools (telemetry, flightrec, memledger)
reach it without pulling in jax.
"""

# utils.flightrec while armed: each cached launch's op name, each staged
# collective, and (through telemetry) span boundaries go to its ring
flightrec = None

# utils.memledger while armed: buffers are registered where they are minted,
# donated operands consumed, and an allocation failure dumps the ledger
memledger = None

# sanitation.validate_dispatch while armed: called as ``checks(result,
# where)`` on every dispatch result, returns the result
checks = None

# sanitation.check_placement while armed: called as ``resplit_check(array,
# comm, split, where=)`` on every resplit's output
resplit_check = None
