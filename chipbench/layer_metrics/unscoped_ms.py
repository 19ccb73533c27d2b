"""``unscoped_ms``: milliseconds of a traced job under no ``ht.`` scope, the
grouped products not counted: the job's small programs beside the one that
names its layers, and the instructions XLA made without metadata.  With the
readers by innermost scope it adds up to the busy time.  Layer: device."""

from chipbench.harness import coverage


def read(ctx):
    return coverage.innermost_ms(ctx, "")
