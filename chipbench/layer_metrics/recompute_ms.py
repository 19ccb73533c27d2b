"""``recompute_ms``: milliseconds of a traced step in operations that
``jax.checkpoint`` runs a second time (a ``rematted_computation`` component in
their scope), whatever layer they are in: the price of the checkpoint policy.
Layer: trainers."""

from chipbench.harness import coverage


def read(ctx):
    return coverage.milliseconds(ctx, coverage.recomputed)
