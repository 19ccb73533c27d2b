"""``mla_rope_ms``: milliseconds of a traced step whose innermost scope is
``ht.attention.rope``: latent attention's rotation of the shared key part and
of the matching part of every query head, and the assembly of the query and
key heads (the split, the concatenation, the broadcast of the shared part to
the heads); forward, recomputed forward and backward.  ``ht.attention.proj``
holds it, and ``attention_proj_ms`` therefore leaves it out.  Layer: model
layers."""

from chipbench.harness import coverage


def read(ctx):
    return coverage.innermost_ms(ctx, "ht.attention.rope")
