"""``sparse_kept_share``: per cent of the causal pairs of a query token and a
key that the sparse layers' selections kept, from the job's counters over the
window (``sparse_kept_pairs`` over ``sparse_causal_pairs``, summed over the
layers' key/value heads).  Layer: model layers."""


def read(ctx):
    kept, causal = ctx.counters.get("sparse_kept_pairs"), ctx.counters.get("sparse_causal_pairs")
    return 100.0 * kept / causal if kept is not None and causal else None
