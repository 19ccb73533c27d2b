"""``recompiles_in_window``: programs built between the end of warm-up and the
end of the window, as ``core/_cache.cache_stats()["misses"]`` counts them, plus
the entries the window added to the persistent compile cache.  Expected 0.
Layer: dispatch."""


def read(ctx):
    c = ctx.counters
    return c["program_cache_misses"] + c["compile_cache_files_added"]
