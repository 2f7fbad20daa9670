from benchmarks.metrics.setup_spans import cache_misses


def read(ctx):
    return cache_misses(ctx)
