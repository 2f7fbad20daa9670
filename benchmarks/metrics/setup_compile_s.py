from benchmarks.metrics.setup_spans import stage_s


def read(ctx):
    return stage_s(ctx, "compile")
