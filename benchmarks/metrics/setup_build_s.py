from benchmarks.metrics.setup_spans import build_self_s


def read(ctx):
    return build_self_s(ctx)
