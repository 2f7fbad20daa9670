from benchmarks.harness import scopes


def read(ctx):
    return scopes.part_time_pct(ctx, "scmoe")
