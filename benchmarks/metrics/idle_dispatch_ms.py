from benchmarks.harness import scopes


def read(ctx):
    return scopes.idle_ms(ctx, "dispatch")
