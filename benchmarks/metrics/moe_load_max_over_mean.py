from benchmarks.metrics.moe_events import moe_events


def read(ctx):
    found = moe_events(ctx, "max_over_mean")
    return sum(found) / len(found) if found else None
