from benchmarks.metrics.moe_events import moe_events


def read(ctx):
    found = moe_events(ctx, "buffer_live_share")
    return sum(found) / len(found) if found else None
