import json
import os

from benchmarks.harness import trace_reduce
from benchmarks.metrics.moe_events import moe_events


def read(ctx):
    """``reducers.kernel_roofline_pct`` with the share of live experts the
    program counted; nothing where there is no kernel time or no count."""
    with open(os.path.splitext(__file__)[0] + ".json") as f:
        params = json.load(f)
    spent = trace_reduce.kernel_s(ctx["trace"], params["kernel_pattern"])
    live = moe_events(ctx, "live_share")
    if spent <= 0 or not ctx["tokens"] or not live:
        return None
    work = getattr(ctx["family"].flops, params["work"])(
        ctx["config"], ctx["tokens"], int(ctx["traffic"]["seq_len"]),
        live_share=sum(live) / len(live))
    least = max(work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
                work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / len(ctx["trace"].devices) / spent
