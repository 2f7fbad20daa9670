import json
import os

from benchmarks.harness import trace_reduce
from benchmarks.metrics.moe_events import moe_events


def read(ctx):
    """``moe_gmm_roofline`` by the work the gated experts' family states
    (three products a layer each way), with the share of live experts as
    the program counted it."""
    with open(os.path.splitext(__file__)[0] + ".json") as f:
        params = json.load(f)
    spent = trace_reduce.kernel_s(ctx["trace"], params["kernel_pattern"])
    live = moe_events(ctx, "live_share")
    work_fn = getattr(ctx["family"].flops, params["work"], None)
    if spent <= 0 or not ctx["tokens"] or not live or work_fn is None:
        return None
    work = work_fn(ctx["config"], ctx["tokens"],
                   int(ctx["traffic"]["seq_len"]),
                   live_share=sum(live) / len(live))
    least = max(work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
                work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / len(ctx["trace"].devices) / spent
