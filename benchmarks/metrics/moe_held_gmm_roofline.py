import json
import os

from benchmarks.harness import trace_reduce
from benchmarks.metrics.moe_events import moe_events


def read(ctx):
    """``moe_gmm_roofline`` with the share of assignments held here, and
    of the held experts that were live, as the program counted them."""
    with open(os.path.splitext(__file__)[0] + ".json") as f:
        params = json.load(f)
    spent = trace_reduce.kernel_s(ctx["trace"], params["kernel_pattern"])
    live, held = moe_events(ctx, "live_share"), moe_events(ctx, "held_share")
    work_fn = getattr(ctx["family"].flops, params["work"], None)
    if spent <= 0 or not ctx["tokens"] or not live or not held \
            or work_fn is None:
        return None
    work = work_fn(ctx["config"], ctx["tokens"],
                   int(ctx["traffic"]["seq_len"]),
                   live_share=sum(live) / len(live),
                   held=sum(held) / len(held))
    least = max(work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
                work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / len(ctx["trace"].devices) / spent
