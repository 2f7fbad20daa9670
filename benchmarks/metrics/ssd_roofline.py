import json
import os

from benchmarks.harness import scopes


def read(ctx):
    """The scan's part of device time against the least time for
    ``ssd_work``; nothing where no op matched the part."""
    with open(os.path.splitext(__file__)[0] + ".json") as f:
        params = json.load(f)
    got = scopes.from_ctx(ctx)
    spent = (got["parts"] or {}).get(params["part"], 0.0)
    work_fn = getattr(ctx["family"].flops, params["work"], None)
    if spent <= 0 or not ctx["tokens"] or work_fn is None:
        return None
    work = work_fn(ctx["config"], ctx["tokens"],
                   int(ctx["traffic"]["seq_len"]))
    least = max(work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
                work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
