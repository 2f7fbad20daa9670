"""What the two ``moe_*`` readers share: the program's ``round/<n>/moe``
point events (``FedLLMAPI._moe_event``; process tracer, in memory)."""


def moe_events(ctx, attr):
    """``attr`` of the window's events, oldest first; ``[]`` for a program
    that leaves none (one without routed experts, or the parent of the PR
    that added them)."""
    records = ctx.get("span_records")
    if records is None:
        from fedml_tpu.telemetry import get_tracer

        records = get_tracer().records()
    found = [r["attrs"][attr] for r in records
             if r.get("point") and r.get("name", "").startswith("round/")
             and r["name"].endswith("/moe") and attr in r.get("attrs", {})]
    return found[-int(ctx["rounds"]):] if ctx.get("rounds") else found
