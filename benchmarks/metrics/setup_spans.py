"""What the five ``setup_*`` readers share: the program's set-up spans
(process tracer, in memory) — ``llm/build`` over ``FedLLMAPI``'s
constructor, and each cataloged program's first-call stages
``program/trace``, ``program/lower`` and ``program/compile`` (attributes
``program`` and, on the last, ``cache``) — as recorded up to the end of
the first ``round/<n>/run``, which is the harness's ``train_one_round(1)``."""

STAGES = ("program/trace", "program/lower", "program/compile")


def _records(ctx):
    records = ctx.get("span_records")
    if records is not None:
        return records
    from fedml_tpu.telemetry import get_tracer
    from fedml_tpu.telemetry.spans import RING_RECORDS

    records = get_tracer().records()
    # a full ring may have dropped set-up's oldest records: no partial sums
    return records if len(records) < RING_RECORDS else None


def _is_run(rec):
    name = rec.get("name", "")
    return (not rec.get("point") and name.startswith("round/")
            and name.endswith("/run") and name.count("/") == 2)


def setup_spans(ctx):
    """Set-up's span records, oldest first; ``None`` where they hold no
    ``llm/build`` span (a program that leaves none, as the parent of the
    PR that added it) or where set-up cannot be read whole."""
    records = _records(ctx)
    if records is None:
        return None
    ends = [i for i, r in enumerate(records) if _is_run(r)]
    if not ends:
        return None
    setup = [r for r in records[:ends[0] + 1] if not r.get("point")]
    if not any(r["name"] == "llm/build" for r in setup):
        return None
    return setup


def stage_s(ctx, stage):
    """Seconds of set-up in ``program/<stage>`` spans, every program."""
    setup = setup_spans(ctx)
    if setup is None:
        return None
    return sum(r["duration_ms"] for r in setup
               if r["name"] == "program/" + stage) / 1e3


def build_self_s(ctx):
    """``llm/build``'s duration less what its ``program/*`` descendants
    cover (the stages do not nest in one another)."""
    setup = setup_spans(ctx)
    if setup is None:
        return None
    build = [r for r in setup if r["name"] == "llm/build"][-1]
    under = {build["span_id"]}
    for r in reversed(setup):  # a child ends before its parent
        if r.get("parent_id") in under:
            under.add(r["span_id"])
    staged = sum(r["duration_ms"] for r in setup
                 if r["name"] in STAGES and r["span_id"] in under)
    return (build["duration_ms"] - staged) / 1e3


def cache_misses(ctx):
    """``program/compile`` spans of set-up that asked the persistent cache
    and compiled (``cache="miss"``)."""
    setup = setup_spans(ctx)
    if setup is None:
        return None
    return float(sum(r["name"] == "program/compile"
                     and r.get("attrs", {}).get("cache") == "miss"
                     for r in setup))
