"""The batched top-k executable's share of its roofline: the least time the
chip could take for one dispatch (the item table read once and one user row
per query, against the scoring operations; the bytes bound it) over the
executable's device time per dispatch in the trace, found by the jitted
module's name."""

from benchmark.lib.counts import (roofline_seconds, topk_dispatch_bytes,
                                  topk_query_flops)


def read(ctx):
    peaks, work = ctx["peaks"], ctx["work"]
    batch = ctx["window"].get("avg_batch")
    if not peaks or not batch:
        return None
    calls = seconds = 0.0
    for name, m in ctx["trace"]["modules"].items():
        if "users_topk" in name:
            calls += m["count"]
            seconds += m["seconds"]
    if not calls or not seconds:
        return None
    least_s, _bound = roofline_seconds(
        topk_query_flops(work["n_items"], work["rank"]) * batch,
        topk_dispatch_bytes(work["n_items"], work["rank"], batch,
                            work["factor_bytes"]), peaks)
    return 100.0 * least_s / (seconds / calls)
