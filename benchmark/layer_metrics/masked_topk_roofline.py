"""The composed-mask top-k executable's share of its roofline: the least
time the chip could take for one dispatch (the live rows of the table the
route scores against, the category array and the availability bitmap read
once, one vector and one list per query, against the scoring operations;
the bytes bound it: benchmark/lib/counts_masked.py, which counts from the
configuration and the batch alone) over the executable's device time per
dispatch in the trace, found by the jitted module's name. A window dispatch
runs one such executable per route it holds queries of; each is a scan of
its own table, and each counts as a call."""

from benchmark.lib.counts import roofline_seconds
from benchmark.lib.counts_masked import dispatch_bytes, query_flops


def read(ctx):
    peaks, work = ctx["peaks"], ctx["work"]
    batch = ctx["window"].get("avg_batch")
    if not peaks or not batch or "category_slots" not in work:
        return None
    calls = seconds = 0.0
    for name, m in ctx["trace"]["modules"].items():
        if "masked_topk" in name:
            calls += m["count"]
            seconds += m["seconds"]
    if not calls or not seconds:
        return None
    # the window's queries spread over the executables that ran
    per_call = batch * ctx["window"]["dispatches"] / calls
    least_s, _bound = roofline_seconds(
        query_flops(work["n_items"], work["rank"]) * per_call,
        dispatch_bytes(work["n_items"], work["rank"], per_call,
                       work["category_slots"], work["listed_per_query"],
                       work["factor_bytes"]), peaks)
    return 100.0 * least_s / (seconds / calls)
