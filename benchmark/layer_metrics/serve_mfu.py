"""The whole serving step's share of the chip's peak FLOP/s: scoring
operations of the queries answered in the slice over the slice. Small by
nature (a query is one matrix-vector product per item); it bounds what any
kernel's roofline can claim once that kernel is off the path."""


def read(ctx):
    w, peaks = ctx["window"], ctx["peaks"]
    if not peaks or not w.get("queries_per_s"):
        return None
    return (100.0 * ctx["work"]["query_flops"] * w["queries_per_s"]
            / peaks["flops_per_s"])
