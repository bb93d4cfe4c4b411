"""The forest histogram's share of its roofline: the least time a chip could
take for the traced forests' histograms (per split level the larger of
benchmark/lib/counts_forest.py's least bytes over peak HBM bytes/s and its
least operations over peak FLOP/s, summed over the levels of every forest
the traced window grew) over the device seconds of the jitted modules whose
name holds `forest_hist` (ops/forest._forest_hist). Nothing where the trace
holds no such module: a program that never runs one, or no device plane."""

from benchmark.lib.counts import roofline_seconds


def read(ctx):
    w, peaks, work = ctx["window"], ctx["peaks"], ctx["work"]
    hist_s = sum(m["seconds"] for name, m in ctx["trace"]["modules"].items()
                 if "forest_hist" in name)
    if not peaks or not w.get("forests") or not hist_s:
        return None
    least_s, _bound = roofline_seconds(work["hist_ops"], work["hist_bytes"],
                                       peaks)
    return 100.0 * least_s * w["forests"] / hist_s
