"""Median of the slice's latencies, scheduled send to last byte, for a cell
that is NOT judged on it: `query_p50_ms.filtered`. The filtered serve cell's
median is two thirds host time (the live reads and the lists, in the
interpreter) and follows the speed of the machine's shared host cores from
run to run: three sets of six runs spread 5.2%, 2.7% and 3.9% where the
harness admits a cell at half the end-to-end metric's bound, 2.5% (PERF.md
sections 4 and 6). Recorded in every traced run."""

import math


def read(ctx):
    value = ctx["window"].get("query_p50_ms")
    return value if value is not None and math.isfinite(value) else None
