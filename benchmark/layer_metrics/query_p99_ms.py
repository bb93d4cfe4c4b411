"""99th percentile of the slice's latencies, scheduled send to last byte, a
failed request counting as infinite: recorded and never judged. It is where
the collector's pauses show before `query_p95_ms` feels them."""

import math


def read(ctx):
    value = ctx["window"].get("query_p99_ms")
    return value if value is not None and math.isfinite(value) else None
