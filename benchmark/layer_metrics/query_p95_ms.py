"""95th percentile of the slice's latencies, scheduled send to last byte,
a failed request counting as infinite, for a cell that is NOT judged on it:
`query_p95_ms.filtered`. At 75 requests a second a window holds 3,000
requests and the 95th percentile sits in a thin tail: the spread over seeds
that the harness admits a cell by (half of the end-to-end metric's bound,
1.5%) is narrower than the percentile's own standard error there (1.8%),
and one machine stall of a second moves it by a third (PERF.md sections 4
and 6). Recorded, so that a later cell or a longer window can take it up."""

import math


def read(ctx):
    value = ctx["window"].get("query_p95_ms")
    return value if value is not None and math.isfinite(value) else None
