"""Seconds the set-up's compile requests spent tracing jaxprs and lowering
them to MLIR (`trace_s` + `lower_s` of the program's set-up account, summed
over its labelled records): what every start pays whatever the compile cache
holds, and what a serialized executable would save."""

from benchmark.lib import setup_account


def read(ctx):
    recs = setup_account.compiles(ctx)
    if recs is None:
        return None
    return sum(r["trace_s"] + r["lower_s"] for r in recs)
