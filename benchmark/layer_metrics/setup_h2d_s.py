"""Seconds the set-up's uploads took from their start to their arrays being
ready on the device, summed over the upload records of the program's set-up
account (tables at `cached_put*`, plans at `ops/als._upload_plan`): the host's
padding, the transfer, and the wait for it. Uploads that overlap count each
their own seconds; their union wall and bytes are in the program's own
`setup` summary (PERF.md section 7)."""

from benchmark.lib import setup_account


def read(ctx):
    recs = setup_account.uploads(ctx)
    if recs is None:
        return None
    return sum(r["t_ready"] - r["t_start"] for r in recs)
