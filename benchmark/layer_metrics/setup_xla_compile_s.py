"""Seconds XLA compiled in set-up: `compile_s` of the set-up account's
labelled records whose outcome is `compiled` (the persistent cache missed).
0 where the cache answered every request."""

from benchmark.lib import setup_account


def read(ctx):
    recs = setup_account.compiles(ctx)
    if recs is None:
        return None
    return sum(r["compile_s"] for r in recs if r["outcome"] == "compiled")
