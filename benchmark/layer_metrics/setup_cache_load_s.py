"""Seconds set-up spent reading executables from the persistent compile
cache: `cache_load_s` of the set-up account's labelled records (the disk and
the deserialization behind it)."""

from benchmark.lib import setup_account


def read(ctx):
    recs = setup_account.compiles(ctx)
    if recs is None:
        return None
    return sum(r["cache_load_s"] for r in recs)
