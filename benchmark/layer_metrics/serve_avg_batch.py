"""Queries per device dispatch over the window, from the micro-batcher's own
counters (/stats.json `avgBatchSize`, taken as a difference over the
window)."""


def read(ctx):
    return ctx["window"].get("avg_batch")
