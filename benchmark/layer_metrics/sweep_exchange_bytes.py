"""Bytes one chip sends to the others in one ALS iteration over row-sharded
tables: the program's own count, from the compiled half-sweeps' collectives
(ops/als.sweep_exchange: each collective's output bytes times the steps of
the scan it sits in, priced by the ring model of
parallel/collective_stats.sent_bytes), which the job puts among its
window's numbers. Nothing to read on a program that keeps no such count."""


def read(ctx):
    return ctx["window"].get("exchange_sent_bytes_per_iteration")
