"""The whole implicit ALS iteration's share of the chip's peak FLOP/s:
`als_sweep_mfu`'s reading (needed operations per iteration over the mean
iteration wall of the slice) with the cell's own work,
benchmark/lib/counts_implicit.py, from the data's degrees, the table sizes
and the rank."""

import os

from benchmark.lib.spec import BENCH_DIR, load_module

read = load_module(os.path.join(BENCH_DIR, "layer_metrics",
                                "als_sweep_mfu.py"),
                   "layer_metric_als_sweep_mfu").read
