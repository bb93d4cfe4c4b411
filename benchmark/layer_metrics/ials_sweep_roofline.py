"""The implicit ALS iteration's share of its roofline: `als_sweep_roofline`'s
reading (the least time a chip could take for one iteration, the larger of
needed operations over peak FLOP/s and needed bytes over peak HBM bytes/s,
over the device's own time in the jitted modules the traced iterations ran,
per iteration) with the cell's own work, benchmark/lib/counts_implicit.py:
the explicit sweep's plus each side's shared Gram. The modules are the
half-sweeps' programs and the two Gram + eigh programs, and nothing else but
the one-element fetch that closes an iteration."""

import os

from benchmark.lib.spec import BENCH_DIR, load_module

read = load_module(os.path.join(BENCH_DIR, "layer_metrics",
                                "als_sweep_roofline.py"),
                   "layer_metric_als_sweep_roofline").read
