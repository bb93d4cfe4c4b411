"""The compile plane (ISSUE 9): kill cold-start.

XLA compilation on a TPU costs seconds to minutes per program against
milliseconds of steady-state execution, and every ``pio deploy``,
hot-swap, canary stage and rollback used to pay it. This package is
the subsystem that amortizes it away:

- :mod:`predictionio_tpu.compile.cache` — JAX's persistent compilation
  cache, managed: ``$JAX_COMPILATION_CACHE_DIR`` when set, else the
  fixed ``<checkout>/.xla_cache``, plus the ``pio cache
  {status,clear}`` surface.
- :mod:`predictionio_tpu.compile.buckets` — the shape-bucket ladders:
  eighth-of-an-octave rungs for the rows of resident factor tables,
  power-of-two buckets for touched-row counts and query batch sizes,
  so growth INSIDE a bucket never changes a traced shape (zero
  recompiles) and bucket promotion is a single, predictable compile
  that can run before the shape is needed.
- :mod:`predictionio_tpu.compile.aot` — the AOT executable registry:
  hot executables (``batch_predict``, the fold-in solves, the ALS
  sweep, the gate probe) are ``jit(...).lower(...).compile()``-ed at
  deploy/swap time against the bucket ladder and dispatched as held
  ``Compiled`` objects — a warmed serve path runs zero trace and zero
  compile per request.

``PIO_AOT=off`` disables AOT dispatch/warming; ``PIO_XLA_CACHE=off``
disables the persistent cache. Both fall back to plain jit dispatch.
"""

from predictionio_tpu.compile.buckets import (bucket_batch, bucket_key,
                                              bucket_rows,
                                              bucket_table_rows,
                                              PROMOTE_AT)
from predictionio_tpu.compile.cache import (cache_status, clear_cache,
                                            enable_persistent_cache,
                                            persistent_cache_enabled)
from predictionio_tpu.compile.aot import (AOTRegistry, aot_enabled,
                                          get_aot, shared_jit,
                                          warm_models)

__all__ = [
    "AOTRegistry", "aot_enabled", "bucket_batch", "bucket_key",
    "bucket_rows", "bucket_table_rows", "cache_status", "clear_cache",
    "enable_persistent_cache", "get_aot", "persistent_cache_enabled",
    "PROMOTE_AT", "shared_jit", "warm_models",
]
