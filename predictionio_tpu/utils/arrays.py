"""Host <-> device pytree conversion helpers.

The serialization analog of the reference's Kryo step
(reference: core/src/main/scala/io/prediction/workflow/CoreWorkflow.scala:74-79):
before pickling a trained model, every jax.Array leaf is materialized to host
numpy (gathering sharded arrays if needed); after unpickling, models are
plain numpy until an algorithm's predict path puts them back on device.
"""

from __future__ import annotations

from typing import Any

import numpy as np


def _is_jax_array(x) -> bool:
    try:
        import jax
        return isinstance(x, jax.Array)
    except ImportError:
        return False


def to_host(obj: Any) -> Any:
    """Recursively convert jax.Array leaves to numpy. Handles dataclasses,
    dicts, lists, tuples (incl. namedtuples), and leaves everything else."""
    if _is_jax_array(obj):
        host = np.asarray(obj)
        # device->host transfer accounting (obs.jaxmon): model gathers
        # are the largest device->host transfers
        from predictionio_tpu.obs import jaxmon
        jaxmon.record_d2h(host.nbytes)
        return host
    if isinstance(obj, dict):
        return {k: to_host(v) for k, v in obj.items()}
    if isinstance(obj, tuple):
        if hasattr(obj, "_fields"):  # namedtuple
            return type(obj)(*(to_host(v) for v in obj))
        return tuple(to_host(v) for v in obj)
    if isinstance(obj, list):
        return [to_host(v) for v in obj]
    import dataclasses
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return dataclasses.replace(obj, **{
            f.name: to_host(getattr(obj, f.name))
            for f in dataclasses.fields(obj)})
    return obj
