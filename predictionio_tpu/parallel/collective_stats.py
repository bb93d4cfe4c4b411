"""Collective-traffic accounting from compiled XLA programs.

GSPMD decides where the collectives go; this module reads them back OUT
of the compiled HLO so multi-chip communication cost is a measured
property of the actual program, not an assumption. Used by
``__graft_entry__.dryrun_multichip`` (the in-env weak-scaling proxy: no
multi-chip hardware is reachable here, but the compiled program's
collective bytes + the chip's published ICI bandwidth bound the scaling
loss).

Role in the reference stack: the Spark UI's shuffle read/write metrics —
the thing an MLlib operator watches to see communication cost
(reference: the block-ALS shuffle in
examples/scala-parallel-recommendation/custom-prepartor/src/main/scala/ALSAlgorithm.scala:55's
``ALS.train``); here the "shuffle" is XLA collectives over ICI.
"""

from __future__ import annotations

import re
from typing import Dict

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")

# optimized TPU HLO splits collectives into async -start/-done pairs;
# count the -start (it carries the shape) and ignore the -done
_LINE_RE = re.compile(
    r"= ((?:\([^)]*\)|[a-z0-9]+\[[^\]]*\])\S*) "
    r"(" + "|".join(_COLLECTIVES) + r")(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(shapes_txt: str, largest_only: bool = False) -> int:
    """Sum (or max, for async -start tuples whose elements are operand +
    result + scratch and would double-count the payload) of the element
    buffer sizes in an HLO shape string."""
    sizes = []
    for dt, dims in _SHAPE_RE.findall(shapes_txt):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * _DTYPE_BYTES.get(dt, 4))
    if not sizes:
        return 0
    return max(sizes) if largest_only else sum(sizes)


def collective_stats(compiled_or_text) -> Dict[str, dict]:
    """Per-collective-op instruction counts and output bytes of a compiled
    XLA program (pass a ``jax.stages.Compiled`` or its ``as_text()``).

    Bytes are the collective OUTPUT buffer sizes — for all-reduce the
    payload each participant contributes/receives, for all-gather the
    gathered result. This is the on-the-wire lower bound per ring pass;
    actual link traffic for a ring all-reduce is ~2x (reduce-scatter +
    all-gather phases), which ``ici_seconds`` accounts for."""
    text = (compiled_or_text if isinstance(compiled_or_text, str)
            else compiled_or_text.as_text())
    out: Dict[str, dict] = {}
    for line in text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        op = m.group(2)
        b = _shape_bytes(m.group(1), largest_only=bool(m.group(3)))
        ent = out.setdefault(op, {"count": 0, "bytes": 0})
        ent["count"] += 1
        ent["bytes"] += b
    out["total"] = {
        "count": sum(v["count"] for v in out.values()),
        "bytes": sum(v["bytes"] for v in out.values()),
    }
    return out


def ici_seconds(stats: Dict[str, dict], n_devices: int,
                ici_bytes_per_s: float = 200e9) -> float:
    """Lower-bound wall time the program's collectives spend on ICI.

    Ring-algorithm cost per collective of payload P over n devices:
    all-reduce moves ~2*P*(n-1)/n per link, all-gather/reduce-scatter
    ~P*(n-1)/n, collective-permute/all-to-all ~P. Default bandwidth is
    the v5e published per-chip ICI figure (1600 Gbps = 200 GB/s);
    pass the target chip's number for others."""
    if n_devices <= 1:
        return 0.0
    scale = (n_devices - 1) / n_devices
    total = 0.0
    for op, ent in stats.items():
        if op == "total":
            continue
        p = ent["bytes"]
        if op == "all-reduce":
            total += 2.0 * p * scale
        elif op in ("all-gather", "reduce-scatter"):
            total += p * scale
        else:
            total += p
    return total / ici_bytes_per_s
