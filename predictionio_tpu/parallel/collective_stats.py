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
from typing import Dict, Optional

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


_COMPUTATION_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLEE_RE = re.compile(
    r"\b(body|condition|to_apply|calls)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_BOUND_RE = re.compile(r"= s32\[\]\S* constant\((\d+)\)")


def _trip_count(line: str, bodies: Dict[str, list]) -> Optional[int]:
    """How often the `while` of `line` runs its body: the count XLA wrote
    on it, else (the TPU compiler writes none) the bound of a condition
    that is a counter compared `LT` with its one s32 constant, which is
    what a `lax.scan` lowers to; None where neither can be read."""
    known = _TRIP_RE.search(line)
    if known:
        return int(known.group(1))
    cond = re.search(r"\bcondition=%?([\w.\-]+)", line)
    lines = bodies.get(cond.group(1), ()) if cond else ()
    bounds = [m.group(1) for ln in lines for m in [_BOUND_RE.search(ln)]
              if m]
    if len(bounds) == 1 and any("direction=LT" in ln for ln in lines):
        return int(bounds[0])
    return None


def executed_collective_stats(compiled_or_text) -> Dict[str, dict]:
    """`collective_stats` with every collective counted as often as the
    program runs it: an instruction inside a loop's body counts the loop's
    trip count times (`_trip_count`; a `lax.scan` over N batches is such a
    loop). A collective inside a loop whose count cannot be read (a
    data-dependent `while_loop`) is a ValueError, not a quiet undercount;
    such a loop with no collective in it is no matter. The ALS half-sweep's
    exchanges all sit inside its scans, where `collective_stats` sees each
    once whatever the number of steps."""
    text = (compiled_or_text if isinstance(compiled_or_text, str)
            else compiled_or_text.as_text())
    bodies: Dict[str, list] = {}
    entry = current = None
    for line in text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m and " = " not in line.split("{")[0]:
            current = m.group(2)
            bodies[current] = []
            if m.group(1):
                entry = current
        elif current is not None:
            bodies[current].append(line)
    if entry is None:                       # a bare fragment: no loops
        return collective_stats(text)
    memo: Dict[str, Dict[str, list]] = {}

    def total(name: str) -> Dict[str, list]:
        if name in memo:
            return memo[name]
        memo[name] = out = {}               # a cycle would stop here
        own = collective_stats("\n".join(bodies.get(name, ())))
        root = [ln for ln in bodies.get(name, ()) if "ROOT " in ln]
        if name.startswith("all-reduce-scatter") and root:
            # the TPU compiler's reduce-scatter: a fusion of an all-reduce
            # and the slice each participant keeps, which is its output
            kept = _SHAPE_RE.search(root[0].split(" = ", 1)[1])
            own = {"reduce-scatter": {
                "count": 1, "bytes": _shape_bytes(kept.group(0))}}
        for op, ent in own.items():
            if op != "total":
                out[op] = [ent["count"], ent["bytes"]]
        for line in bodies.get(name, ()):
            for kind, callee, branches in _CALLEE_RE.findall(line):
                names = ([callee] if callee else
                         [b.strip().lstrip("%") for b in branches.split(",")])
                times = _trip_count(line, bodies) if kind == "body" else 1
                for callee_name in names:
                    if times is None and total(callee_name):
                        raise ValueError(
                            f"collectives {sorted(total(callee_name))} run "
                            f"inside the loop {callee_name!r}, whose trip "
                            f"count cannot be read from the program")
                    for op, (c, b) in total(callee_name).items():
                        ent = out.setdefault(op, [0, 0])
                        ent[0] += times * c
                        ent[1] += times * b
        return out

    out = {op: {"count": c, "bytes": b} for op, (c, b) in
           total(entry).items()}
    out["total"] = {"count": sum(v["count"] for v in out.values()),
                    "bytes": sum(v["bytes"] for v in out.values())}
    return out


def merged_stats(many) -> Dict[str, dict]:
    """The stats of several programs as one: counts and bytes summed."""
    out: Dict[str, dict] = {}
    for stats in many:
        for op, ent in stats.items():
            into = out.setdefault(op, {"count": 0, "bytes": 0})
            into["count"] += ent["count"]
            into["bytes"] += ent["bytes"]
    return out


def sent_bytes(stats: Dict[str, dict], n_devices: int) -> float:
    """Bytes one participant sends for the collectives of `stats` (either
    function's), by the ring model `ici_seconds` prices: an all-gather of
    output P sends P (n-1)/n, an all-reduce twice that, a reduce-scatter
    of OUTPUT P (its input is n P) sends P (n-1), an all-to-all P (n-1)/n
    and a collective-permute P."""
    if n_devices <= 1:
        return 0.0
    scale = (n_devices - 1) / n_devices
    per_op = {"all-reduce": 2.0 * scale, "all-gather": scale,
              "reduce-scatter": float(n_devices - 1), "all-to-all": scale,
              "collective-permute": 1.0}
    return float(sum(per_op[op] * ent["bytes"]
                     for op, ent in stats.items() if op != "total"))


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
