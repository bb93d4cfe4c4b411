"""The comparison that decides `correct`: numbers from the timed path's
output against the configuration's plain reference, each held to a limit of
its own (benchmark/limits/<cell>.json; how each was set is in PERF.md)."""

from __future__ import annotations

import json
import sys

import numpy as np


SOLVE_BUDGET = 1 << 20       # gathered counterpart rows per reference block
SOLVE_MAX_B = 4096            # systems per reference block


def _pow2_at_least(x: np.ndarray, floor: int = 8) -> np.ndarray:
    x = np.maximum(np.asarray(x, np.int64), floor)
    return (1 << np.ceil(np.log2(x)).astype(np.int64))


def solve_csr(reference, table, ptr, idx, val, lam: float, scaling: str
              ) -> np.ndarray:
    """The reference's rows for E entities: entity e has the ratings
    val[ptr[e]:ptr[e+1]] of the counterpart rows table[idx[...]]. Solved in
    blocks of [B, K] whose shapes depend on K alone (K a power of two, B *
    K within SOLVE_BUDGET), so that every seed meets the same few
    programs. `table` stays where it is (a device array on the chip); the
    blocks are fetched once all are queued, so that preparing one overlaps
    solving the last."""
    import jax
    deg = np.diff(ptr)
    width = _pow2_at_least(deg)
    out = np.zeros((deg.size, int(table.shape[1])), np.float32)

    @jax.jit
    def block(table, bidx, bval, bmask):
        return reference.solve_rows(table[bidx], bval, bmask, lam, scaling)

    queued = []
    for K in np.unique(width):
        K = int(K)
        ents = np.flatnonzero(width == K)
        B = max(1, min(SOLVE_MAX_B, SOLVE_BUDGET // K))
        lane = np.arange(K, dtype=np.int64)[None, :]
        for lo in range(0, ents.size, B):
            e = ents[lo:lo + B]
            mask = np.zeros((B, K), bool)
            mask[:e.size] = lane < deg[e][:, None]
            pos = np.zeros((B, K), np.int64)
            pos[:e.size] = ptr[e][:, None] + lane
            pos[~mask] = 0
            bidx = np.where(mask, idx[pos], 0).astype(np.int32)
            bval = np.where(mask, val[pos], 0).astype(np.float32)
            queued.append((e, block(table, bidx, bval,
                                    mask.astype(np.float32))))
    for e, x in queued:
        out[e] = np.asarray(x)[:e.size]
    return out


def als_reference(reference, config: dict, sample: dict, seed_tables: dict,
                  precision: str | None = None) -> dict:
    """Each half-sweep once from the seed's tables, for the sampled rows
    alone: the sampled users solved from the seed's item table, the sampled
    items from the seed's user table. With a `precision`, the tables are
    rounded through it before they are read (the control)."""
    import jax
    lam, scaling = float(config["lam"]), config["lambda_scaling"]
    out = {}
    for side, counter in (("user", "item"), ("item", "user")):
        table = seed_tables[counter]
        if precision:
            table = jax.device_put(reference.round_operands(table, precision))
        s = sample[side]
        out[side] = solve_csr(reference, table, s["ptr"], s["idx"], s["val"],
                              lam, scaling)
    return out


def als_reference_end(reference, config: dict, sample: dict,
                      rater_rows: np.ndarray,
                      precision: str | None = None) -> np.ndarray:
    """The sampled items solved from `rater_rows`: row j is the user row
    of the sampled items' j-th rating, as the program's last item
    half-sweep read it (padded to one length for every seed). With a
    `precision`, those rows are rounded through it first (the control)."""
    import jax
    s = sample["item"]
    if precision:
        rater_rows = reference.round_operands(rater_rows, precision)
    return solve_csr(reference, jax.device_put(rater_rows), s["ptr"],
                     np.arange(s["idx"].size), s["val"],
                     float(config["lam"]), config["lambda_scaling"])


def row_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """|got_j - want_j| / |want_j| per row; infinite where got is not
    finite."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = (np.linalg.norm(got - want, axis=1)
           / np.maximum(np.linalg.norm(want, axis=1), 1e-30))
    err[~np.isfinite(got).all(axis=1)] = np.inf
    return err


def als_numbers(got: dict, want: dict, sample: dict, strata) -> dict:
    """Per comparison (`user`, `item`: the first half-sweeps; `item_end`:
    the window's last item half-sweep): the median and the widest row error
    over the sampled rows, and (without limits) the widest in each stratum
    of rating count."""
    out = {}
    for name in got:
        err = row_errors(got[name], want[name])
        deg = sample[name.split("_")[0]]["degree"]
        out[f"{name}_err_p50"] = float(np.median(err))
        out[f"{name}_err_max"] = float(err.max())
        out[f"{name}_rows"] = int(err.size)
        for lo, hi in strata:
            sel = (deg >= lo) & (deg <= hi)
            if sel.any():
                out[f"{name}_err_max.n{lo}"] = float(err[sel].max())
        out[f"{name}_err_heaviest"] = float(err[np.argmax(deg)])
        out[f"{name}_heaviest_n"] = int(deg.max())
    return out


def parse_answer(body: str) -> dict | None:
    """{"ids": [...], "scores": [...]} of one /queries.json answer, or None
    where the body is not one."""
    try:
        rows = json.loads(body)["itemScores"]
        return {"ids": [int(r["item"]) for r in rows],
                "scores": [float(r["score"]) for r in rows]}
    except (ValueError, KeyError, TypeError):
        return None


def topk_numbers(answers: list, user_rows: np.ndarray,
                 item_table: np.ndarray, reference, k: int,
                 precision: str | None = None) -> dict:
    """For each sampled answer (ids and scores as served; None where there
    was none) against the reference's ranking over the same tables:

    rank_gap  the widest gap, over the k positions, by which the exact
              score of the served item lies below the reference's item at
              that position, as a share of the reference's best score (0
              for an exact ranking; a near-tie swap reads a rounding);
    score_err the widest |served score - exact score of that item|, same
              share;
    malformed answers with other than k distinct valid ids, or none.
    With a `precision` the reference's own ranking at that lower operand
    precision stands in for the served answers (the control)."""
    best_s, _ = reference.top_k(user_rows, item_table, k)
    malformed = 0
    if precision is None:
        good = np.zeros(len(answers), bool)
        ids = np.zeros((len(answers), k), np.int64)
        served = np.zeros((len(answers), k))
        for q, a in enumerate(answers):
            got = a["ids"] if a else []
            if (len(got) != k or len(set(got)) != k or min(got) < 0
                    or max(got) >= item_table.shape[0]):
                malformed += 1
                continue
            ids[q], served[q], good[q] = got, a["scores"], True
    else:
        good = np.ones(len(answers), bool)
        served, ids = reference.top_k(user_rows, item_table, k, precision)
    if not good.any():
        return {"rank_gap_max": None, "score_err_max": None,
                "malformed": int(malformed), "answers": len(answers)}
    exact = reference.scores_of(user_rows, item_table, ids)
    scale = np.abs(best_s[:, :1])
    gap = (np.maximum(best_s - exact, 0.0) / scale)[good]
    err = (np.abs(served - exact) / scale)[good]
    return {"rank_gap_max": float(gap.max()),
            "rank_gap_p50": float(np.median(gap.max(axis=1))),
            "score_err_max": float(err.max()),
            "score_err_p50": float(np.median(err.max(axis=1))),
            "malformed": int(malformed), "answers": len(answers)}


def decide(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each limited number beside its limit; correct when every one is
    present, finite and within it."""
    compared = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = (value is not None and np.isfinite(value)
                and value <= limit)
        ok = ok and bool(good)
        compared[name] = {"value": value, "limit": limit}
    return ok, compared


def report(compared: dict, correct: bool) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error."""
    for name, c in compared.items():
        v = c["value"]
        shown = "missing" if v is None else f"{v:.6g}"
        print(f"compared {name} = {shown} (limit {c['limit']:g})",
              file=sys.stderr)
    print(f"correct = {str(correct).lower()}", file=sys.stderr, flush=True)
