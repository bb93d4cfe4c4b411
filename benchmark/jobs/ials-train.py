"""Job kind `ials-train`: steady implicit-feedback ALS iterations through the
program's own training path, as als_train runs it with
`implicit_prefs=True`: ops/ratings plan -> ops/als._upload_plan -> per
iteration the Gram and eigendecomposition of the item table (_gram_eig),
the user half-sweep (_run_side), the Gram and eigendecomposition of the
user table, the item half-sweep; the configuration's parameters as
`pio train` resolves them on the device. The interface, the sample of rows,
the window and what is left out of als_train (host-side init, the
sentinel's copies, the final fetch) are jobs/als-train.py's, whose Job this
one extends.

The program has to take the Gram over the table's live rows itself
(`_gram_eig(table, n_live=...)`): sliced by the caller, as before PR 27,
the item table is a second 3.3 GB array for as long as each Gram runs. A
program without that fails here at once, before any data is made.

`correct`, two comparisons of rows drawn from the seed, both made once the
window has closed and the program's state is freed:

first  set-up drives the window's own calls once each from the seed's
       tables (item Gram and user half-sweep from the seed's item table;
       user Gram and item half-sweep from the seed's user table) and sets
       the sampled rows aside; the window goes on from that state. The
       plain reference takes its own Gram of the same seed's table and
       solves the same rows.
end    the sampled item rows as the window's LAST item half-sweep left
       them, against the reference's solve from the whole user table read
       back when the window closes (that half-sweep's input: nothing
       writes the user table after it), the reference taking its own Gram
       of it. Program-prepared data, as in jobs/als-train.py: it holds the
       window's own last answers, whatever the iteration, through Gram,
       eigh, gather, every solver route and scatter.
"""

from __future__ import annotations

import inspect
import os
import time

import numpy as np

from benchmark.lib import compare, counts_implicit, datagen, datagen_implicit
from benchmark.lib.spec import load_module

_base = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "als-train.py"), "job_als_train_base")


def _solve_csr(reference, table, gram, ptr, idx, val, config: dict
               ) -> np.ndarray:
    """lib/compare.solve_csr for the implicit reference: the rows of E
    entities, entity e observing val[ptr[e]:ptr[e+1]] of the counterpart
    rows table[idx[...]], solved in blocks of [B, K] whose shapes depend
    on K alone. The Gram is an argument of the jitted block, not a value
    closed over: every seed then meets the same few programs."""
    import jax
    lam, alpha = float(config["lam"]), float(config["alpha"])
    scaling = config["lambda_scaling"]
    deg = np.diff(ptr)
    width = compare._pow2_at_least(deg)
    out = np.zeros((deg.size, int(table.shape[1])), np.float32)

    @jax.jit
    def block(table, gram, bidx, bval, bmask):
        return reference.solve_rows(table[bidx], bval, bmask, gram, lam,
                                    alpha, scaling)

    queued = []
    for K in np.unique(width):
        K = int(K)
        ents = np.flatnonzero(width == K)
        B = max(1, min(compare.SOLVE_MAX_B, compare.SOLVE_BUDGET // K))
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
            queued.append((e, block(table, gram, bidx, bval,
                                    mask.astype(np.float32))))
    for e, x in queued:
        out[e] = np.asarray(x)[:e.size]
    return out


def _reference_rows(reference, config: dict, rows: dict, table, n_live: int,
                    precision: str | None) -> np.ndarray:
    """The sampled `rows` of one side solved from `table`, whose first
    `n_live` rows are entities, with the reference's own Gram of them;
    with a `precision`, from the table rounded through it (the control)."""
    import jax
    if precision:
        table = reference.round_operands(table, precision)
    table = jax.device_put(table)
    return _solve_csr(reference, table, reference.gram(table, n_live),
                      rows["ptr"], rows["idx"], rows["val"], config)


class Job(_base.Job):
    # -- set-up -----------------------------------------------------------
    def setup(self):
        import jax

        from predictionio_tpu.compile.cache import enable_persistent_cache
        from predictionio_tpu.ops import als
        from predictionio_tpu.ops.ratings import (RatingsCOO, plan_for_items,
                                                  plan_for_users)
        from predictionio_tpu.ops.solve import resolve_solver
        from predictionio_tpu.parallel.mesh import current_mesh
        if "n_live" not in inspect.signature(als._gram_eig_impl).parameters:
            raise SystemExit(
                "benchmark: this program's ops/als._gram_eig takes no count "
                "of live rows, so it cannot run the implicit configuration "
                "as the cell drives it (jobs/ials-train.py)")
        enable_persistent_cache()
        c = self.config
        t0 = time.perf_counter()
        user_idx, item_idx, value = datagen_implicit.view_events(c, self.seed)
        self.spans["generate_s"] = time.perf_counter() - t0
        self.n_users, self.n_items = int(c["n_users"]), int(c["n_items"])
        self.nnz = int(user_idx.size)
        coo = RatingsCOO(user_idx, item_idx, value, self.n_users,
                         self.n_items)
        self._draw_sample(user_idx, item_idx, value)

        mesh = current_mesh()
        # the configuration's parameters as ECommAlgorithm.train and
        # als_train resolve them for this device
        self.als_cfg = als.ALSConfig(
            rank=int(c["rank"]), lam=float(c["lam"]),
            alpha=float(c["alpha"]), lambda_scaling=c["lambda_scaling"],
            implicit_prefs=bool(c["implicit_prefs"]),
            factor_dtype=c["factor_dtype"],
            compute_dtype=als.default_compute_dtype(),
            solver=resolve_solver(c["solver"], mesh.n_devices),
            sweep_chunk=int(c["sweep_chunk"]),
            work_budget=int(c["work_budget"]),
            bucket_ratio=float(c["bucket_ratio"]))
        cfg = self.als_cfg
        self.resolved = {"solver": cfg.solver,
                         "compute_dtype": cfg.compute_dtype,
                         "sweep_chunk": als.resolve_sweep_chunk(
                             cfg.sweep_chunk, mesh.n_devices)}

        t0 = time.perf_counter()
        kw = dict(work_budget=cfg.work_budget,
                  batch_multiple=mesh.data_parallelism,
                  bucket_ratio=cfg.bucket_ratio)
        user_plan = plan_for_users(coo, **kw)
        item_plan = plan_for_items(coo, **kw)
        self.spans["plan_s"] = time.perf_counter() - t0
        del coo, user_idx, item_idx, value

        t0 = time.perf_counter()
        rank = cfg.rank
        self.U = datagen.init_table(self.n_users + 1, rank, self.seed, 1,
                                    mesh.replicated())
        self.V = datagen.init_table(self.n_items + 1, rank, self.seed, 2,
                                    mesh.replicated())
        chunk = self.resolved["sweep_chunk"]
        self.user_groups = als._upload_plan(mesh, user_plan, chunk)
        self.item_groups = als._upload_plan(mesh, item_plan, chunk)
        del user_plan, item_plan
        self.lam = mesh.put_replicated(np.float32(cfg.lam))
        self.alpha = mesh.put_replicated(np.float32(cfg.alpha))
        self._take = jax.jit(lambda table, ix: table[ix])
        self._rows = {side: jax.device_put(self.sample[side]["rows"])
                      for side in ("user", "item")}
        self._run_side, self._side_gram = als._run_side, als._side_gram
        _base._sync(self.V)
        if self.item_groups:
            float(np.asarray(jax.device_get(
                self.item_groups[-1][2][:1, :1, :1])).ravel()[0])
        self.spans["upload_s"] = time.perf_counter() - t0

        # each half-sweep once from the seed's tables, through the window's
        # own calls: they compile (or load from the cache), and their
        # sampled rows are what `correct` compares. The user table the
        # first leaves waits on the host while the second reads the seed's.
        t0 = time.perf_counter()
        self.user_half_sweep()
        first = {"user": self._snapshot("user")}
        parked = np.asarray(self.U)
        del self.U
        self.U = datagen.init_table(self.n_users + 1, rank, self.seed, 1,
                                    mesh.replicated())
        self.item_half_sweep()
        first["item"] = self._snapshot("item")
        del self.U
        self.U = jax.device_put(parked, mesh.replicated())
        _base._sync(self.U)
        self.first = first
        self.spans["first_iteration_s"] = time.perf_counter() - t0

    # -- the timed path ---------------------------------------------------
    def user_half_sweep(self):
        import jax
        with jax.profiler.TraceAnnotation("bench.user_half_sweep"):
            gram = self._side_gram(self.als_cfg, self.V, self.n_items, "item")
            self.U = self._run_side(self.user_groups, self.U, self.V,
                                    self.als_cfg, gram, self.lam,
                                    self.alpha, side="user")

    def item_half_sweep(self):
        import jax
        with jax.profiler.TraceAnnotation("bench.item_half_sweep"):
            gram = self._side_gram(self.als_cfg, self.U, self.n_users, "user")
            self.V = self._run_side(self.item_groups, self.V, self.U,
                                    self.als_cfg, gram, self.lam,
                                    self.alpha, side="item")

    # -- after the window -------------------------------------------------
    def collect(self) -> dict:
        """The sampled rows as the window left them and the whole user
        table, which its last item half-sweep read; then free the
        program's state."""
        last = {side: self._snapshot(side) for side in ("user", "item")}
        users = np.asarray(self.U)
        del self.U, self.V, self.user_groups, self.item_groups, self._rows
        return {"first": self.first, "last": last, "users": users}

    def compare(self, collected: dict, reference,
                precision: str | None = None) -> dict:
        """The program's first half-sweeps and the window's last item
        half-sweep against the reference's; with a `precision`, the
        reference at that lower precision in the program's place (the
        control)."""
        c, rank = self.config, int(self.config["rank"])

        def seed_table(side):
            n, salt = ((self.n_users, 1) if side == "user"
                       else (self.n_items, 2))
            return datagen.init_table(n + 1, rank, self.seed, salt), n

        def reference_of(p):
            out = {}
            for side, counter in (("user", "item"), ("item", "user")):
                table, n_live = seed_table(counter)
                out[side] = _reference_rows(reference, c, self.sample[side],
                                            table, n_live, p)
                del table
            out["item_end"] = _reference_rows(
                reference, c, self.sample["item"], collected["users"],
                self.n_users, p)
            return out

        if self._want is None:
            self._want = reference_of(None)
        if precision is None:
            got = dict(collected["first"], item_end=collected["last"]["item"])
        elif precision == "fault:half":
            # half of every batch left out, planted in the reference put in
            # the program's place: every second sampled row stays as the
            # seed made it
            got = {}
            for name in ("user", "item", "item_end"):
                side = name.split("_")[0]
                rows = self.sample[side]["rows"][1::2]
                got[name] = self._want[name].copy()
                got[name][1::2] = np.asarray(seed_table(side)[0][rows])
        else:
            got = reference_of(precision)
        numbers = compare.als_numbers(got, self._want, self.sample,
                                      self.traffic["strata"])
        numbers["nonfinite_rows_at_end"] = int(
            (~np.isfinite(collected["users"]).all(axis=1)).sum()
            + (~np.isfinite(collected["last"]["item"]).all(axis=1)).sum())
        return numbers

    def work(self) -> dict:
        """What one iteration needs, from the data's degrees, the table
        sizes and the rank."""
        rank = int(self.config["rank"])
        return {
            "iteration_flops": counts_implicit.ials_iteration_flops(
                self.user_degrees, self.item_degrees, rank),
            "iteration_bytes": counts_implicit.ials_iteration_bytes(
                self.user_degrees, self.item_degrees, rank,
                np.dtype(self.config["factor_dtype"]).itemsize),
        }
