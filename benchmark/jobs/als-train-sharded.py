"""Job kind `als-train-sharded`: steady explicit-ALS iterations over factor
tables that no single chip holds, through the program's own training path
for `factor_sharding: "model"` on the chips of one host, as `pio train`
(`ALSAlgorithm.train` -> `als_train`) runs it: the mesh `model_mesh` builds
over all the host's chips, both tables row-sharded over its model axis, the
plans' batches multiples of the chips and divided over them
(`ops/als.batch_shards`, `_upload_plan(..., "model")`), every chip solving
its own quarter of every batch (`_run_side` -> the per-chip half-sweep). The
interface, the sample of rows, the window, `work` and what is left out of
als_train (host-side init, the sentinel's copies, the final fetch) are
jobs/als-train.py's, whose Job this one extends. Tables and plans are
placed by the program's own functions: this file writes no PartitionSpec.

The parameters are resolved through the program before any data is made, so
a program that cannot divide a plan over the chips that share the tables
(the parent of the PR that brought this cell: every chip would hold the
whole plan and solve every system, and the half-sweep's temporaries do not
fit, PERF.md section 4) fails at once, and a run whose batches turn out
divided another number of ways than the configuration's chips refuses
itself (`batch_shards`).

`correct` is jobs/als-train.py's two comparisons, with three differences
that the size forces. The sampled rows are read from whichever shard holds
them (a gather on the sharded table), and the sample must hold rows of every
shard of both tables: a shard that was never written, or written by the
wrong chip, then fails it (`spans["sample_rows_by_shard"]`). The seed's
tables have the program's row count (n + 1 rounded up to the shards), on
both sides of the comparison, and the reference reads its copy of them
sharded over the same chips: the user table alone is 16.8 GB. And set-up
parks no table on the host: the user half-sweep runs first from the seed's
tables and its sampled rows are set aside, then the user table is made again
from the seed and the item half-sweep runs from it; the window goes on from
that state (the seed's user table, the item table one half-sweep in)."""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark.lib import datagen
from benchmark.lib.spec import load_module

_base = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "als-train.py"), "job_als_train_base")
_sync = _base._sync


class Job(_base.Job):
    # -- set-up -----------------------------------------------------------
    def setup(self):
        import jax

        from predictionio_tpu.compile.cache import enable_persistent_cache
        from predictionio_tpu.ops import als
        from predictionio_tpu.ops.ratings import (RatingsCOO, plan_for_items,
                                                  plan_for_users)
        from predictionio_tpu.parallel.mesh import model_mesh
        enable_persistent_cache()
        c = self.config
        sharding = c["factor_sharding"]
        # the mesh as ALSAlgorithm.train resolves it for "model"
        mesh = model_mesh(len(jax.devices()))
        if mesh.model_parallelism != int(c["chips_sharing_the_tables"]):
            raise SystemExit(
                f"benchmark: the configuration shares its tables over "
                f"{c['chips_sharing_the_tables']} chips; this host's mesh "
                f"has {mesh.model_parallelism}")
        # the configuration's parameters as ALSAlgorithm.train and
        # als_train resolve them for this mesh
        self.als_cfg = als.ALSConfig(
            rank=int(c["rank"]), lam=float(c["lam"]),
            lambda_scaling=c["lambda_scaling"],
            implicit_prefs=bool(c["implicit_prefs"]),
            factor_dtype=c["factor_dtype"],
            compute_dtype=als.default_compute_dtype(),
            solver=als.sweep_solver(c["solver"], mesh, sharding),
            sweep_chunk=int(c["sweep_chunk"]),
            work_budget=int(c["work_budget"]),
            bucket_ratio=float(c["bucket_ratio"]),
            factor_sharding=sharding)
        cfg = self.als_cfg
        chunk = als.resolve_sweep_chunk(cfg.sweep_chunk, mesh.n_devices)
        self.resolved = {"solver": cfg.solver,
                         "compute_dtype": cfg.compute_dtype,
                         "sweep_chunk": chunk,
                         "gather_layout": als._gather_layout(
                             mesh, cfg.rank, sharding)}
        batch_multiple = als.batch_shards(mesh, sharding)

        t0 = time.perf_counter()
        user_idx, item_idx, value = datagen.ratings(c, self.seed)
        self.spans["generate_s"] = time.perf_counter() - t0
        self.n_users, self.n_items = int(c["n_users"]), int(c["n_items"])
        self.nnz = int(user_idx.size)
        coo = RatingsCOO(user_idx, item_idx, value, self.n_users,
                         self.n_items)
        self._draw_sample(user_idx, item_idx, value)

        t0 = time.perf_counter()
        kw = dict(work_budget=cfg.work_budget,
                  batch_multiple=batch_multiple,
                  bucket_ratio=cfg.bucket_ratio)
        user_plan = plan_for_users(coo, **kw)
        item_plan = plan_for_items(coo, **kw)
        self.spans["plan_s"] = time.perf_counter() - t0
        del coo, user_idx, item_idx, value

        t0 = time.perf_counter()
        self._rank, self._mesh = cfg.rank, mesh
        # row counts as als_train's _init_factors rounds them for the shards
        self._table_rows = {
            "user": als.table_rows(self.n_users, mesh.model_parallelism),
            "item": als.table_rows(self.n_items, mesh.model_parallelism)}
        self.U = self._seed_table("user")
        self.V = self._seed_table("item")
        self.user_groups = als._upload_plan(mesh, user_plan, chunk, cfg.rank,
                                            sharding)
        self.item_groups = als._upload_plan(mesh, item_plan, chunk, cfg.rank,
                                            sharding)
        del user_plan, item_plan
        self.lam = mesh.put_replicated(np.float32(cfg.lam))
        self.alpha = mesh.put_replicated(np.float32(cfg.alpha))
        self._take = jax.jit(lambda table, ix: table[ix],
                             out_shardings=mesh.replicated())
        self._rows = {side: mesh.put_replicated(self.sample[side]["rows"])
                      for side in ("user", "item")}
        raters = np.zeros(int(self.traffic["check_max_ratings"]), np.int32)
        raters[:self.sample["item"]["idx"].size] = self.sample["item"]["idx"]
        self._raters = mesh.put_replicated(raters)
        self._run_side = als._run_side
        n_table, n_batch = als.sweep_shards(self.U, self.user_groups)
        self.spans["table_shards"], self.spans["batch_shards"] = (n_table,
                                                                  n_batch)
        if n_batch != mesh.model_parallelism:
            raise SystemExit(
                f"benchmark: the batches are divided {n_batch} ways over "
                f"{mesh.model_parallelism} chips that share the tables: "
                f"every chip would solve systems of another's share")
        self._sample_by_shard(n_table)
        _sync(self.V)
        float(np.asarray(jax.device_get(
            self.item_groups[-1][2][:1, :1, :1])).ravel()[0])
        self.spans["upload_s"] = time.perf_counter() - t0

        # each half-sweep once from the seed's tables, through the window's
        # own calls: they compile (or load from the cache), and their
        # sampled rows are what `correct` compares. No table waits on the
        # host: the user table is made again from the seed.
        t0 = time.perf_counter()
        self.user_half_sweep()
        first = {"user": self._snapshot("user")}
        self.spans["user_half_sweep_s"] = time.perf_counter() - t0
        del self.U               # before the next is made: never three
        self.U = self._seed_table("user")
        self.item_half_sweep()
        first["item"] = self._snapshot("item")
        self.first = first
        self.spans["first_iteration_s"] = time.perf_counter() - t0
        # what the compiled half-sweeps exchange, from the program's own
        # reading of their HLO (a compile the cache serves: the programs
        # have just run)
        t0 = time.perf_counter()
        self.spans["exchange_bytes"] = {
            "user": als.sweep_exchange(mesh, self.user_groups, self.U,
                                       self.V, cfg),
            "item": als.sweep_exchange(mesh, self.item_groups, self.V,
                                       self.U, cfg)}
        self.spans["exchange_read_s"] = time.perf_counter() - t0

    def _seed_table(self, side: str):
        """The seed's [rows, rank] table of one side, with the program's
        row count, placed as als_train places its own."""
        return datagen.init_table(
            self._table_rows[side], self._rank, self.seed,
            1 if side == "user" else 2, self._mesh.model_sharded(2))

    def _sample_by_shard(self, shards: int) -> None:
        """How many sampled rows each shard of each table holds; a shard
        with none would go unjudged."""
        by_shard = {}
        for side in ("user", "item"):
            per_shard = self._table_rows[side] // shards
            by_shard[side] = np.bincount(
                self.sample[side]["rows"] // per_shard,
                minlength=shards).tolist()
            if min(by_shard[side]) == 0:
                raise SystemExit(
                    f"benchmark: the sample holds no {side} row of some "
                    f"shard ({by_shard[side]}): draw more rows a stratum")
        self.spans["sample_rows_by_shard"] = by_shard

    # -- the window -------------------------------------------------------
    def user_half_sweep(self):
        import jax
        with jax.profiler.TraceAnnotation("bench.user_half_sweep"):
            self.U = self._run_side(self.user_groups, self.U, self.V,
                                    self.als_cfg, None, self.lam,
                                    self.alpha, side="user",
                                    mesh=self._mesh)

    def item_half_sweep(self):
        import jax
        with jax.profiler.TraceAnnotation("bench.item_half_sweep"):
            self.V = self._run_side(self.item_groups, self.V, self.U,
                                    self.als_cfg, None, self.lam,
                                    self.alpha, side="item",
                                    mesh=self._mesh)

    def window(self, seconds: float) -> dict:
        out = super().window(seconds)
        # bytes one chip sends in an iteration
        out["exchange_sent_bytes_per_iteration"] = float(sum(
            side["sent"] for side in self.spans["exchange_bytes"].values()))
        out["chips"] = self._mesh.n_devices
        return out

    # -- after the window -------------------------------------------------
    def collect(self) -> dict:
        collected = super().collect()
        del self.lam, self.alpha
        return collected

    def compare(self, collected: dict, reference,
                precision: str | None = None) -> dict:
        """jobs/als-train.py's comparison with the seed's tables made at
        the program's row counts and left sharded over the chips, on the
        control's side too (the user table does not fit one chip)."""
        from benchmark.lib import compare
        c = self.config
        if self._seed_tables is None:
            self._seed_tables = {side: self._seed_table(side)
                                 for side in ("user", "item")}
        if self._want is None:
            self._want = compare.als_reference(reference, c, self.sample,
                                               self._seed_tables)
            self._want["item_end"] = compare.als_reference_end(
                reference, c, self.sample, collected["raters"])
        if precision is None or precision == "fault:half":
            return super().compare(collected, reference, precision)
        # the control: the reference in the program's place, its tables
        # rounded through `precision` on the host and placed as the seed's,
        # whose own copies leave the chips first (two pairs do not fit
        # beside a block's temporaries) and are made again when next asked
        rounded = {side: reference.round_operands(table, precision)
                   for side, table in self._seed_tables.items()}
        self._seed_tables = None
        rounded = {side: self._mesh.put_model_sharded(table)
                   for side, table in rounded.items()}
        got = compare.als_reference(reference, c, self.sample, rounded)
        del rounded
        got["item_end"] = compare.als_reference_end(
            reference, c, self.sample, collected["raters"], precision)
        numbers = compare.als_numbers(got, self._want, self.sample,
                                      self.traffic["strata"])
        numbers["nonfinite_rows_at_end"] = int(sum(
            (~np.isfinite(collected["last"][side]).all(axis=1)).sum()
            for side in ("user", "item")))
        return numbers
