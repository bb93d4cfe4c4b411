"""Job kind `als-train`: steady ALS iterations through the program's own
training path (ops/ratings plan -> ops/als._upload_plan -> the _run_side /
_solve_sweep half-sweeps that als_train runs), with the configuration's
parameters as `pio train` resolves them on the device.

What als_train does around the sweeps and this job does not: the host-side
factor init (made here on the device from the seed), the sentinel's
last-good HBM copies (two more tables: 16.6 GB at the amazonbooks size, see
PERF.md) and the final whole-table fetch.

`correct`, two comparisons of rows drawn from the seed, both made once the
window has closed and the program's state is freed
(benchmark/lib/compare.py):

first  set-up drives the window's own two half-sweep calls once each from
       the seed's tables (users from the seed's item table, items from the
       seed's user table; they are also the warm-up) and sets the sampled
       rows aside; the window goes on from that state. The configuration's
       plain reference solves the same rows from the same seed's tables,
       so it takes nothing the program made and needs the counterpart rows
       of the sampled rows' ratings alone (a second or two, where following
       the item half-sweep from the program's own first user table took
       55 s; PERF.md section 4).
end    the sampled item rows as the window's LAST item half-sweep left
       them, against the reference's solve of the same systems from the
       user rows that half-sweep read: the rows of the sampled items'
       raters, read back from the program's user table when the window
       closes. That input is program-prepared data (a user row the program
       got wrong is wrong on both sides: the `first` comparison is what
       holds user rows); what it holds is the window's own last answers,
       whatever the iteration, through gather, Gram, every solver route
       and scatter.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.lib import counts, datagen


def _sync(table) -> float:
    """Close a timed region: a one-element fetch cannot complete before the
    device has finished the chain that produces the table."""
    import jax
    return float(np.asarray(jax.device_get(table[:1, :1]))[0, 0])


class Job:
    def __init__(self, cell: dict, seed: int, spans: dict):
        self.cell, self.seed, self.spans = cell, int(seed), spans
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self._want = self._seed_tables = None   # the reference's, once made

    # -- set-up -----------------------------------------------------------
    def setup(self):
        import jax

        from predictionio_tpu.compile.cache import enable_persistent_cache
        from predictionio_tpu.ops import als
        from predictionio_tpu.ops.ratings import (RatingsCOO, plan_for_items,
                                                  plan_for_users)
        from predictionio_tpu.ops.solve import resolve_solver
        from predictionio_tpu.parallel.mesh import current_mesh
        enable_persistent_cache()
        c = self.config
        t0 = time.perf_counter()
        user_idx, item_idx, value = datagen.ratings(c, self.seed)
        self.spans["generate_s"] = time.perf_counter() - t0
        self.n_users, self.n_items = int(c["n_users"]), int(c["n_items"])
        self.nnz = int(user_idx.size)
        coo = RatingsCOO(user_idx, item_idx, value, self.n_users,
                         self.n_items)
        self._draw_sample(user_idx, item_idx, value)

        mesh = current_mesh()
        # the configuration's parameters as ALSAlgorithm.train and
        # als_train resolve them for this device
        self.als_cfg = als.ALSConfig(
            rank=int(c["rank"]), lam=float(c["lam"]),
            lambda_scaling=c["lambda_scaling"],
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
        # the sampled items' raters, padded to the allowance: one shape
        # for every seed
        raters = np.zeros(int(self.traffic["check_max_ratings"]), np.int32)
        raters[:self.sample["item"]["idx"].size] = self.sample["item"]["idx"]
        self._raters = jax.device_put(raters)
        self._run_side = als._run_side
        _sync(self.V)
        if self.item_groups:
            float(np.asarray(jax.device_get(
                self.item_groups[-1][2][:1, :1, :1])).ravel()[0])
        self.spans["upload_s"] = time.perf_counter() - t0

        # each half-sweep once from the seed's tables, through the window's
        # own calls: they compile (or load from the cache), and their
        # sampled rows are what `correct` compares. The user table the
        # first leaves waits on the host while the second reads the seed's
        # (a third table beside the sweep's temporaries does not fit).
        t0 = time.perf_counter()
        self.user_half_sweep()
        first = {"user": self._snapshot("user")}
        parked = np.asarray(self.U)
        del self.U               # before the next is made: never three
        self.U = datagen.init_table(self.n_users + 1, rank, self.seed, 1,
                                    mesh.replicated())
        self.item_half_sweep()
        first["item"] = self._snapshot("item")
        del self.U
        self.U = jax.device_put(parked, mesh.replicated())
        _sync(self.U)
        self.first = first
        self.spans["first_iteration_s"] = time.perf_counter() - t0

    def _snapshot(self, side: str) -> np.ndarray:
        table = self.U if side == "user" else self.V
        return np.asarray(self._take(table, self._rows[side]))

    def _draw_sample(self, user_idx, item_idx, value):
        """Draw, from the seed, the rows the reference will solve: some of
        every stratum of rating count (the program's three solver routes
        and the smallest bucket) and the heaviest row of each side, with
        every rating of each. `user_idx` is sorted (datagen.ratings)."""
        t = self.traffic
        rng = np.random.default_rng([self.seed, 2])
        deg_u = np.bincount(user_idx, minlength=self.n_users)
        deg_i = np.bincount(item_idx, minlength=self.n_items)
        self.user_degrees, self.item_degrees = deg_u, deg_i

        def draw(deg, cap):
            heaviest = np.argsort(-deg, kind="stable")[:t["check_heaviest"]]
            drawn = []
            for lo, hi in t["strata"]:
                pool = np.flatnonzero((deg >= lo) & (deg <= hi))
                take = min(t["check_rows_per_stratum"], pool.size)
                drawn.append(rng.choice(pool, take, replace=False))
            drawn = np.setdiff1d(np.concatenate(drawn), heaviest)
            # the drawn rows stop before their ratings outgrow the
            # allowance, the lightest going first; the heaviest stay
            drawn = drawn[np.argsort(deg[drawn], kind="stable")]
            room = cap - int(deg[heaviest].sum())
            drawn = drawn[np.cumsum(deg[drawn]) <= room]
            return np.sort(np.concatenate([heaviest, drawn]))

        def ratings_of(rows, mine):
            """Positions of the sampled rows' ratings, row by row."""
            slot = np.full(max(self.n_users, self.n_items), -1, np.int32)
            slot[rows] = np.arange(rows.size, dtype=np.int32)
            pos = slot[mine]
            sel = np.flatnonzero(pos >= 0)
            return sel[np.argsort(pos[sel], kind="stable")]

        self.sample = {}
        for side, deg, mine, theirs in (
                ("user", deg_u, user_idx, item_idx),
                ("item", deg_i, item_idx, user_idx)):
            rows = draw(deg, t["check_max_ratings"])
            sel = ratings_of(rows, mine)
            self.sample[side] = {
                "rows": rows.astype(np.int32), "degree": deg[rows],
                "ptr": np.concatenate([[0], np.cumsum(deg[rows])]),
                "idx": theirs[sel].astype(np.int32), "val": value[sel]}

    # -- the timed path ---------------------------------------------------
    def user_half_sweep(self):
        import jax
        with jax.profiler.TraceAnnotation("bench.user_half_sweep"):
            self.U = self._run_side(self.user_groups, self.U, self.V,
                                    self.als_cfg, None, self.lam,
                                    self.alpha)

    def item_half_sweep(self):
        import jax
        with jax.profiler.TraceAnnotation("bench.item_half_sweep"):
            self.V = self._run_side(self.item_groups, self.V, self.U,
                                    self.als_cfg, None, self.lam,
                                    self.alpha)

    def iteration(self):
        """One whole ALS iteration, closed by a hard sync."""
        import jax
        self.user_half_sweep()
        self.item_half_sweep()
        with jax.profiler.TraceAnnotation("bench.hard_sync"):
            _sync(self.V)

    def window(self, seconds: float) -> dict:
        done = 0
        t0 = time.perf_counter()
        while True:
            self.iteration()
            done += 1
            wall = time.perf_counter() - t0
            if wall >= seconds:
                break
        return {"attempted": done, "failed": 0, "wall_s": wall,
                "iterations": done,
                "train_ratings_per_s": self.nnz * done / wall}

    # -- after the window -------------------------------------------------
    def collect(self) -> dict:
        """The sampled rows as the window left them, and the user rows its
        last item half-sweep read for the sampled items; then free the
        program's state. (Of the user rows at the end only finiteness is
        judged: the item table they were solved from is gone.)"""
        last = {side: self._snapshot(side) for side in ("user", "item")}
        raters = np.asarray(self._take(self.U, self._raters))
        del self.U, self.V, self.user_groups, self.item_groups, self._rows
        del self._raters
        return {"first": self.first, "last": last, "raters": raters}

    def compare(self, collected: dict, reference,
                precision: str | None = None) -> dict:
        """The program's first half-sweeps and the window's last item
        half-sweep against the reference's; with a `precision`, the
        reference at that lower precision in the program's place (the
        control)."""
        from benchmark.lib import compare
        c = self.config
        if self._want is None:
            self._seed_tables = {
                "user": datagen.init_table(self.n_users + 1, int(c["rank"]),
                                           self.seed, 1),
                "item": datagen.init_table(self.n_items + 1, int(c["rank"]),
                                           self.seed, 2)}
            self._want = compare.als_reference(reference, c, self.sample,
                                               self._seed_tables)
            self._want["item_end"] = compare.als_reference_end(
                reference, c, self.sample, collected["raters"])
        if precision is None:
            got = dict(collected["first"], item_end=collected["last"]["item"])
        elif precision == "fault:half":
            # half of every batch left out, planted in the reference put in
            # the program's place: every second sampled row stays as the
            # seed made it
            got = {}
            for name in ("user", "item", "item_end"):
                side = name.split("_")[0]
                got[name] = self._want[name].copy()
                got[name][1::2] = np.asarray(self._seed_tables[side][
                    self.sample[side]["rows"][1::2]])
        else:
            got = compare.als_reference(reference, c, self.sample,
                                        self._seed_tables, precision)
            got["item_end"] = compare.als_reference_end(
                reference, c, self.sample, collected["raters"], precision)
        numbers = compare.als_numbers(got, self._want, self.sample,
                                      self.traffic["strata"])
        numbers["nonfinite_rows_at_end"] = int(sum(
            (~np.isfinite(collected["last"][side]).all(axis=1)).sum()
            for side in ("user", "item")))
        return numbers

    def release(self) -> None:
        self.sample = self.first = self._want = self._seed_tables = None

    def close(self) -> None:
        """Nothing of this job outlives the process's own state."""

    def work(self) -> dict:
        """What one iteration needs, from the data's degrees and the rank."""
        rank = int(self.config["rank"])
        return {
            "iteration_flops": counts.als_iteration_flops(
                self.user_degrees, self.item_degrees, rank),
            "iteration_bytes": counts.als_iteration_bytes(
                self.user_degrees, self.item_degrees, rank,
                np.dtype(self.config["factor_dtype"]).itemsize),
        }
