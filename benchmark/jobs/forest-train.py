"""Job kind `forest-train`: whole random forests back to back through the
program's own training path, the functions `RandomForestAlgorithm.train`
reaches through `forest_train`: ops/forest.forest_setup (host binning by
one searchsorted a feature, the upload of the bins and labels, the tree
state made on the device) once, then ops/forest.forest_grow per forest
(the bootstrap draw and every level's histogram, split search and
routing, ended by the fetch of the trees), with the configuration's
parameters as the template's engine.json would give them.

A program without `forest_setup` / `forest_grow` (the one-hot forest before
them, which asks 37.6 GB for the one-hot of the bins alone) fails here at
once, before any data is made.

`correct`, once the window has closed and the program's state is freed, on
the window's last forest (benchmark/limits/<cell>.json):

trees   tree 0, the last tree and two drawn from the seed, grown again by
        the configuration's plain reference from the same rows and the
        seed alone (its own edges, draws and subset size, each by the
        configuration's definition), level by level on the chip: every
        node's class counts
        (exact), every split (ties within 1e-6 of the node's impurity
        adopted; a threshold off the reference's edges is a mismatch),
        every node's majority class;
test    the held-out rows through the whole forest, the program's
        `ForestModel.predict_batch` against the reference's own traversal
        of the same arrays; `test_accuracy` beside it, not judged.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.lib import counts_forest, datagen_forest


class Job:
    def __init__(self, cell: dict, seed: int, spans: dict):
        self.cell, self.seed, self.spans = cell, int(seed), spans
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.resolved: dict = {}
        self.data = self.model = self._test = None

    # -- set-up -----------------------------------------------------------
    def setup(self):
        from predictionio_tpu.ops import forest
        if not all(hasattr(forest, f) for f in ("forest_setup",
                                                "forest_grow")):
            raise SystemExit(
                "benchmark: this program's ops/forest has no forest_setup "
                "/ forest_grow, so it cannot grow the configuration's forest "
                "(jobs/forest-train.py)")
        from predictionio_tpu.compile.cache import enable_persistent_cache
        enable_persistent_cache()
        c = self.config
        t0 = time.perf_counter()
        X, y = datagen_forest.rows(c, self.seed)
        self.spans["generate_s"] = time.perf_counter() - t0
        n = int(c["n_train"])
        self.X, self.y = X[:n], y[:n]
        self.X_test, self.y_test = X[n:], y[n:]
        self.num_trees = int(c["num_trees"])
        self.subset_k = forest.feature_subset_size(
            c["feature_subset_strategy"], X.shape[1], self.num_trees)
        t0 = time.perf_counter()
        self.data = forest.forest_setup(
            self.X, self.y, num_classes=int(c["num_classes"]),
            num_trees=self.num_trees, max_bins=int(c["max_bins"]))
        self.spans["bin_upload_s"] = time.perf_counter() - t0
        self._grow = forest.forest_grow
        self.resolved = {"subset_k": self.subset_k,
                         "hist_block": self.data.block,
                         "n_padded": int(self.data.xb.shape[1])}
        # one whole forest: every level's programs compile (or load)
        t0 = time.perf_counter()
        self.forest(self.seed)
        self.spans["first_forest_s"] = time.perf_counter() - t0

    # -- the timed path ---------------------------------------------------
    def forest(self, seed: int, telemetry: dict | None = None):
        """One whole forest; the fetch of its trees is the hard sync."""
        import jax
        c = self.config
        with jax.profiler.TraceAnnotation("bench.forest"):
            return self._grow(self.data, max_depth=int(c["max_depth"]),
                              impurity=c["impurity"],
                              subset_k=self.subset_k, seed=seed,
                              telemetry=telemetry)

    def window(self, seconds: float) -> dict:
        done = split = allowed = 0
        t0 = time.perf_counter()
        while True:
            tel: dict = {}
            self.model = self.forest(self.seed + done, tel)
            done += 1
            split += tel["nodes_split"]
            allowed += tel["nodes_allowed"]
            wall = time.perf_counter() - t0
            if wall >= seconds:
                break
        self.model_seed = self.seed + done - 1
        return {"attempted": done, "failed": 0, "wall_s": wall,
                "forests": done, "s_per_forest": wall / done,
                "nodes_split": split, "nodes_allowed": allowed,
                "train_ratings_per_s": self.X.shape[0] * done / wall}

    # -- after the window -------------------------------------------------
    def collect(self) -> dict:
        """The window's last forest and its seed; then free the program's
        state on the device."""
        self.data = None
        return {"model": self.model, "seed": self.model_seed}

    def trees(self) -> list[int]:
        """Tree 0, the last, and `check_trees_drawn` others from the
        seed."""
        fixed = sorted({0, self.num_trees - 1})
        rest = np.setdiff1d(np.arange(self.num_trees), fixed)
        drawn = np.random.default_rng([self.seed, 3]).choice(
            rest, min(int(self.traffic["check_trees_drawn"]), rest.size),
            replace=False)
        return sorted(fixed + [int(i) for i in drawn])

    def compare(self, collected: dict, reference,
                precision: str | None = None) -> dict:
        """The sampled trees of the last forest against the reference's;
        with a `precision`, against the reference with its histograms
        accumulated at that lower precision (the control)."""
        c, model = self.config, collected["model"]
        trees = self.trees()
        program = {k: getattr(model, k)[trees] for k in (
            "feature", "threshold", "is_leaf", "leaf_class", "node_counts")}
        numbers = reference.grow(
            self.X, self.y, collected["seed"], trees,
            max_bins=int(c["max_bins"]), max_depth=int(c["max_depth"]),
            impurity_kind=c["impurity"],
            subset_k=int(c["feature_subset_size"]),
            num_classes=int(c["num_classes"]), program=program,
            dtype=precision or "float32")
        numbers["trees_compared"] = len(trees)
        if self._test is None:
            got = model.predict_batch(self.X_test).astype(np.int64)
            want = reference.predict(
                {k: getattr(model, k) for k in (
                    "feature", "threshold", "is_leaf", "leaf_class")},
                self.X_test, max_depth=int(c["max_depth"]),
                num_classes=int(c["num_classes"]))
            self._test = {"test_pred_mismatch": int((got != want).sum()),
                          "test_accuracy": float((got == self.y_test).mean())}
        numbers.update(self._test)
        splits = ~model.is_leaf
        numbers["nonfinite"] = int(
            (~np.isfinite(model.node_counts)).sum()
            + (~np.isfinite(model.threshold[splits])).sum())
        return numbers

    def release(self) -> None:
        self.X = self.y = self.X_test = self.y_test = self.model = None

    def close(self) -> None:
        """Nothing of this job outlives the process's own state."""

    def work(self) -> dict:
        """What one forest's histograms need, from the configuration."""
        return counts_forest.forest_hist_work(self.config)
