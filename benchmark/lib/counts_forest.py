"""What growing a forest needs, from the configuration alone: per split
level (depth 0 .. max_depth - 1) the class histogram has to read each
example's bins and label once (n (F + 1) bytes at a byte each) and each
(tree, example) pair's Poisson count and node id, and write the node id
(3 T n bytes at a byte each), and to add T n F weights into its cells.
Nothing here reads the program's blocking or dtypes."""

from __future__ import annotations


def hist_level_bytes(config: dict) -> float:
    n, F = float(config["n_train"]), float(config["n_features"])
    return n * (F + 1) + 3.0 * float(config["num_trees"]) * n


def hist_level_ops(config: dict) -> float:
    return (float(config["num_trees"]) * float(config["n_train"])
            * float(config["n_features"]))


def forest_hist_work(config: dict) -> dict:
    """Bytes and operations of one forest's histograms."""
    levels = int(config["max_depth"])
    return {"hist_bytes": levels * hist_level_bytes(config),
            "hist_ops": levels * hist_level_ops(config),
            "levels": levels}
