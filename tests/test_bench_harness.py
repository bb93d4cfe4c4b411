"""Suite-speed guards for the bench.py measurement harness: the parity
job and the pooled MLlib-shaped sweep are artifact-producing code paths
(MATH_PARITY.json, the north-star denominator) that no other test
imports. Toy sizes only — the committed artifacts use the real ones."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench


class TestMllibHalfSweep:
    def test_pooled_sweep_is_bit_identical_to_serial(self):
        """The thread-pooled baseline writes disjoint entity ranges, so
        n-core results must equal 1-core results EXACTLY — any drift
        means the north-star denominator depends on core count."""
        n_users, n_items, nnz, rank, lam = 300, 120, 9_000, 16, 0.05
        ui, ii, vv = bench.synthetic_ml20m(n_users, n_items, nnz, seed=3)
        rng = np.random.default_rng(7)
        U0 = np.abs(rng.standard_normal((n_users, rank))) / np.sqrt(rank)
        V = np.abs(rng.standard_normal((n_items, rank))) / np.sqrt(rank)
        solve = bench.mllib_solver(rank)

        out_serial, out_pooled = U0.copy(), U0.copy()
        bench.mllib_half_sweep(ui, ii, vv, n_users, V, out_serial,
                               rank, lam, solve, n_workers=1)
        bench.mllib_half_sweep(ui, ii, vv, n_users, V, out_pooled,
                               rank, lam, solve, n_workers=4)
        assert np.array_equal(out_serial, out_pooled)


class TestMathParityHarness:
    def test_toy_scale_parity_artifact(self, tmp_path):
        """End-to-end smoke of the --math-parity job: identical data,
        both trainers, held-out split, artifact written, parity holds.
        (At toy scale the two paths track each other just as they do at
        rank 200 — see the committed MATH_PARITY.json for the real run.)

        rank must be >= 16 so the dualcap variant's scaled-down cap
        (rank // 2 = 8) actually BINDS a dual-route solve: the Woodbury
        branch needs K < rank and the bucket ladder's minimum K is 8, so
        at the old rank 8 the dual route never fired and a regressed cap
        passed unnoticed (ADVICE round-5 item 1)."""
        out = tmp_path / "parity.json"
        rc = bench.math_parity_report(
            out_path=str(out), iters=2,
            n_users=400, n_items=150, nnz=20_000, rank=16)
        d = json.loads(out.read_text())
        assert d["artifact"] == "rank200_math_parity"
        assert set(d["results"]) == {"mllib_shaped_float64",
                                     "als_train_f32_tables",
                                     "als_train_bf16_tables",
                                     "als_train_dualcap16_cg"}
        assert d["workload"]["nnz_train"] + d["workload"]["nnz_heldout"] \
            == 20_000
        for v in d["results"].values():
            assert v["heldout_rmse"] > 0
        # the held-out RMSEs must be in the same ballpark even at toy
        # scale; rc encodes the tolerance verdict
        assert rc == 0 and d["parity_ok"] is True


class TestNoChipNoNumber:
    """bench.py measures the chip or nothing: no CPU re-exec, no banked
    artifact to cite, no default peak for a device it does not know."""

    def test_no_chip_exits_nonzero_and_prints_no_metric(self):
        """`python bench.py` on a machine without a TPU ends non-zero
        with the reason on stderr and NOTHING on stdout — in particular
        no line carrying the chip metric's name."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        p = subprocess.run(
            [sys.executable, os.path.join(repo, "bench.py")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=300)
        assert p.returncode != 0
        assert p.stdout.strip() == ""
        assert "no chip" in p.stderr and "platform=cpu" in p.stderr

    def test_unknown_device_kind_raises(self, monkeypatch):
        """A device_kind missing from the peak table is an error (it used
        to be priced silently at 919 TFLOP/s / 819 GB/s); prefixes
        resolve longest-first, so a v5e is never priced as a v5p; the
        CPU is a named entry for --tiny runs."""
        import jax

        def as_kind(kind):
            class _Dev:
                device_kind = kind
            monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])

        as_kind("TPU v9 ultra")
        with pytest.raises(KeyError, match="TPU v9 ultra"):
            bench.device_peak_flops()
        with pytest.raises(KeyError):
            bench.device_hbm_bw()
        as_kind("TPU v5 lite")
        assert (bench.device_peak_flops(), bench.device_hbm_bw()) == (
            197e12, 819e9)
        as_kind("TPU v5")
        assert bench.device_peak_flops() == 459e12
        as_kind("cpu")
        assert bench.DEVICE_PEAKS["cpu"] == (bench.device_peak_flops(),
                                             bench.device_hbm_bw())
