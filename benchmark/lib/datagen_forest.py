"""Labelled rows of HIGGS's shape from the seed, nothing downloaded (UCI
"HIGGS", Baldi, Sadowski & Whiteson, Nature Communications 5:4308, 2014):
28 real features, 21 low-level kinematic columns (lepton pT, eta, phi;
missing-energy magnitude and phi; four jets' pT, eta, phi and b-tag) and
7 high-level masses, in the data set's column order; a binary label. The
last `n_test` rows are the test set, as HIGGS's last 500,000 are.

What the configuration assumes (`assumed` in its file): transverse
momenta and masses lognormal, pseudorapidities normal, azimuths uniform
on (-pi, pi], each b-tag one of three values as HIGGS's are (so that
quantile edges collide); the label a fixed nonlinear function of a
handful of features plus noise, cut at the quantile that leaves
`positive_share` of the rows positive, so that trees keep finding gains
down to the configuration's depth."""

from __future__ import annotations

import numpy as np

# the columns of HIGGS, in order
COLUMNS = (["lepton_pt", "lepton_eta", "lepton_phi", "met", "met_phi"]
           + [f"jet{j}_{q}" for j in range(1, 5)
              for q in ("pt", "eta", "phi", "btag")]
           + ["m_jj", "m_jjj", "m_lv", "m_jlv", "m_bb", "m_wbb", "m_wwbb"])
BTAG_VALUES = np.array([0.0, 1.107, 2.215], np.float32)


def _lognormal(rng, n, sigma):
    return np.exp(sigma * rng.standard_normal(n, np.float32)).astype(
        np.float32)


def rows(config: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """X [n_train + n_test, 28] float32 and y [same] int32 of the seed."""
    n = int(config["n_train"]) + int(config["n_test"])
    a = config["assumed"]
    rng = np.random.default_rng([int(seed), 1])
    X = np.empty((n, len(COLUMNS)), np.float32)
    for f, name in enumerate(COLUMNS):
        if name.endswith("_pt") or name == "met":
            X[:, f] = _lognormal(rng, n, a["pt_sigma"])
        elif name.startswith("m_"):
            X[:, f] = _lognormal(rng, n, a["mass_sigma"])
        elif name.endswith("_eta"):
            X[:, f] = a["eta_sigma"] * rng.standard_normal(n, np.float32)
        elif name.endswith("_phi"):
            X[:, f] = np.float32(np.pi) * (
                2.0 * rng.random(n, np.float32) - 1.0)
        else:
            X[:, f] = BTAG_VALUES[rng.integers(0, 3, n, dtype=np.int8)]
    col = {name: X[:, f] for f, name in enumerate(COLUMNS)}
    # signal-like rows: two b-tagged jets with a b-b mass near one value, a
    # heavy W-W-b-b system, a lepton close to the missing energy's azimuth
    btags = col["jet1_btag"] + col["jet2_btag"]
    z = (1.5 * (btags > 2.0) * np.exp(-4.0 * np.log(col["m_bb"]) ** 2)
         + 0.8 * np.tanh(np.log(col["m_wwbb"]) - 0.3)
         + 0.5 * np.cos(col["lepton_phi"] - col["met_phi"])
         * (col["lepton_pt"] > 1.0)
         - 0.4 * np.abs(col["lepton_eta"])
         + 0.3 * np.log(col["m_jlv"]) * np.sign(col["jet1_eta"])
         + 0.25 * (col["jet3_btag"] > 0) * np.log(col["m_jjj"])
         + a["noise"] * rng.standard_normal(n, np.float32))
    cut = np.quantile(z, 1.0 - float(a["positive_share"]))
    return X, (z > cut).astype(np.int32)
