"""The rest of a run with the timed path broken underneath: `correct` has to
come out false for each fault a cell can have. (One chip: there is no
exchange between chips to leave out.)"""

import json

import numpy as np
import pytest

import tinytree
from benchmark import run
from benchmark.lib.spec import Spec


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tinytree.build(str(tmp_path_factory.mktemp("tree")))


def run_cell(tree, workload, seconds=1.0):
    return run.run_cell(Spec(tree), workload, 2**31 + 11, seconds, False,
                        need_chip=False)


def test_sound_runs_are_correct(tree):
    assert run_cell(tree, "tiny-r32.train")["correct"] is True
    assert run_cell(tree, "tiny-r32.serve-tiny")["correct"] is True


def test_train_step_that_returns_its_state_unchanged(tree, monkeypatch):
    from predictionio_tpu.ops import als
    monkeypatch.setattr(als, "_run_side",
                        lambda groups, factors, *a, **k: factors)
    r = run_cell(tree, "tiny-r32.train")
    assert r["correct"] is False
    assert r["numbers"]["user_err_max"] > 0.5     # a row that has not moved


def test_train_half_of_each_batch_left_out(tree, monkeypatch):
    from predictionio_tpu.ops import als
    real = als._run_side

    def half(groups, factors, *a, **k):
        cut = tuple((rows.at[:, ::2].set(-1), idx, val, mask)
                    for rows, idx, val, mask in groups)
        return real(cut, factors, *a, **k)

    monkeypatch.setattr(als, "_run_side", half)
    r = run_cell(tree, "tiny-r32.train")
    assert r["correct"] is False
    c = r["compared"]
    assert c["user_err_max"]["value"] > c["user_err_max"]["limit"]


def test_train_fault_that_first_appears_inside_the_window(tree, monkeypatch):
    """Set-up's two half-sweeps are sound, so the comparison from the
    seed's tables passes; from the window's first call on, half of each
    batch is left out. The window's last item half-sweep, held against the
    reference's solve from the user rows it read, has to fail."""
    from predictionio_tpu.ops import als
    real = als._run_side
    calls = {"n": 0}

    def late_half(groups, factors, *a, **k):
        calls["n"] += 1
        if calls["n"] > 2:
            groups = tuple((rows.at[:, ::2].set(-1), idx, val, mask)
                           for rows, idx, val, mask in groups)
        return real(groups, factors, *a, **k)

    monkeypatch.setattr(als, "_run_side", late_half)
    r = run_cell(tree, "tiny-r32.train")
    c = r["compared"]
    assert c["user_err_max"]["value"] <= c["user_err_max"]["limit"]
    assert c["item_err_max"]["value"] <= c["item_err_max"]["limit"]
    assert c["item_end_err_max"]["value"] > c["item_end_err_max"]["limit"]
    assert r["correct"] is False


def _alter_served(monkeypatch, alter):
    from predictionio_tpu.ops import als
    real = als.users_topk_serve_begin

    def begin(model, user_ixs, k):
        finish = real(model, alter.get("users", lambda u: u)(
            np.asarray(user_ixs)), k)

        def altered():
            scores, idx = finish()
            return alter.get("answer", lambda s, i: (s, i))(scores, idx)
        return altered

    monkeypatch.setattr(als, "users_topk_serve_begin", begin)


def test_served_ids_swapped_where_they_are_produced(tree, monkeypatch):
    def swap(scores, idx):
        idx = np.array(idx)
        idx[:, [0, 9]] = idx[:, [9, 0]]
        return scores, idx
    _alter_served(monkeypatch, {"answer": swap})
    r = run_cell(tree, "tiny-r32.serve-tiny")
    assert r["correct"] is False


def test_served_row_of_another_entity(tree, monkeypatch):
    _alter_served(monkeypatch, {
        "users": lambda u: (u + 1) % 6000})
    r = run_cell(tree, "tiny-r32.serve-tiny")
    assert r["correct"] is False
    c = r["compared"]
    assert c["rank_gap_max"]["value"] > c["rank_gap_max"]["limit"]


def test_unanswered_requests_are_not_correct(tree, monkeypatch):
    """An answer that never comes is for `correct`, and misses the tail."""
    from benchmark.lib import loadgen
    from predictionio_tpu.models import recommendation as R
    real = R.ALSAlgorithm.batch_predict_begin
    count = loadgen.request_count
    armed = {"on": False, "n": 0}

    def arm(mix, seconds):       # the window's first call in this process
        armed["on"] = True
        return count(mix, seconds)

    def failing(self, model, queries):
        if armed["on"]:
            armed["n"] += 1
            if armed["n"] % 3 == 0:
                raise RuntimeError("planted")
        return real(self, model, queries)

    monkeypatch.setattr(loadgen, "request_count", arm)
    monkeypatch.setattr(R.ALSAlgorithm, "batch_predict_begin", failing)
    r = run_cell(tree, "tiny-r32.serve-tiny")
    assert r["failed"] > 0 and r["correct"] is False
    assert json.dumps(run._finite(r))     # an infinite tail still prints
