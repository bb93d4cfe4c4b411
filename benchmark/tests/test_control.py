"""Each configuration's control, at a size a test run can hold: the
configuration's own file with its counts cut, its plain reference put in the
program's place at the nearest precision below the one the configuration
states (float8_e4m3fn operands for bfloat16), held to the limits of the
configuration's own cells. It has to come out as not correct, and the
program, as the configuration states it, as correct."""

import os
import shutil

import pytest

import tinytree
from benchmark import prove
from benchmark.lib.spec import Spec

CELLS = [("rec-amazonbooks14-r200", "serve-tiny",
          "rec-amazonbooks14-r200.serve-uniform"),
         ("rec-goodreads-r200", "train", "rec-goodreads-r200.train")]


@pytest.mark.parametrize("config,traffic,cell", CELLS,
                         ids=[c[2] for c in CELLS])
def test_control_is_not_correct(tmp_path, config, traffic, cell):
    tree = tinytree.build(str(tmp_path), base=config)
    # the tiny cell is held to the limits of the configuration's own cell
    shutil.copy(os.path.join(tinytree.REPO, "benchmark", "limits",
                             cell + ".json"),
                os.path.join(tree, "benchmark", "limits",
                             f"tiny-r32.{traffic}.json"))
    spec = Spec(tree)
    precision = spec.cell("tiny-r32." + traffic)["config"][
        "control_precision"]
    assert precision == "float8_e4m3fn"
    r = prove.prove_seed(spec, "tiny-r32." + traffic, 2**31 + 5, 1.0,
                         controls=(precision,), need_chip=False)
    # the harness's own verdicts, by compare.decide on the cell's limits
    assert r["correct"] is True, r["compared"]
    assert r["control_correct:float8_e4m3fn"] is False, \
        r["control:float8_e4m3fn"]
