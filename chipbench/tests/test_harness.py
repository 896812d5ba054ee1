"""The harness runs each cell end to end at smoke size on the CPU (the
kernels in the Pallas interpreter), and the command refuses a CPU."""
import json

import jax
import pytest

from chipbench import common, run
from chipbench.tests import cells

CELLS = ["opt-125m.train", "qwen3-0.6b.serve-steady",
         "qwen3-0.6b.serve-longctx"]
PEAKS = common.load_json(f"{common.BENCH}/peaks.json")["TPU v5 lite"]


def test_command_refuses_a_cpu(capsys):
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        common.peaks("cpu")


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end(name, capsys):
    cell, args = cells.cell(name), cells.args(name, seed=2**31 + 17)
    devices = jax.devices()
    rec, out = run.execute(cell, args, devices)
    # the comparison with the reference passes; on the CPU the kernels ran
    # in the interpreter, which the route check refuses
    assert out["ok"], out["checks"]
    assert [p for p in out["problems"] if "interpret" not in p] == []
    assert out["attempted"] > 0 and out["failed"] == 0
    run.finish(cell, args, devices, PEAKS, rec, out)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is False
    want = {m["name"] for m in cell["end_to_end"]}
    assert set(line["metrics"]) == want
    for v in line["metrics"].values():
        assert v["value"] > 0


def test_dense_ff_cell_is_correct(capsys):
    """``opt-125m.train`` with the configuration's ``program.linear`` set to
    ``dense``: no harness file knows it, yet the run is correct (no DYAD
    kernel runs, so on the CPU no kernel runs in the interpreter)."""
    from repro import obs

    obs.reset_route_counts()    # a run's process starts with none
    name = "opt-125m.train"
    cell, args = cells.cell(name, linear="dense"), cells.args(name, seed=7)
    devices = jax.devices()
    rec, out = run.execute(cell, args, devices)
    assert out["ok"] and out["problems"] == [], out
    assert rec["model"].train_dyad_mm_calls(rec["m"], 2, 32) == []
    run.finish(cell, args, devices, PEAKS, rec, out)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
