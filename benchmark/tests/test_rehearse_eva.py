"""``evabyte_l8.rawlog_backlog`` rehearsed on the CPU (window 64, chunk 4:
windows close in prompts AND in decoding) ends in a contract-shaped line, and
the judge refuses a control applied to the same process. Slow (a minute a
run): tiny sizes, no chip; the numbers in the lines are not measurements."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "evabyte_l8.rawlog_backlog"
ARGS = ["--workload", CELL, "--seed", "3000000019", "--seconds", "4", "--rehearse"]


def _line(cmd, trace):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run([sys.executable, *cmd, *ARGS, "--trace", str(trace)],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_line(trace):
    line = _line(["benchmark/run.py"], trace)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    verdict = line["detail"]["reference"]
    assert verdict["rows_closing_while_decoding"] > 0
    if trace:
        for name in ("eva_summary_rows_pct", "eva_window_pages_share_of_cache_pct"):
            assert 0 < line["metrics"][name]["value"] < 100
        assert line["metrics"]["compiles_in_window"]["value"] == 0.0
    else:
        assert line["metrics"]["tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("control", ["no_mu", "sliding_reference",
                                     "bf16_residual", "bf16_statistics"])
def test_judge_refuses_a_control_in_the_cell(control):
    line = _line(["tools/eva_control.py", control], 0)
    assert line["correct"] is False and line["failed"] == 0
    verdict = line["detail"]["reference"]
    assert verdict["ok"] is False
    # what tokens cannot show is held by a rule of its own
    assert (verdict["residual_carried_as"] == ["float32"]) == (control != "bf16_residual")
    if control == "bf16_statistics":
        assert verdict["summary_values_off"] > verdict["summary_values_off_limit"]
