"""Whole runs of a cell on the CPU at width 32, past the look for a
chip: sound, and with the answers altered where they are produced."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def cpu_run(cell, fault=None, seconds=1.5, seed=2**31 + 3, control=0):
    args = run.parse_args(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", "0",
                           "--control", str(control)])
    return run.run(args, require_chip=False,
                   model_overrides={"hidden": 32}, fault=fault)


def alter_one_answer(svc):
    """Every bin's first prediction comes back 1% high."""
    engines = list(getattr(svc.engine, "replicas", None) or [svc.engine])
    for e in engines:
        orig = e.run_bin

        def run_bin(chunk, *a, _orig=orig, **kw):
            out = np.array(_orig(chunk, *a, **kw))
            out[0] = out[0] * 1.01 + 0.01
            return out
        e.run_bin = run_bin


@pytest.mark.parametrize("cell", ["sage-zoo-saturate", "gcn-zoo-saturate"])
def test_sound_run_is_correct(cell):
    res = cpu_run(cell)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_log_gap"]["value"] <= \
        res["checks"]["max_log_gap"]["limit"]
    assert "setup_s" in res["metrics"]


def test_altered_answer_is_not_correct():
    res = cpu_run("sage-zoo-saturate", fault=alter_one_answer)
    assert res["correct"] is False
    assert res["checks"]["max_log_gap"]["value"] > \
        res["checks"]["max_log_gap"]["limit"]


def test_control_in_the_programs_place_is_not_correct():
    res = cpu_run("sage-zoo-saturate", control=1)
    assert res["correct"] is False
    assert res["failed"] == 0
    assert res["checks"]["max_log_gap"]["value"] > \
        res["checks"]["max_log_gap"]["limit"]


def test_no_chip_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"),
                        "--workload", "sage-zoo-saturate", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env,
                       cwd=BENCH.parent, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_result_line_is_json_with_the_contract_keys():
    res = cpu_run("gcn-zoo-saturate", seconds=1.0)
    line = json.loads(json.dumps(res))
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}


def test_sweep_reports_a_rate_row_for_an_open_loop():
    import sweep
    sess = run.Session({"name": "sweep", "config": "pmgns-sage-512",
                        "traffic": "zoo-saturate", "chips": 1}, 5,
                       require_chip=False, model_overrides={"hidden": 32})
    open_loop = dict(sess.traffic, loop="open", arrivals="poisson",
                     rate_per_s=12.0)
    try:
        w = sess.window(1.0, stream=10, traffic=open_loop)
    finally:
        sess.close()
    row = sweep.rate_row(w, 12.0)
    assert row["offered_per_s"] == pytest.approx(12.0)
    assert row["failed"] == 0 and row["p95_ms"] > 0
