"""CPU rehearsals of whole runs at the smoke size, faults planted under
the timed path, the refusal to run without a TPU, and a metric, a
traffic mix and a cell added by files and entries alone."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from lib.compiles import CompileCounter
from lib.measure import measure
from lib.registry import BENCH, ROOT, load_cell

SEED = 2**31 + 424242
# long enough at the smoke size on the CPU for requests to finish
CELLS = {"qwen3-1.7b.decode-batch": 3.0}


def _run(workload, fault=None, seconds=1.5, root=ROOT):
    return measure(load_cell(workload, root), SEED, seconds, False, t_start=time.time(),
                   smoke=True, counter=CompileCounter(), fault=fault)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_smoke_run_is_correct_with_nothing_compiled_in_the_window(workload):
    result, info, checks = _run(workload, seconds=CELLS[workload])
    assert result["correct"], result["check"]
    assert info["window_compiles"] == 0 and info["drain_compiles"] == 0
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) >= {"tokens_per_s", "setup_s"}
    assert list(result)[-1] == "check"
    assert checks and all(c.startswith("check ") for c in checks)


def _alter_tokens(engine):
    pick = engine._pick

    def altered(logits):
        return (pick(logits) + 1) % engine.model.spec.vocab

    engine._pick = altered


def _stale_state(engine):
    decode = engine.decode

    def unchanged(params, cache, tokens, pos):
        logits, _ = decode(params, cache, tokens, pos)
        return logits, cache

    engine.decode = unchanged


@pytest.mark.parametrize("fault", [_alter_tokens, _stale_state])
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_broken_timed_path_is_not_correct(workload, fault):
    result, _, _ = _run(workload, fault=fault)
    assert result["correct"] is False
    assert result["check"]["widest_gap"]["value"] > result["check"]["widest_gap"]["limit"]


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload",
                        "qwen3-1.7b.decode-batch", "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_with_only_the_benchmark_files_it_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.decode-batch",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


# a throwaway mix of each loop: the open one at a rate the smoke size serves
TINY = {"closed": {"loop": "closed", "clients": 2, "queue_limit": 4,
                   "prompt_lens": {"8": 1}, "gen_lens": {"4": 1}},
        "open": {"loop": "open", "rate_rps": 4.0, "queue_limit": 8,
                 "prompt_lens": {"8": 1, "16": 1}, "gen_lens": {"4": 1, "6": 1}}}


@pytest.mark.parametrize("loop", sorted(TINY))
def test_a_cell_traffic_and_metric_are_added_by_files_alone(tmp_path, loop):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    with open(tmp_path / "bench" / "traffic" / "tiny.json", "w") as f:
        json.dump(TINY[loop], f)
    with open(tmp_path / "bench" / "metrics" / "joins_seen.py", "w") as f:
        f.write("def read(record):\n    return record['spans']['join']['count']\n")
    bm["workloads"].append({"name": "qwen3-1.7b.tiny", "config": "qwen3-1.7b", "traffic": "tiny",
                            "chips": 1, "why": "throwaway"})
    bm["per_layer"].append({"name": "joins_seen", "unit": "joins", "better": "higher",
                            "source": "host_clock", "layer": "serving engine",
                            "moves": "tokens_per_s", "workloads": ["qwen3-1.7b.tiny"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bm, f)
    cell = load_cell("qwen3-1.7b.tiny", str(tmp_path))
    assert [m["name"] for m in cell.per_layer] == ["joins_seen"]
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s", "setup_s"]
    assert cell.reader("joins_seen").read({"spans": {"join": {"count": 3}}}) == 3
    result, info, _ = _run("qwen3-1.7b.tiny", seconds=1.5, root=str(tmp_path))
    assert result["correct"] and info["joins"] >= 2
    assert info["window_compiles"] == 0 and info["drain_compiles"] == 0


def test_the_sample_holds_the_longest_request():
    from lib.check import sample

    done = [{"id": i, "tokens": [0] * n} for i, n in enumerate([5, 300, 40, 40, 7, 300])]
    picked = sample(done, SEED)
    assert picked[0]["id"] == 1
    assert sum(len(r["tokens"]) for r in picked) >= 400
    assert [r["id"] for r in sample(done, SEED)] == [r["id"] for r in picked]
    assert np.all([r in done for r in picked])
