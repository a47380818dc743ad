"""Adding a cell takes new files and new entries only.

A copy of the benchmark gains a traffic mix (``traffic/short.json``:
short prompts at a high rate), a
per-layer metric (``metrics/sched.prefill_pump_share.py``) and a cell that
uses both, as entries in its ``BENCHMARK.json``; no existing file changes.
The copy's harness finds all three by name and runs the new cell on the
CPU at toy widths.
"""

import importlib.util
import json
import os
import shutil
import sys

import small

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))

SHORT = {"loop": "open", "rate_per_s": 6.0, "warmup_s": 0.5,
         "prompt_len": {"dist": "uniform", "min": 4, "max": 16},
         "output_len": {"dist": "uniform", "min": 2, "max": 8},
         "sampling": {"temperature": 0.0}}

METRIC = '''"""Scheduler: share of the window's pumps that ran a prefill."""


def read(run):
    pumps = [p for p in run.pumps if p.in_window]
    if not pumps:
        return None
    return 100.0 * sum(p.kind == "prefill" for p in pumps) / len(pumps)
'''


def test_new_mix_metric_and_cell(tmp_path):
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns(
        "__pycache__", "data"))
    (chip / "traffic" / "short.json").write_text(json.dumps(SHORT))
    (chip / "metrics" / "sched.prefill_pump_share.py").write_text(METRIC)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({
        "name": "smollm-360m.short", "config": "smollm-360m",
        "traffic": "short", "chips": 1, "why": "short prompts"})
    bench["per_layer"].append({
        "name": "sched.prefill_pump_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "scheduler",
        "moves": "ttft_p50_ms", "workloads": ["smollm-360m.short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = importlib.util.spec_from_file_location("run_copy",
                                                  chip / "run.py")
    copy = importlib.util.module_from_spec(spec)
    sys.modules["run_copy"] = copy
    spec.loader.exec_module(copy)
    bench = copy.load_json(str(tmp_path), "BENCHMARK.json")
    cell = next(w for w in bench["workloads"]
                if w["name"] == "smollm-360m.short")
    mix = copy.load_json(copy.HERE, "traffic", f"{cell['traffic']}.json")
    assert mix == SHORT
    reader = copy.load_metric("sched.prefill_pump_share")
    run = copy.Run(cell=cell, cfg={}, mix=mix, work=None, peaks=None,
                   slots=4, pool_pages=1, w0=0.0, w1=1.0, setup_s=0.0,
                   pumps=[copy.Pump(0, 0, k, 0, 0, 0, [], 0, True)
                          for k in ("prefill", "decode", "decode",
                                    "decode")],
                   served=[], admission_waits=[])
    assert copy.read_metrics(bench, run, trace=True)[
        "sched.prefill_pump_share"] == {"value": 25.0, "unit": "%"}
    cfg = small.config("smollm-360m")
    copy.cell_limit = lambda workload: 0.05   # the toy model's limit
    res = copy.run_cell(bench, cell, cfg, mix, 2**32 + 1, 2.0, False,
                        allow_cpu=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0


def test_seeds_offer_the_same_requests_at_the_same_times():
    """Only the token ids follow the seed: sizes, sampling and due times
    are the same for every seed, so the window's work does not move."""
    import loadgen
    a = loadgen.Traffic(SHORT, 9, 20.0, 256).open_loop()
    b = loadgen.Traffic(SHORT, 2**32 + 5, 20.0, 256).open_loop()
    assert [(len(s.prompt), s.max_new, s.temperature, s.due) for s in a] \
        == [(len(s.prompt), s.max_new, s.temperature, s.due) for s in b]
    assert [s.prompt for s in a] != [s.prompt for s in b]
    due = [s.due for s in a]
    assert due == sorted(due) and due[0] == 0.0
    assert len(due) == round(6.0 * 0.5) + round(6.0 * 20.0)
    assert loadgen.prompt_range(SHORT) == (4, 16)
