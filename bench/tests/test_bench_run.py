"""A whole run of a cell at a small size on the CPU, past the harness's
look for a chip: sound, it is correct; with the timed path broken
underneath, ``correct`` comes out false."""

import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from harness import faults
from harness.cell import reader_path, run
from harness.model import load_config
from harness.traffic import load_mix

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TINY = BENCH / "tests" / "fixtures" / "m3vit_tiny.json"
CELL = "m3vit.frames.sat"


def small_run(plant=None, trace=False, tmp_path=None, seconds=1.5,
              cell=CELL, traffic="frames.sat"):
    from dataclasses import replace

    mix = replace(load_mix(BENCH / "traffic" / f"{traffic}.json"),
                  frame_pool=8)
    return run(BENCH, cell, 2**32 + 9, seconds, trace, time.perf_counter(),
               require_tpu=False, cfg=load_config(TINY), mix=mix,
               plant=plant, trace_dir=tmp_path)


def test_sound_run_is_correct(tmp_path):
    out = small_run(trace=True, tmp_path=tmp_path)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 8
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"batch_occupancy", "forward_ms.sat",
                                   "expert_hit_rate", "paged_mb_per_pred"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_open_loop_run_reports_its_tails():
    out = small_run(cell="m3vit.frames.rate", traffic="frames.rate",
                    seconds=2.0)
    assert out["correct"] is True, out["checks"]
    # 6.4 frames/s for 2 s, two tasks each
    assert out["attempted"] == 2 * round(6.4 * 2.0)
    assert set(out["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                   "setup_s"}
    m = out["metrics"]
    assert m["latency_p50_ms"]["value"] <= m["latency_p95_ms"]["value"]


@pytest.mark.parametrize("fault", ["stale", "half_batch", "altered",
                                   "stale_slot"])
def test_broken_timed_path_is_not_correct(fault):
    name = {"stale": "stale_answer"}.get(fault, fault)
    out = small_run(plant=lambda s: faults.plant(s.backend.server, name))
    assert out["correct"] is False, (fault, out["checks"])
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_without_a_tpu_no_result_is_printed():
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_benchmark_alone_is_not_a_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_benchmark_file_names_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in spec["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert reader_path(BENCH, m["name"]).is_file()


def test_a_metric_without_cells_is_read_where_its_end_to_end_is(tmp_path):
    from harness.cell import load_cell

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "queue_wait_ms.any", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "scheduler", "moves": "setup_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench").symlink_to(BENCH)
    for w in spec["workloads"]:
        *_, e2e, per_layer = load_cell(tmp_path / "bench", w["name"])
        names = {m["name"] for m in per_layer}
        assert "queue_wait_ms.any" in names
        assert names <= {m["name"] for m in spec["per_layer"]
                         if w["name"] in m.get("workloads", [w["name"]])}
    assert reader_path(BENCH, "queue_wait_ms.any").name == "queue_wait_ms.py"
