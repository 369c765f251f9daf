"""The harness end to end at test-only sizes: the loop, the metrics'
arithmetic, the correctness check and the result's format, on the fixture's
manifest (``fixture/BENCHMARK.json``) — which also shows that a cell, a
traffic mix, a configuration and a per-layer metric are added with files and
manifest entries only. Slow on a CPU: minutes."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.harness import cell as cell_mod
from benchmark.harness import loop, spec
from benchmark.harness import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture", "BENCHMARK.json")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(name, trace, tmp_path, seconds=1.0):
    cell = spec.load_cell(name, FIXTURE)
    result = cell_mod.run(cell, seed=3, seconds=seconds, trace=trace,
                          t_start=time.time(), out_dir=str(tmp_path))
    return cell, json.loads(json.dumps(result))  # as the last line carries it


@pytest.mark.parametrize("name, cohort", [
    ("tiny_cnn.tiny_sampled", 4), ("tiny_cnn.tiny_mesh", 8),
    ("tiny_lr.tiny_fast", 4)])
def test_untraced_run_reports_the_end_to_end_metrics(name, cohort, tmp_path):
    """The last cell's configuration has another model and another data
    generator, both found by name: files and manifest entries only."""
    cell, result = run(name, False, tmp_path)
    assert set(result) == RESULT_KEYS  # exactly: the driver refuses more
    assert result["correct"] is True and result["failed"] == 0
    rounds, left = divmod(result["attempted"], cohort)
    # the window ends on the block after a whole evaluation interval
    assert left == 0 and rounds % cell.traffic["eval_every"] == 1
    metrics = result["metrics"]
    assert set(metrics) == {"rounds_per_s", "eval_s", "setup_s"}
    assert all(m["value"] > 0 for m in metrics.values())
    assert metrics["rounds_per_s"]["unit"] == "rounds/s"
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 4
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}


def test_traced_run_reports_the_per_layer_metrics(tmp_path):
    cell, result = run("tiny_cnn.tiny_resident", True, tmp_path)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    # no compilation inside the window; the resident cohort is never packed
    assert metrics["recompiles"]["value"] == 0.0
    assert metrics["pack_ms"]["value"] == 0.0
    assert metrics["dispatch_ms"]["value"] > 0.0
    assert 0.0 < metrics["padded_row_share"]["value"] < 100.0
    assert metrics["mfu"]["value"] > 0.0
    # the slice ran (13 rounds at least), so round 16 may or may not be
    # there; a CPU has no device plane, so the device's metrics are left out
    assert "device_idle_share" not in metrics
    # a metric listed for another cell only is not reported here
    assert "prefetch_misses" not in metrics
    assert result["device"]["busy_s"] == 0.0
    assert result["device"]["window_s"] > 0.0
    assert result["breakdown"] == {"device_ops": [], "idle_gaps": [
        [n, s] for n, s in result["breakdown"]["idle_gaps"]]}
    assert os.listdir(tmp_path / "trace") == [cell.name + ".json.gz"]
    saved = tr.load(str(tmp_path / "trace" / (cell.name + ".json.gz")))
    window = tr.window_of(saved)
    assert len(tr.spans_in(saved, "bench.run_round", window)
               ) >= loop.TRACE_ROUNDS


def test_a_wrong_count_or_loss_fails_the_rounds(tmp_path, monkeypatch):
    """A driver that trains one row too few must not pass (b)."""
    real = loop.measure

    def broken(*args, **kwargs):
        window = real(*args, **kwargs)
        window.stats[1]["count"] -= 1.0
        return window

    monkeypatch.setattr(loop, "measure", broken)
    _, result = run("tiny_cnn.tiny_sampled", False, tmp_path)
    assert result["correct"] is False
    assert result["failed"] == 4  # one round's cohort


def test_readers_on_the_recorded_trace():
    """The trace readers' arithmetic on the chip's recording, through the
    real metric files of a real cell."""
    cell = spec.load_cell("fedcifar100_resnet18gn.dense")
    recorded = tr.load(os.path.join(HERE, "fixture", "trace_v5e.json.gz"))
    window = loop.Window(rounds=20, wall_s=10.0, eval_walls=[0.5, 0.5],
                         phases={"dispatch": 0.2, "pack": 0.4, "upload": 0.6},
                         stats=[{"loss_sum": 6.0, "count": 2.0}] * 17)
    ctx = cell_mod.Context(
        cell=cell, window=window,
        counts={"recompiles": 0, "real_rows": 128000.0,
                "packed_rows": 128000.0},
        flops_per_row=2.0e9, peak={"bf16_flops_per_s": 197e12,
                                   "hbm_bytes_per_s": 819e9},
        # the recording is of the 3x3-stem model at a cohort of 80
        params=11220132, cohort_per_chip=80, memory_peak_bytes=2 ** 33,
        host_rss_bytes=2 ** 31, trace=recorded,
        trace_window=tr.window_of(recorded), trace_rounds=2)
    got = {k: v["value"] for k, v in cell_mod.read_metrics(
        ctx, cell.per_layer, lambda m: None).items()}
    assert got["dispatch_ms"] == pytest.approx(10.0)
    assert got["pack_ms"] == pytest.approx(50.0)
    assert got["prefetch_wait_ms"] == 0.0
    assert got["padded_row_share"] == 0.0
    assert got["loss_at_round_16"] == pytest.approx(3.0)
    assert got["mfu"] == pytest.approx(
        100 * 2.0e9 * 128000 / 9.0 / 197e12)
    assert got["peak_hbm_gib"] == 8.0 and got["host_rss_gib"] == 2.0
    assert got["agg_kernel_ms"] == pytest.approx(5.004, rel=1e-3)
    assert got["train_device_ms"] == pytest.approx(
        1e3 * (0.448532 - 0.010009) / 2, rel=1e-4)
    least = 4.0 * (80 * 11220132 + 11220132 + 80) / 819e9
    assert got["agg_kernel_roofline"] == pytest.approx(
        100 * least / 5.004e-3, rel=1e-3)
    assert got["device_idle_share"] == pytest.approx(
        100 * (1 - 0.51272808 / 0.520942584), rel=1e-5)
    assert "allreduce_exposed_share" not in got  # not this cell's


def cli(cwd, workload):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_run_py_refuses_to_report_without_a_tpu():
    done = cli(spec.ROOT, "femnist_cnn.resident")
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "needs 1 tpu chip" in done.stderr


def test_run_py_fails_beside_nothing_but_the_benchmark(tmp_path):
    import shutil

    shutil.copy(spec.MANIFEST, tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("_out", "__pycache__"))
    done = cli(tmp_path, "femnist_cnn.resident")
    assert done.returncode != 0
    assert done.stdout.strip() == ""
