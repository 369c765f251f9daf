"""Bench trend ledger (fedml_tpu/obs/trend.py) + bench.py wiring.

The guardrail contract, unit-by-unit: the first-ever row of a key
passes, a planted 2x rounds/sec regression is caught, the thresholds
are flag-tunable, host-fingerprint keying keeps a laptop's trajectory
from gating a chip's, the median window bounds history, and a torn
final line (a killed writer) never poisons the reader. bench.py's
extraction (`_trend_metrics`) and verdict (`--check-trend`) are
exercised against the real ledger format, and the CLI gate's exit
codes are pinned.
"""

import json
import subprocess
import sys

import pytest

from fedml_tpu.obs import trend


def _row(stage="s", rps=None, bpr=None, host="cpu-smoke"):
    metrics = {}
    if rps is not None:
        metrics["rounds_per_sec"] = rps
    if bpr is not None:
        metrics["bytes_per_round"] = bpr
    return trend.make_row(stage, metrics, host_tag=host)


class TestCheckRow:
    def test_first_row_always_passes(self):
        assert trend.check_row([], _row(rps=1.0)) == []
        assert trend.check_row([], _row(rps=0.001, bpr=1e9)) == []

    def test_planted_2x_rps_regression_caught(self):
        history = [_row(rps=100.0) for _ in range(5)]
        assert trend.check_row(history, _row(rps=50.0))  # 2x drop: fail
        # exactly at the 30% floor passes (70 vs median 100)
        assert trend.check_row(history, _row(rps=70.0)) == []
        assert trend.check_row(history, _row(rps=69.0))  # just under

    def test_bytes_regression_caught(self):
        history = [_row(bpr=1000.0) for _ in range(5)]
        assert trend.check_row(history, _row(bpr=1600.0))  # >1.5x: fail
        assert trend.check_row(history, _row(bpr=1500.0)) == []

    def test_thresholds_are_tunable(self):
        history = [_row(rps=100.0), _row(bpr=1000.0, rps=100.0)]
        # a 10% ceiling turns a 15% drop into a regression...
        assert trend.check_row(history, _row(rps=85.0),
                               max_rps_drop=0.10)
        # ...and a loose 60% ceiling forgives a 2x drop
        assert trend.check_row(history, _row(rps=50.0),
                               max_rps_drop=0.60) == []
        assert trend.check_row(history, _row(bpr=1900.0),
                               max_bytes_x=2.0) == []
        assert trend.check_row(history, _row(bpr=1100.0),
                               max_bytes_x=1.05)

    def test_host_fingerprint_keys_do_not_mix(self):
        # a fast chip history must NOT gate the cpu-smoke row (and the
        # fingerprints really differ by host tag)
        chip = [_row(rps=300.0, host="tpu:v5") for _ in range(5)]
        smoke = _row(rps=2.0, host="cpu-smoke")
        assert smoke["host_fingerprint"] != chip[0]["host_fingerprint"]
        assert trend.check_row(chip, smoke) == []
        # same-key history does gate
        assert trend.check_row(chip, _row(rps=100.0, host="tpu:v5"))

    def test_stage_keys_do_not_mix(self):
        other = [_row(stage="a", rps=100.0) for _ in range(5)]
        assert trend.check_row(other, _row(stage="b", rps=1.0)) == []

    def test_median_window_bounds_history(self):
        # 10 ancient rows at 1000, then 8 recent at 100: window=8 means
        # the median is 100 and a 90 passes; window=18 drags the median
        # to ~1000 and 90 fails
        history = [_row(rps=1000.0) for _ in range(10)] \
            + [_row(rps=100.0) for _ in range(8)]
        assert trend.check_row(history, _row(rps=90.0), window=8) == []
        assert trend.check_row(history, _row(rps=90.0), window=18)

    def test_median_not_poisoned_by_one_outlier(self):
        # one wedged capture at 1 must not drag the median down
        history = [_row(rps=100.0)] * 4 + [_row(rps=1.0)]
        assert trend.check_row(history, _row(rps=80.0)) == []


class TestLedgerIo:
    def test_append_and_load_roundtrip(self, tmp_path):
        path = str(tmp_path / "trends.jsonl")
        r1, r2 = _row(rps=1.0), _row(rps=2.0)
        trend.append_row(path, r1)
        trend.append_row(path, r2)
        rows = trend.load_rows(path)
        assert [r["rounds_per_sec"] for r in rows] == [1.0, 2.0]
        assert all(r["schema_version"] == trend.TREND_SCHEMA_VERSION
                   for r in rows)

    def test_torn_final_line_skipped(self, tmp_path):
        path = tmp_path / "trends.jsonl"
        trend.append_row(str(path), _row(rps=1.0))
        with open(path, "a") as f:
            f.write('{"stage": "s", "rounds_per')  # killed writer
        rows = trend.load_rows(str(path))
        assert len(rows) == 1

    def test_append_never_raises(self, tmp_path):
        # unwritable target: the observer contract — warn, drop, return
        trend.append_row(str(tmp_path), _row(rps=1.0))  # path IS a dir

    def test_check_latest_gates_newest_row_per_key(self, tmp_path):
        path = str(tmp_path / "trends.jsonl")
        for _ in range(4):
            trend.append_row(path, _row(stage="good", rps=100.0))
        trend.append_row(path, _row(stage="good", rps=99.0))
        for _ in range(4):
            trend.append_row(path, _row(stage="bad", rps=100.0))
        trend.append_row(path, _row(stage="bad", rps=10.0))
        problems = trend.check_latest(path)
        assert len(problems) == 1 and "bad" in problems[0]
        assert trend.check_latest(path, stage="good") == []

    def test_summarize_ledger(self, tmp_path):
        path = str(tmp_path / "trends.jsonl")
        for rps in (1.0, 2.0, 3.0):
            trend.append_row(path, _row(rps=rps, bpr=10.0))
        (summary,) = trend.summarize_ledger(path)
        assert summary["rows"] == 3
        assert summary["rounds_per_sec_median"] == 2.0
        assert summary["rounds_per_sec_latest"] == 3.0
        assert summary["bytes_per_round_latest"] == 10.0


class TestBenchWiring:
    """bench.py's extraction + verdict against the real row shapes."""

    def test_trend_metrics_top_level(self):
        import bench
        assert bench._trend_metrics({"rounds_per_sec": 2.5}) == {
            "rounds_per_sec": 2.5}

    def test_trend_metrics_nested_legs(self):
        import bench
        # the compression stage gates on the compressed leg
        row = {"policy_none": {"rounds_per_sec": 3.0,
                               "bytes_per_round_total": 9000.0},
               "policy_topk_ef_int8": {"rounds_per_sec": 2.0,
                                       "bytes_per_round_total": 1200.0}}
        assert bench._trend_metrics(row) == {"rounds_per_sec": 2.0,
                                             "bytes_per_round": 1200.0}
        # the chaos stage gates on the chaos leg
        row = {"clean": {"rounds_per_sec": 5.0},
               "chaos": {"rounds_per_sec": 4.0}}
        assert bench._trend_metrics(row) == {"rounds_per_sec": 4.0}

    def test_trend_metrics_skips_non_evidence_rows(self):
        import bench
        assert bench._trend_metrics({"error": "x",
                                     "rounds_per_sec": 1.0}) is None
        assert bench._trend_metrics({"tokens_per_sec": 1.0}) is None

    def test_append_trend_row_first_passes_then_regression_fails(
            self, tmp_path, monkeypatch):
        """The bench-side acceptance shape: the first-ever row passes,
        a planted 2x rounds/sec regression on the same key fails."""
        import bench
        ledger = str(tmp_path / "trends.jsonl")
        monkeypatch.setattr(bench, "_TREND_LEDGER", ledger)
        assert bench._append_trend_row(
            "stage_x", {"rounds_per_sec": 100.0}, "cpu-smoke") == []
        assert bench._append_trend_row(
            "stage_x", {"rounds_per_sec": 101.0}, "cpu-smoke") == []
        problems = bench._append_trend_row(
            "stage_x", {"rounds_per_sec": 50.0}, "cpu-smoke")
        assert problems and "rounds_per_sec" in problems[0]
        # the regressed row still entered the trajectory (evidence
        # first; the verdict is the exit code's job)
        assert len(trend.load_rows(ledger)) == 3
        # --check-trend verdict: collected problems -> non-zero exit
        assert bench._trend_verdict(True, problems) == 1
        assert bench._trend_verdict(False, problems) == 0
        assert bench._trend_verdict(True, []) == 0


class TestTrendCli:
    def _seed(self, path, rps_last):
        for _ in range(4):
            trend.append_row(path, _row(stage="cli", rps=100.0))
        trend.append_row(path, _row(stage="cli", rps=rps_last))

    @pytest.mark.parametrize("rps_last,code", [(95.0, 0), (40.0, 1)])
    def test_check_latest_exit_codes(self, tmp_path, rps_last, code):
        import os
        path = str(tmp_path / "trends.jsonl")
        self._seed(path, rps_last)
        rc = subprocess.run(
            [sys.executable, "-m", "fedml_tpu.obs", "trend", path,
             "--check-latest"],
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert rc.returncode == code, rc.stderr

    def test_empty_ledger_passes_unless_required(self, tmp_path):
        import os
        path = str(tmp_path / "absent.jsonl")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        rc = subprocess.run(
            [sys.executable, "-m", "fedml_tpu.obs", "trend", path,
             "--check-latest"],
            capture_output=True, text=True, env=env)
        assert rc.returncode == 0  # vacuous pass while seeding
        rc = subprocess.run(
            [sys.executable, "-m", "fedml_tpu.obs", "trend", path,
             "--check-latest", "--require-rows"],
            capture_output=True, text=True, env=env)
        assert rc.returncode == 2

    def test_summary_output(self, tmp_path):
        import os
        path = str(tmp_path / "trends.jsonl")
        self._seed(path, 100.0)
        rc = subprocess.run(
            [sys.executable, "-m", "fedml_tpu.obs", "trend", path],
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert rc.returncode == 0
        (line,) = rc.stdout.strip().splitlines()
        summary = json.loads(line)
        assert summary["stage"] == "cli" and summary["rows"] == 5

    def test_threshold_flags_reach_the_gate(self, tmp_path):
        import os
        path = str(tmp_path / "trends.jsonl")
        self._seed(path, 80.0)  # a 20% drop
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        rc = subprocess.run(
            [sys.executable, "-m", "fedml_tpu.obs", "trend", path,
             "--check-latest", "--max-rps-drop", "0.10"],
            capture_output=True, text=True, env=env)
        assert rc.returncode == 1  # tightened gate catches it
        rc = subprocess.run(
            [sys.executable, "-m", "fedml_tpu.obs", "trend", path,
             "--check-latest"],
            capture_output=True, text=True, env=env)
        assert rc.returncode == 0  # default 30% gate forgives it


class TestShippedLedgerSeeded:
    def test_repo_ledger_has_a_real_bench_row(self):
        """The acceptance criterion: runs/trends.jsonl ships seeded with
        at least one real cpu-smoke bench row, and the shipped rows all
        pass their own trend check (the trajectory starts clean)."""
        import os
        path = os.path.join(os.path.dirname(__file__), "..", "runs",
                            "trends.jsonl")
        rows = trend.load_rows(path)
        bench_rows = [r for r in rows if r.get("host") == "cpu-smoke"
                      and r.get("rounds_per_sec")]
        assert bench_rows, "runs/trends.jsonl must ship a seeded row"
        assert all(r["schema_version"] == trend.TREND_SCHEMA_VERSION
                   for r in rows)
        assert trend.check_latest(path) == []
