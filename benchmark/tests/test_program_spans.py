"""The readers of what the program records about itself: device idle time
under the program's own spans (``idle_by_program_span``), on a hand-made
trace and span log whose answer can be worked out on paper, and the
program's count of dispatched rows (``dispatched_padding``) against the
benchmark's own on the fixture's cells."""

import json
import os
import time

import pytest

from benchmark.harness import cell as cell_mod
from benchmark.harness import spec
from benchmark.harness import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture", "BENCHMARK.json")
reader = spec.load_module(os.path.join(
    spec.ROOT, "benchmark", "readers", "idle_by_program_span.py"))

#: the trace's clock starts far from the program's and runs a little faster
OFFSET_S = 1.7e9
DRIFT = 1e-4
ROUND_S = 0.1


def to_program_ns(trace_s):
    return int(round((trace_s - OFFSET_S) * (1 + DRIFT) * 1e9)) + 5_000_000_000


def make(rounds, late_end=0.0):
    """Rounds of ``ROUND_S`` one after another from trace second
    ``OFFSET_S``. In each the harness span is the whole round and the
    program's ``round`` span starts with it and ends 10 us early (``late_end``
    later); ``prefetch_wait`` and ``dispatch`` lie inside at the given times
    from the round's start; a worker's ``produce`` straddles the round's
    start. The device is busy from 0.03 to 0.08 in every round, so it
    is idle for 0.028 under ``prefetch_wait``, for 0.01 under ``dispatch``,
    for 0.002 + 0.01 - 10 us under the round alone, and for 0.02 + 0.03 under
    ``produce``."""
    ops, spans, program = [], [], []
    for i, r in enumerate(rounds):
        start = OFFSET_S + i * ROUND_S
        spans.append(["bench.run_round", start, ROUND_S])
        ops.append(["%fusion.1 = fusion()", start + 0.03, 0.05])
        for name, thread, (lo, hi) in (
                ("produce", "worker", (-0.02, 0.04)),
                ("round", "main", (0.0, ROUND_S - 10e-6 + late_end)),
                ("prefetch_wait", "main", (0.002, 0.042)),
                ("dispatch", "main", (0.05, 0.09))):
            program.append((name, thread, r, to_program_ns(start + lo),
                            to_program_ns(start + hi)))
    window = (OFFSET_S - 0.5, OFFSET_S + len(rounds) * ROUND_S + 0.5)
    trace = {"names": ["%fusion.1 = fusion()"],
             "devices": [{"name": "/device:TPU:0", "async": [], "modules": [],
                          "ops": [[0, s, d] for _, s, d in ops]}],
             "spans": [[tr.SLICE, window[0], window[1] - window[0]]] + spans}
    return trace, window, sorted(program, key=lambda s: s[3])


def test_slice_rounds_are_the_loops():
    assert reader.slice_rounds(5) == list(range(6, 16))
    assert reader.slice_rounds(2) == list(range(3, 13))
    assert reader.slice_rounds(3) == list(range(4, 16))
    assert reader.slice_rounds(50) == list(range(51, 101))


def test_idle_split_is_recovered_across_offset_and_drift():
    rounds = reader.slice_rounds(5)
    trace, window, program = make(rounds)
    # an older round of a slice index (the warm-up's) and the rounds around
    # the slice must not be taken for the slice's
    stale = [("round", "main", rounds[0], 1_000, 2_000_000),
             ("dispatch", "main", rounds[0], 1_500, 1_900_000),
             ("round", "main", rounds[-1] + 1,
              to_program_ns(OFFSET_S + 10 * ROUND_S + 0.6),
              to_program_ns(OFFSET_S + 10 * ROUND_S + 0.7))]
    program = sorted(program + stale, key=lambda s: s[3])
    got = {name: reader.idle_ms(trace, window, program, rounds, *args)
           for name, args in {
               "wait": ("prefetch_wait",), "dispatch": ("dispatch",),
               "produce": ("produce",),
               "other": ("round", ["prefetch_wait", "dispatch"])}.items()}
    # exact but for the anchoring error: the drift over one round, 10 us
    assert got["wait"] == pytest.approx(28.0, abs=2e-2)      # 0.002 .. 0.03
    assert got["dispatch"] == pytest.approx(10.0, abs=2e-2)  # 0.08 .. 0.09
    assert got["other"] == pytest.approx(2.0 + 10.0 - 0.01, abs=2e-2)
    # a worker's span straddles two rounds and takes the nearer's offset
    assert got["produce"] == pytest.approx(20.0 + 30.0, abs=3e-2)
    # the three add up to the harness's own idle time under bench.run_round
    harness = dict(tr.idle_by_span(trace, window, ["bench.run_round"],
                                   "bench.loop"))["bench.run_round"]
    assert harness == pytest.approx(0.05 * len(rounds))
    assert got["wait"] + got["dispatch"] + got["other"] == pytest.approx(
        1e3 * harness / len(rounds), abs=3e-2)


def test_nothing_when_a_round_does_not_nest_or_has_left_the_ring(capsys):
    rounds = reader.slice_rounds(5)
    trace, window, program = make(rounds, late_end=0.5e-3)
    assert reader.idle_ms(trace, window, program, rounds, "dispatch") is None
    assert "by the program's span" in capsys.readouterr().err
    trace, window, program = make(rounds, late_end=0.15e-3)  # in tolerance
    assert reader.idle_ms(trace, window, program, rounds, "dispatch"
                          ) == pytest.approx(10.0, abs=0.2)
    trace, window, program = make(rounds)
    gone = [s for s in program if s[2] != rounds[3]]
    assert reader.idle_ms(trace, window, gone, rounds, "dispatch") is None
    assert "not in the span ring" in capsys.readouterr().err
    assert reader.idle_ms(trace, window, program, rounds[:-1], "dispatch"
                          ) is None
    assert "the slice 9 rounds" in capsys.readouterr().err


def test_read_returns_nothing_without_a_device_plane_or_a_span_log(
        monkeypatch):
    class Ctx:
        trace = None
        trace_window = None
        cell = spec.load_cell("fedcifar100_resnet18gn.dense")

    assert reader.read(Ctx, "dispatch") is None
    rounds = reader.slice_rounds(int(Ctx.cell.traffic["eval_every"]))
    Ctx.trace, Ctx.trace_window, program = make(rounds)
    import fedml_tpu.utils.tracing as tracing

    monkeypatch.setattr(tracing, "recent_spans", lambda: program)
    assert reader.read(Ctx, "dispatch") == pytest.approx(10.0, abs=1e-2)
    monkeypatch.delattr(tracing, "recent_spans")  # the parent commit's
    assert reader.read(Ctx, "dispatch") is None
    Ctx.trace = {**Ctx.trace, "devices": []}  # a CPU run
    assert reader.read(Ctx, "dispatch") is None


@pytest.fixture(scope="module")
def manifest_with_the_new_metrics(tmp_path_factory):
    """The fixture's manifest with the metrics this file is about appended
    from the real one: files and entries only."""
    manifest = spec.load_json(FIXTURE)
    names = {"starved_ms", "starved_max_ms", "produce_ms",
             "dispatched_padding_share", "idle_dispatch_ms"}
    manifest["per_layer"] += [m for m in spec.load_json(spec.MANIFEST)[
        "per_layer"] if m["name"] in names]
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


@pytest.mark.parametrize("name", [
    "tiny_cnn.tiny_sampled", "tiny_cnn.tiny_resident", "tiny_cnn.tiny_mesh",
    "tiny_lr.tiny_fast"])
def test_the_programs_count_of_padding_is_the_benchmarks(
        name, manifest_with_the_new_metrics, tmp_path):
    cell = spec.load_cell(name, manifest_with_the_new_metrics)
    result = cell_mod.run(cell, seed=2 ** 31 + 5, seconds=1.0, trace=True,
                          t_start=time.time(), out_dir=str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0.0 < metrics["padded_row_share"] < 100.0
    assert metrics["dispatched_padding_share"] == pytest.approx(
        metrics["padded_row_share"], abs=1e-9)
    # the probe and the produce span ran in every round of the window
    assert metrics["produce_ms"] > 0.0
    assert metrics["starved_max_ms"] >= metrics["starved_ms"] >= 0.0
    # a CPU has no device plane: nothing to lay the spans over
    assert "idle_dispatch_ms" not in metrics
