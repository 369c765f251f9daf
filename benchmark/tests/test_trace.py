"""The trace reduction, on hand-made traces whose answers can be worked out
on paper and on a small trace recorded on the chip."""

import os

import numpy as np
import pytest

from benchmark.harness import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = ("bench.run_round", "bench.drain", "bench.evaluate")


def make(ops, modules=(), flights=(), spans=(), window=(0.0, 10.0)):
    """A one-device trace from (name, start, duration) triples."""
    names = []

    def rows(events):
        out = []
        for name, start, dur in events:
            if name not in names:
                names.append(name)
            out.append([names.index(name), start, dur])
        return out

    device = {"name": "/device:TPU:0", "ops": rows(ops),
              "async": rows(flights), "modules": rows(modules)}
    spans = [[tr.SLICE, window[0], window[1] - window[0]]] + [
        list(s) for s in spans]
    return {"names": names, "devices": [device], "spans": spans}


def test_interval_arithmetic():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert tr.gaps([(1, 2), (3, 4)], (0, 5)) == [(0, 1), (2, 3), (4, 5)]
    assert tr.clip([(0, 2), (3, 9)], (1, 4)) == [(1, 2), (3, 4)]
    assert tr.subtract([(0, 10)], [(1, 2), (1.5, 3), (9, 12)]) == [
        (0, 1), (3, 9)]
    assert tr.total([(0, 1), (2, 2.5)]) == 1.5


def test_busy_is_the_union_and_a_while_is_not_billed_twice():
    # a while from 1 to 5 encloses two body operations; one more after it
    t = make([("%while.1 = while()", 1.0, 4.0),
              ("%fusion.1 = fusion()", 1.0, 1.0),
              ("%fusion.2 = fusion()", 2.5, 2.0),
              ("%copy.1 = copy()", 6.0, 1.0)])
    w = tr.window_of(t)
    assert tr.busy_seconds(t, w) == pytest.approx(5.0)
    assert tr.op_seconds(t, w) == pytest.approx(5.0)  # self times
    assert tr.op_seconds(t, w, pattern=r"^%while") == pytest.approx(1.0)
    assert tr.op_seconds(t, w, exclude=r"^%while") == pytest.approx(4.0)
    assert tr.op_seconds(t, w, pattern="nothing") is None
    top = tr.top_ops(t, w, k=2)
    assert [round(s, 6) for _, s in top] == [2.0, 1.0]
    assert top[0][0].startswith("%fusion.2")


def test_neighbours_that_overlap_by_a_rounding_are_siblings():
    t = make([("%a = a()", 1.0, 3e-9), ("%b = b()", 1.0 + 2e-9, 1.0)])
    w = tr.window_of(t)
    assert tr.op_seconds(t, w, pattern="^%a") == pytest.approx(3e-9)
    assert tr.op_seconds(t, w, pattern="^%b") == pytest.approx(1.0)


def test_events_are_cut_at_the_window_not_dropped():
    t = make([("%a = a()", -1.0, 2.0), ("%b = b()", 9.5, 2.0)])
    assert tr.busy_seconds(t, tr.window_of(t)) == pytest.approx(1.5)


def test_programs_and_host_spans_select_operations():
    t = make(
        ops=[("%x = x()", 1.0, 1.0), ("%x = x()", 4.0, 1.0),
             ("%x = x()", 7.0, 1.0)],
        modules=[("jit_round_fn(1)", 0.9, 1.2), ("jit_evaluate(2)", 3.9, 1.2),
                 ("jit_body(3)", 6.9, 1.2)],
        spans=[("bench.evaluate", 6.5, 2.0)])
    w = tr.window_of(t)
    rounds = r"^jit_(round_fn|body)\("
    assert tr.op_seconds(t, w, within_modules=rounds) == pytest.approx(2.0)
    assert tr.op_seconds(t, w, within_modules=rounds,
                         outside_spans=["bench.evaluate"]
                         ) == pytest.approx(1.0)


def test_idle_gaps_go_to_the_host_span_open_at_the_time():
    t = make(ops=[("%x = x()", 1.0, 2.0), ("%x = x()", 5.0, 1.0)],
             spans=[("bench.run_round", 0.0, 0.5), ("bench.drain", 0.5, 2.5),
                    ("bench.evaluate", 3.0, 3.5)])
    # idle: 0-1 (run_round 0.5, drain 0.5), 3-5 (evaluate), 6-10 (evaluate
    # to 6.5, then no span)
    got = dict(tr.idle_by_span(t, tr.window_of(t), SPANS, "bench.loop"))
    assert got == pytest.approx({"bench.run_round": 0.5, "bench.drain": 0.5,
                                 "bench.evaluate": 2.5, "bench.loop": 3.5})
    assert list(got)[0] == "bench.loop"  # longest first


def test_exposed_share_of_collectives():
    # an asynchronous all-reduce in flight from 1 to 5, half of it under a
    # fusion; a synchronous one from 6 to 7 under nothing
    t = make(ops=[("%all-reduce-start.1 = all-reduce-start()", 1.0, 0.01),
                  ("%fusion.1 = fusion(%all-reduce-start.1)", 2.0, 2.0),
                  ("%all-reduce-done.1 = all-reduce-done()", 4.99, 0.01),
                  ("%all-reduce.2 = all-reduce()", 6.0, 1.0)],
             flights=[("%all-reduce-start.1 = all-reduce-start()", 1.0, 4.0)])
    share = tr.exposed_share(t, tr.window_of(t), r"^%all-reduce")
    assert share == pytest.approx((2.0 + 1.0) / 5.0)
    assert tr.exposed_share(t, tr.window_of(t), "^%nothing") is None


def test_short_name_drops_layouts():
    name = ("%fusion.3 = f32[20,26]{1,0:T(8,128)} fusion(bf16[4]{0} %a), "
            "kind=kLoop")
    assert tr.short_name(name) == ("%fusion.3 f32[20,26] fusion(bf16[4] %a), "
                                   "kind=kLoop")


# -- the trace recorded on the chip ----------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return tr.load(os.path.join(HERE, "fixture", "trace_v5e.json.gz"))


def test_recorded_trace_busy_by_an_independent_sweep(recorded):
    w = tr.window_of(recorded)
    ops = np.array([[s, s + d] for _, s, d in recorded["devices"][0]["ops"]])
    ops = ops[np.argsort(ops[:, 0])]
    reach = np.maximum.accumulate(ops[:, 1])
    idle = (ops[0, 0] - w[0]) + (w[1] - reach[-1]) + np.sum(
        np.maximum(0.0, ops[1:, 0] - reach[:-1]))
    busy = tr.busy_seconds(recorded, w)
    assert busy == pytest.approx((w[1] - w[0]) - idle, rel=1e-9)
    assert busy == pytest.approx(0.51272808, rel=1e-6)
    # the self times add up to the union: nothing billed twice or dropped
    assert tr.op_seconds(recorded, w) == pytest.approx(busy, rel=1e-6)
    by_span = tr.idle_by_span(recorded, w, SPANS, "bench.loop")
    assert sum(s for _, s in by_span) == pytest.approx(idle, rel=1e-6)
    assert by_span[0][0] == "bench.evaluate"


def test_recorded_trace_kernel_and_programs(recorded):
    w = tr.window_of(recorded)
    names = recorded["names"]
    kernel = sum(d for i, _, d in recorded["devices"][0]["ops"]
                 if "tpu_custom_call" in names[i])
    assert tr.op_seconds(recorded, w, pattern="tpu_custom_call"
                         ) == pytest.approx(kernel, rel=1e-9)
    assert kernel == pytest.approx(2 * 5.004e-3, rel=1e-3)  # two calls
    rounds = tr.op_seconds(recorded, w, within_modules=r"^jit_round_fn\(")
    evals = tr.op_seconds(recorded, w, within_modules=r"^jit_evaluate\(")
    assert rounds == pytest.approx(0.448532, rel=1e-5)
    assert evals == pytest.approx(0.0641827, rel=1e-5)
    assert rounds + evals <= tr.busy_seconds(recorded, w)
    assert tr.top_ops(recorded, w, 1)[0][0].startswith("%pad.2 f32[80,")
