"""The round's host timeline (utils/tracing.py): ``RoundTimer.phase()`` as a
span, the starved-device probe, ``rows_dispatched``, the ``fedml.*``
annotations in a profiler trace and the scopes on the device program.

The contract under test: the spans only observe. Totals read as before,
the rings are bounded, every span knows its thread and round, and the
trajectories of both drivers are bit for bit the parent commit's.
"""

import glob
import hashlib
import os
import re
import threading

import jax
import numpy as np
import pytest

from fedml_tpu.utils.tracing import (SPANS_PER_ROUND, RoundTimer,
                                     recent_spans)

ROUNDS = 5


def _dataset():
    from fedml_tpu.data.synthetic import make_powerlaw_blob_federated
    return make_powerlaw_blob_federated(client_num=40, dim=16, class_num=5,
                                        seed=2)


def _train_config():
    from fedml_tpu.trainer.functional import TrainConfig
    return TrainConfig(epochs=1, batch_size=8, lr=0.1)


def _sim(ds, cohort=6, **config):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.models.lr import LogisticRegression
    return FedAvgAPI(ds, LogisticRegression(num_classes=5),
                     config=FedAvgConfig(**{**dict(
                         comm_round=ROUNDS, client_num_per_round=cohort,
                         seed=7, frequency_of_the_test=2,
                         train=_train_config()), **config}))


def _spmd(ds, cohort=6, **config):
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                         DistributedFedAvgConfig, build_mesh)
    return DistributedFedAvgAPI(
        ds, LogisticRegression(num_classes=5),
        mesh=build_mesh({"clients": 8}),
        config=DistributedFedAvgConfig(**{**dict(
            comm_round=ROUNDS, client_num_per_round=cohort, seed=7,
            frequency_of_the_test=2, train=_train_config()), **config}))


DRIVERS = {"sim": _sim, "spmd": _spmd}


def _inside(inner, outer):
    return outer[3] <= inner[3] and inner[4] <= outer[4]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


# -- the timer alone --------------------------------------------------------

def test_a_span_carries_its_thread_and_the_round_open_when_it_closed():
    timer = RoundTimer()
    with timer.phase("eval"):
        pass
    timer.begin_round(3)
    with timer.phase("dispatch"):
        pass

    def pack():
        with timer.phase("pack"):
            pass

    worker = threading.Thread(target=pack, name="a-worker")
    worker.start()
    worker.join(timeout=10)
    assert not worker.is_alive()
    rec = timer.end_round(3)
    main = threading.current_thread().name
    spans = timer.spans()
    assert [(s[0], s[1], s[2]) for s in spans] == [
        ("eval", main, None), ("dispatch", main, 3),
        ("pack", "a-worker", 3), ("round", main, 3)]
    assert all(s[4] >= s[3] for s in spans)
    round_span = spans[-1]
    assert all(_inside(s, round_span) for s in spans[1:3])
    # the record lists what closed inside the round, from the round's start
    assert [s[:2] for s in rec["spans"]] == [["dispatch", main],
                                             ["pack", "a-worker"]]
    assert all(0 <= t0 <= t1 <= rec["duration_s"] * 1e9 + 1e3
               for _, _, t0, t1 in rec["spans"])
    assert "round" not in timer.totals  # the record's duration_s has it
    # every live timer's ring, process-wide, by start
    assert [s for s in recent_spans() if s in spans] == sorted(
        spans, key=lambda s: s[3])


def test_totals_are_the_sums_of_the_intervals_and_add_keeps_none():
    timer = RoundTimer()
    for _ in range(7):
        with timer.phase("pack"):
            with timer.phase("upload"):
                pass
    timer.add("prefetch_wait", 0.25)
    spans = timer.spans()
    for name in ("pack", "upload"):
        lengths = [(s[4] - s[3]) * 1e-9 for s in _named(spans, name)]
        assert timer.counts[name] == len(lengths) == 7
        assert timer.totals[name] == pytest.approx(sum(lengths), rel=1e-12)
    assert timer.totals["prefetch_wait"] == 0.25
    assert not _named(spans, "prefetch_wait")


def test_the_span_ring_is_bounded_in_rounds():
    timer = RoundTimer(ring_capacity=4)
    for r in range(50):
        timer.begin_round(r)
        for _ in range(40):
            with timer.phase("dispatch"):
                pass
        rec = timer.end_round(r)
    assert len(timer.spans()) == 4 * SPANS_PER_ROUND
    assert timer.spans()[-1][:3] == ("round", "MainThread", 49)
    # a round that closed more than the ring holds lists what is left
    assert len(rec["spans"]) == 4 * SPANS_PER_ROUND
    assert timer.counts["dispatch"] == 50 * 40  # totals lose nothing


def test_a_superseded_or_mismatched_round_leaves_no_round_span():
    timer = RoundTimer()
    timer.begin_round(1)
    timer.begin_round(2)
    assert timer.end_round(3) is None
    assert timer.end_round(2) is None
    assert timer.spans() == []


class Leaf:
    """An array whose ``is_ready()`` answers are scripted."""

    def __init__(self, *answers):
        self.answers = list(answers)

    def is_ready(self):
        return self.answers.pop(0)


@pytest.mark.parametrize("answers, phase", [
    ((True, True), "device_starved"),         # idle all along
    ((False, True), "device_starved_max"),    # ran dry inside
    ((False, False), None)])                  # the wait was hidden
def test_the_starved_probe_charges_by_two_queries(answers, phase):
    timer = RoundTimer()
    timer.begin_round(0)
    leaf = Leaf(*answers)
    with timer.starved_probe(leaf):
        with timer.phase("prefetch_wait"):
            pass
    rec = timer.end_round(0)
    assert leaf.answers == []  # two queries, no more
    charged = {p for p in ("device_starved", "device_starved_max")
               if p in timer.totals}
    assert charged == ({phase} if phase else set())
    assert timer.counters["starved_rounds"] == (1 if phase else 0)
    if phase:
        starved, wait = _named(timer.spans(), phase)[0], _named(
            timer.spans(), "prefetch_wait")[0]
        assert _inside(wait, starved)
        assert rec["phases"][phase]["n"] == 1
        assert rec["counters"]["starved_rounds"] == 1


def test_the_starved_probe_takes_a_host_array_for_an_idle_device():
    timer = RoundTimer()
    with timer.starved_probe(np.zeros(3)):  # a restored checkpoint's leaf
        pass
    assert timer.counts["device_starved"] == 1


# -- the drivers ------------------------------------------------------------

@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_driver_spans_nest_by_thread_and_round(driver):
    api = DRIVERS[driver](_dataset(), frequency_of_the_test=10 ** 9)
    # the premise of the last block: round 0 starts on an idle device (on
    # a loaded host the fresh model's init may still be in flight)
    jax.block_until_ready(api.variables)
    for r in range(ROUNDS):
        api.run_round(r)
    jax.block_until_ready(api.variables)
    spans = api.timer.spans()
    rounds = _named(spans, "round")
    assert [s[2] for s in rounds] == list(range(ROUNDS))
    main = threading.current_thread().name
    for name in ("prefetch_wait", "dispatch"):
        inner = _named(spans, name)
        assert [s[2] for s in inner] == list(range(ROUNDS))
        assert all(s[1] == main and _inside(s, rounds[s[2]])
                   for s in inner)
    produced = _named(spans, "produce")
    assert len(produced) == ROUNDS  # one miss, then the worker's
    missed, = [s for s in produced if s[1] == main]
    assert _inside(missed, rounds[0])
    assert {s[1] for s in produced} == {main, api._prefetch[0].name}
    for name in ("pack", "upload"):
        inner = _named(spans, name)
        assert len(inner) == ROUNDS
        assert all(any(s[1] == p[1] and _inside(s, p) for p in produced)
                   for s in inner)
    # round 0 finds an idle device and packs inline: starved, and by at
    # least what its produce took
    starved = _named(spans, "device_starved")
    assert starved and starved[0][2] == 0
    assert _inside(missed, starved[0])
    totals = api.timer.totals
    assert totals["produce"] >= totals["pack"] + totals["upload"]


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_eval_is_billed_once_by_train_and_once_by_a_direct_call(driver):
    api = DRIVERS[driver](_dataset())
    api.train()
    evaluations = len(api.history)
    assert evaluations == 3  # rounds 0, 2, 4
    assert api.timer.counts["eval"] == evaluations
    assert api.timer.counts["device_wait"] == evaluations
    if driver == "sim":
        api.evaluate(ROUNDS - 1)
    else:
        api._eval_global()
    assert api.timer.counts["eval"] == evaluations + 1
    assert len(_named(api.timer.spans(), "eval")) == evaluations + 1


@pytest.mark.parametrize("driver, slots", [("sim", 6), ("spmd", 8)])
def test_rows_dispatched_is_slots_times_padded_length(driver, slots):
    ds = _dataset()
    api = DRIVERS[driver](ds, frequency_of_the_test=10 ** 9)
    expected = 0
    for r in range(ROUNDS):
        cohort, _ = api.run_round(r)
        assert len(cohort) == 6  # six clients, padded to the mesh's eight
        expected += slots * ds.cohort_padded_len(cohort, 8)
    assert api.timer.counters["rows_dispatched"] == expected
    assert [rec["counters"]["rows_dispatched"]
            for rec in api.timer.round_records()] == [
        slots * ds.cohort_padded_len(rec["cohort"], 8)
        for rec in api.timer.round_records()]


def test_fused_blocks_open_one_round_span_each():
    api = _sim(_dataset(), frequency_of_the_test=10 ** 9)
    fused = api.fused_rounds()
    fused.run_rounds(0, 2)
    fused.run_rounds(2, 3)
    rounds = _named(api.timer.spans(), "round")
    assert [s[2] for s in rounds] == [0, 2]
    assert [rec["rounds"] for rec in api.timer.round_records()] == [2, 3]
    assert all(_inside(s, rounds[0 if s[2] == 0 else 1])
               for s in _named(api.timer.spans(), "dispatch"))


def test_a_profiler_trace_holds_the_programs_spans_on_both_threads(tmp_path):
    from jax.profiler import ProfileData

    api = _sim(_dataset(), frequency_of_the_test=10 ** 9)
    api.run_round(0)  # compiled before the trace starts
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for r in range(1, 4):
            api.run_round(r)
        jax.block_until_ready(api.variables)
        api.evaluate(3)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    lines = {}  # span name -> the host lines (one a thread) it is on
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for thread, line in enumerate(plane.lines):
                for event in line.events:
                    if event.name.startswith("fedml."):
                        lines.setdefault(event.name, []).append(thread)
    assert len(lines["fedml.round"]) == 3
    assert len(lines["fedml.dispatch"]) == 3
    assert len(lines["fedml.prefetch_wait"]) == 3
    assert len(lines["fedml.eval"]) == 1
    assert lines["fedml.produce"] and lines["fedml.pack"]
    # the worker's spans are on another thread's line than the round's
    assert not set(lines["fedml.produce"]) & set(lines["fedml.round"])
    assert set(lines["fedml.dispatch"]) == set(lines["fedml.round"])


def test_the_three_scopes_name_the_device_programs():
    from fedml_tpu.ops import tree_weighted_mean_pallas

    ds = _dataset()
    for driver, want in (("sim", ("fedml.local_train",)),
                         ("spmd", ("fedml.local_train", "fedml.aggregate"))):
        api = DRIVERS[driver](ds, prefetch_depth=0)
        _, args = api._host_round_inputs(0)
        if driver == "sim":
            args = args + (np.uint32(0),)
        text = api._round_fn.lower(api.variables, *args).as_text(
            debug_info=True)
        # in the name stack of an operation, e.g.
        # "jit(round_fn)/vmap(fedml.local_train)/dot_general"
        assert all(re.search(rf'"[^"]*{re.escape(scope)}[^"]*/\w+"', text)
                   for scope in want), driver
    sim = DRIVERS["sim"](ds)
    train, _ = sim._eval_arrays()
    assert "fedml.eval" in sim._eval_fn.lower(sim.variables, *train).as_text(
        debug_info=True)
    stacked = {"w": np.ones((4, 300), np.float32)}
    kernel = jax.jit(lambda s, w: tree_weighted_mean_pallas(
        s, w, interpret=True))
    assert "fedml.aggregate" in kernel.lower(
        stacked, np.ones(4, np.float32)).as_text(debug_info=True)


# -- the trajectories, against the parent commit's ---------------------------

def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


#: the parameters after ``train()`` on this file's federation and seed, as
#: commit c6aaf36 (before the spans) computed them on this sandbox's CPU
#: under tests/conftest.py's settings; XLA's CPU code for another host may
#: round differently, and these are then to be recorded again there
PARENT = {
    "sim": (
        "6edf9648225097fe68758e3238c8530540fa117fade9fd67e9988c2f7c9d67e2",
        [1.5499008387735445, 0.011514557946112848, 0.0058413713920016245]),
    "spmd": (
        "12114c1e50238a7c8f17b5098367f36d45dbbfeef937888fdb1971e365f999fd",
        [1.5499009432857984, 0.011514569482495707, 0.0058413713920016245]),
}


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_trajectory_is_bit_identical_to_the_parents(driver):
    ds = _dataset()
    api = DRIVERS[driver](ds)
    api.train()
    digest, losses = PARENT[driver]
    assert [h["train_loss_local"] for h in api.history] == losses
    assert _digest(api.variables) == digest
    # and to the un-instrumented arithmetic: the round program called on
    # the serial pack, no timer and no prefetcher in the way
    plain = DRIVERS[driver](ds, prefetch_depth=0)
    for r in range(ROUNDS):
        if driver == "sim":
            _, args = plain._pack_round(r)[1:]
            args = args + (np.uint32(r),)
        else:
            _, _, args = plain._pack_round(r)
        plain.variables, _ = plain._round_fn(plain.variables, *args)
    assert _digest(plain.variables) == digest
