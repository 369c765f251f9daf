"""The measured window: the program's own round loop, on the host's clock.

The loop is ``FedAvgAPI.train`` / ``DistributedFedAvgAPI.train`` without
their logging: ``api.run_round(r)`` for r = 0, 1, ...; after every
``eval_every``-th round (r % eval_every == 0, as the program counts)
``jax.block_until_ready(api.variables)`` and then the driver's evaluation.
Between two evaluations the host reads nothing from the device: each round's
``stats`` stay device arrays in a list until the window is over, because a
``float()`` a round would serialise host and device and measure another
system.

The clock is read only after a block, so every reading is of finished work.
The window ends at the first block at or past ``seconds`` (and past the first
evaluation); the evaluation that would follow is not run. The drain before an evaluation is training
time; the evaluation's own wall is taken out of it.

With ``tracer`` set, one steady slice runs under the profiler: from the end
of the second evaluation to the end of the first evaluation at least
``TRACE_ROUNDS`` rounds later, so whole evaluation intervals with their
drains and evaluations. The window does not end before the slice has. Starting and stopping the
profiler is timed and taken out of the training wall.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax


#: rounds the traced slice holds at least
TRACE_ROUNDS = 10


@dataclasses.dataclass
class Window:
    rounds: int = 0
    wall_s: float = 0.0
    eval_walls: List[float] = dataclasses.field(default_factory=list)
    profiler_s: float = 0.0
    cohorts: List = dataclasses.field(default_factory=list)
    stats: List[Dict] = dataclasses.field(default_factory=list)
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    compiles: int = 0
    traced: bool = False

    @property
    def train_wall_s(self) -> float:
        return self.wall_s - sum(self.eval_walls) - self.profiler_s


class Tracer:
    """Starts and stops the JAX profiler around the traced slice, host
    spans on, the Python call tracer off (it slows the loop it measures)."""

    def __init__(self, directory: str):
        self.directory = directory

    def start(self) -> None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)

    def stop(self) -> None:
        jax.profiler.stop_trace()


def _delta(after: Dict, before: Dict) -> Dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def measure(api, evaluate: Callable, *, eval_every: int, seconds: float,
            round_bound: int, compiles: Callable[[], int],
            tracer: Optional[Tracer] = None) -> Window:
    """Run the loop on ``api`` for ``seconds``; see the module docstring.
    ``compiles()`` is the process's count of compilations so far."""
    annotate = jax.profiler.TraceAnnotation
    win = Window()
    timer = api.timer
    phases0, counters0 = dict(timer.totals), dict(timer.counters)
    compiles0 = compiles()
    device_stats = []
    # the slice opens after the evaluation at round eval_every and closes
    # after the first one at least TRACE_ROUNDS rounds later
    slice_open = eval_every
    slice_close = slice_open + -(-TRACE_ROUNDS // eval_every) * eval_every
    slice_span = None
    start = time.perf_counter()
    for r in range(round_bound):
        with annotate("bench.run_round"):
            cohort, stats = api.run_round(r)
        win.cohorts.append(cohort)
        device_stats.append(stats)
        if r % eval_every:
            continue
        with annotate("bench.drain"):
            jax.block_until_ready(api.variables)
        now = time.perf_counter()
        # not before one evaluation has been timed, nor inside the slice
        unfinished = not win.eval_walls or (tracer is not None
                                            and not win.traced)
        if now - start >= seconds and not unfinished:
            win.rounds, win.wall_s = r + 1, now - start
            break
        with annotate("bench.evaluate"):
            evaluate(api, r)
        win.eval_walls.append(time.perf_counter() - now)
        if tracer is not None and r in (slice_open, slice_close):
            t0 = time.perf_counter()
            if r == slice_open:
                tracer.start()
                slice_span = annotate("bench.slice")
                slice_span.__enter__()
            else:
                slice_span.__exit__(None, None, None)
                tracer.stop()
                win.traced = True
            win.profiler_s += time.perf_counter() - t0
    else:
        raise RuntimeError(
            f"the window was still open after round_bound={round_bound} "
            "rounds; the traffic file's round_bound is too small for this "
            "system")
    win.phases = _delta(dict(timer.totals), phases0)
    win.counters = _delta(dict(timer.counters), counters0)
    win.compiles = compiles() - compiles0
    win.stats = [{k: float(v) for k, v in s.items()}
                 for s in jax.device_get(device_stats)]
    return win
