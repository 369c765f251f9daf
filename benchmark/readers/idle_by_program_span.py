"""Device idle milliseconds per round of the traced slice under a span that
the PROGRAM wrote (``RoundTimer.phase``; ``fedml_tpu.utils.tracing
.recent_spans``), averaged over the chips: idle while a span named ``span``
is open and none named in ``outside``.

The program's spans are on ``time.perf_counter_ns()``, the trace on the
profiler's clock, and the reduced trace keeps of the host only the
harness's own ``bench.*`` spans. The two meet at the rounds:
``api.run_round`` is the first thing inside a ``bench.run_round`` span and
opening its ``round`` span the first thing it does. So the program's
``round`` span of every round of the slice (the newest of its index: the
warm-up ran rounds of the same indices earlier) is laid on the start of the
``bench.run_round`` span of the same ordinal, one offset per round so that
drift between the clocks does not add up; any other span takes the offset of
the round nearest to it in time. The device's idle intervals are the
harness's own (``harness/trace.py``: ``events``, ``merge``, ``gaps``).

Nothing rather than a wrong number: None, with the reason on standard
error, when the program keeps no spans, the slice's rounds have left its
ring, their count is not the trace's, or a ``round`` span once laid down
sticks out of its ``bench.run_round`` span by more than ``NEST_TOLERANCE_S``.
"""

import sys

from benchmark.harness import loop
from benchmark.harness import trace as tr

HARNESS_ROUND = "bench.run_round"
PROGRAM_ROUND = "round"
#: how far an anchored ``round`` span may reach past its harness span
NEST_TOLERANCE_S = 0.2e-3


def _nothing(reason):
    print(f"[bench] idle_by_program_span: {reason}; metric left out",
          file=sys.stderr, flush=True)
    return None


def slice_rounds(eval_every):
    """The round indices ``loop.measure`` runs under the profiler."""
    slice_open = eval_every
    slice_close = slice_open + -(-loop.TRACE_ROUNDS // eval_every) * eval_every
    return list(range(slice_open + 1, slice_close + 1))


def on_trace_clock(spans, rounds, harness_rounds):
    """``{name: [(start, end)]}`` of the program's ``spans`` around the
    slice in trace seconds; a ``ValueError`` says why not. ``spans`` are
    ``(name, thread, round, t0_ns, t1_ns)`` by start; ``harness_rounds`` the
    ``bench.run_round`` intervals of the slice in order."""
    newest = {s[2]: s for s in spans if s[0] == PROGRAM_ROUND}
    missing = [r for r in rounds if r not in newest]
    if missing:
        raise ValueError(f"rounds {missing[:5]} of the slice are not in the "
                         "span ring")
    anchored = []  # (t0, t1, offset) of each round, program seconds
    for r, (start, end) in zip(rounds, harness_rounds):
        t0, t1 = newest[r][3] * 1e-9, newest[r][4] * 1e-9
        if t1 - t0 > end - start + NEST_TOLERANCE_S:
            raise ValueError(
                f"round {r} took {t1 - t0:.6f} s by the program's span and "
                f"{end - start:.6f} s by the harness's")
        anchored.append((t0, t1, start - t0))
    lo, hi = anchored[0][0], anchored[-1][1]
    out = {}
    for name, _, _, t0_ns, t1_ns in spans:
        t0, t1 = t0_ns * 1e-9, t1_ns * 1e-9
        if t1 < lo or t0 > hi:
            continue  # not the slice's: the warm-up, the rest of the window
        offset = min(anchored, key=lambda a: max(a[0] - t1, t0 - a[1], 0.0)
                     )[2]
        out.setdefault(name, []).append((t0 + offset, t1 + offset))
    return out


def idle_ms(trace, window, spans, rounds, span, outside=()):
    """The reduction; ``read`` without the context object."""
    harness_rounds = sorted(tr.spans_in(trace, HARNESS_ROUND, window))
    if len(harness_rounds) != len(rounds):
        return _nothing(f"the trace holds {len(harness_rounds)} "
                        f"{HARNESS_ROUND} spans, the slice {len(rounds)} "
                        "rounds")
    try:
        placed = on_trace_clock(spans, rounds, harness_rounds)
    except ValueError as why:
        return _nothing(why)
    under = tr.subtract(placed.get(span, []),
                        [i for name in outside for i in placed.get(name, [])])
    per_device = []
    for device in range(len(trace["devices"])):
        busy = tr.merge(tr.events(trace, device, "ops", window).intervals())
        per_device.append(sum(tr.total(tr.clip(under, gap))
                              for gap in tr.gaps(busy, window)))
    return 1e3 * sum(per_device) / len(per_device) / len(rounds)


def read(ctx, span, outside=()):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    try:
        from fedml_tpu.utils.tracing import recent_spans
    except ImportError:
        return _nothing("this program keeps no spans")
    return idle_ms(ctx.trace, ctx.trace_window, recent_spans(),
                   slice_rounds(int(ctx.cell.traffic["eval_every"])), span,
                   outside)
