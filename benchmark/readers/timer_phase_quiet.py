"""Host milliseconds per round in the named ``RoundTimer`` phases over the
rounds of the window that began after the traced slice had closed: the
phases as the timed system has them, where ``timer_phase`` in a traced run
averages over a window half of whose rounds ran under the profiler.

The program keeps every phase as a span on its own clock, on whatever
thread ran it (``fedml_tpu.utils.tracing.recent_spans``). The quiet stretch
runs from the start of the first round after the slice to the end of the
window's last round, by the program's ``round`` spans (the newest of each
index: the warm-up ran rounds of the same indices earlier), and the spans
read are those that closed inside it - a worker's span that closes during a
drain or an evaluation counts, which a round's own record (what closed
while it was open) would lose. The figure is their time over the stretch's
rounds; with ``per_span`` the mean span of each phase, summed over the
phases: a cohort is made a round, so the two agree over a long stretch, and
over a short one (five rounds on four chips, where the round thread runs
ahead of the device and the last cohorts are done after the last round has
been enqueued) only the second is free of the stretch's edges. The slice's
rounds are reckoned as ``idle_by_program_span.slice_rounds`` reckons them;
the rounds before the slice hold the refill after ``release_prefetch()``
and are left out like the slice's own. Nothing, with the reason on standard
error, where the program keeps no spans, the rounds have left its ring or
fewer than ``MIN_ROUNDS`` rounds followed the slice."""

import sys

PROGRAM_ROUND = "round"
#: the rounds after the slice a figure has to rest on
MIN_ROUNDS = 3


def _nothing(reason):
    print(f"[bench] timer_phase_quiet: {reason}; metric left out",
          file=sys.stderr, flush=True)
    return None


def quiet_ms(spans, rounds, phases, per_span=False):
    """The figure over ``rounds``; ``read`` without the context object.
    ``spans`` are ``(name, thread, round, t0_ns, t1_ns)`` by start."""
    if len(rounds) < MIN_ROUNDS:
        return _nothing(f"{len(rounds)} rounds followed the slice")
    newest = {s[2]: s for s in spans if s[0] == PROGRAM_ROUND}
    if rounds[0] not in newest or rounds[-1] not in newest:
        return _nothing(f"rounds {rounds[0]}..{rounds[-1]} are not in the "
                        "span ring")
    lo, hi = newest[rounds[0]][3], newest[rounds[-1]][4]
    inside = {phase: [t1 - t0 for name, _, _, t0, t1 in spans
                      if name == phase and lo <= t1 <= hi]
              for phase in phases}
    print(f"[bench] timer_phase_quiet: "
          f"{ {p: len(d) for p, d in inside.items()} } spans in the "
          f"{len(rounds)} rounds {rounds[0]}..{rounds[-1]} after the slice "
          f"({(hi - lo) * 1e-9:.3f} s)", file=sys.stderr, flush=True)
    if per_span:
        return 1e-6 * sum(sum(d) / len(d) for d in inside.values() if d)
    return 1e-6 * sum(sum(d) for d in inside.values()) / len(rounds)


def read(ctx, phases, per_span=False):
    if not ctx.window.traced:
        return None
    try:
        from fedml_tpu.utils.tracing import recent_spans
    except ImportError:
        return _nothing("this program keeps no spans")
    sliced = ctx.cell.module("readers", "idle_by_program_span").slice_rounds(
        int(ctx.cell.traffic["eval_every"]))
    return quiet_ms(recent_spans(),
                    list(range(sliced[-1] + 1, ctx.window.rounds)), phases,
                    per_span)
