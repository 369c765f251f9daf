"""Device milliseconds per round of the traced slice, averaged over the
chips, of the operations the PROGRAM names: those whose ``fedml.*`` scope
chain (``jax.named_scope``; ``fedml_tpu.utils.tracing.device_scopes``) holds
one of ``scope`` and none of ``exclude`` - or, with ``unscoped``, those
nobody named - inside programs matching ``within_modules`` and outside the
host spans ``outside_spans``.

The trace keeps of a device operation only its name, the whole HLO
instruction (``%fusion.3 = f32[...] fusion(...)``); the program hands out,
for each of its round programs, the scope chain of every instruction of the
optimised HLO (a fusion under the product it holds, an instruction the
compiler inserted under the ``while`` that runs it). The two meet at the
instruction's name and the module's (``jit_round_fn(<fingerprint>)`` ->
``jit_round_fn``); an event counts as known only if its result type and
operation are also the map's for that name (``kinds``: another compilation
of the same program numbers its instructions a little differently). Times
are self times as ``harness/trace.py::op_seconds``
takes them, so an enclosing ``while`` and its body are not counted twice and
the chains' times add up to the programs' whole self time.

Nothing rather than a wrong number: None, with the reason on standard
error, when the program hands out no map (an older tree, a lowering that
failed), when the map knows less than ``COVERAGE`` of the programs' self
time in the slice (the text is not of the executable that ran), or when no
token of ``scope`` occurs anywhere in the map (a mistyped name, or an
executable from a compile cache an older tree filled: the cache's key
leaves the metadata out). The first metric of a run to get that far prints
the table of milliseconds a round by scope chain, longest first, with the
part of each that ran in fusions holding more than one chain (``mixed``:
what the fusion rule decided).
"""

import re
import sys

import numpy as np

from benchmark.harness import trace as tr

#: the share of the programs' self time the map has to know
COVERAGE = 0.99
INSTRUCTION = re.compile(r"^%?([^\s=]+) = (.*)$", re.S)
LAYOUT = re.compile(r"\{[^{}]*\}")
KIND = re.compile(r"^(.*?[\w\-])\(", re.S)
_TABLES = "_scope_ops"


def _nothing(reason):
    print(f"[bench] scope_ops: {reason}; metric left out", file=sys.stderr,
          flush=True)
    return None


def by_chain(trace, window, maps, within_modules, outside_spans=()):
    """``{"whole", "known": seconds, "chains": {chain: [seconds, of them in
    mixed fusions]}, "tokens": the maps' scope names}`` of the operations in
    ``window`` inside the programs matching ``within_modules``, averaged
    over the devices. ``maps`` is ``{module: (chains, mixed, kinds)}``."""
    names = trace["names"]
    instruction, kind = [], []
    for name in names:
        found = INSTRUCTION.match(name)
        head = found and KIND.match(LAYOUT.sub("", found.group(2)))
        instruction.append(found.group(1) if found else None)
        kind.append(head.group(1) if head else "")
    wanted = re.compile(within_modules)
    barred = tr.merge([i for n in outside_spans
                       for i in tr.spans_in(trace, n, window)])
    chains, whole, known, tokens = {}, 0.0, 0.0, set()
    count = max(1, len(trace["devices"]))
    for device in range(len(trace["devices"])):
        ops = tr.events(trace, device, "ops", window)
        programs = tr.events(trace, device, "modules", window)
        allowed = ~ops.inside(barred)
        for program in np.unique(programs.ids):
            if not wanted.search(names[program]):
                continue
            ran = allowed & ops.inside(tr.merge(
                programs.intervals(programs.ids == program)))
            whole += float(ops.self_s[ran].sum()) / count
            scopes = maps.get(names[program].split("(", 1)[0])
            if scopes is None:
                continue
            tokens.update(t for chain in scopes.chains.values()
                          for t in chain)
            by_name = np.bincount(ops.ids[ran], weights=ops.self_s[ran],
                                  minlength=len(names)) / count
            for at in np.flatnonzero(by_name):
                chain = scopes.chains.get(instruction[at])
                if (chain is None
                        or scopes.kinds.get(instruction[at]) != kind[at]):
                    continue
                known += by_name[at]
                total = chains.setdefault(chain, [0.0, 0.0])
                total[0] += by_name[at]
                if instruction[at] in scopes.mixed:
                    total[1] += by_name[at]
    return {"whole": whole, "known": known, "chains": chains,
            "tokens": tokens}


def _print(table, rounds):
    rows = sorted(table["chains"].items(), key=lambda item: -item[1][0])
    share = table["known"] / table["whole"] if table["whole"] else 0.0
    lines = [f"[bench] scope_ops: {1e3 * table['whole'] / rounds:.3f} ms a "
             f"round of device time in the round programs, the map knows "
             f"{100 * share:.3f} % of it; by scope chain (of it in mixed "
             "fusions):"]
    for chain, (seconds, mixed) in rows:
        lines.append(f"[bench]   {1e3 * seconds / rounds:10.3f} ms  "
                     f"({1e3 * mixed / rounds:8.3f})  "
                     f"{' > '.join(chain) or '(unscoped)'}")
    print("\n".join(lines), file=sys.stderr, flush=True)


def read(ctx, scope=(), exclude=(), unscoped=False, within_modules=None,
         outside_spans=()):
    if ctx.trace is None or not ctx.trace_rounds:
        return None
    tables = ctx.trace.setdefault(_TABLES, {})
    key = (within_modules, tuple(outside_spans))
    if key not in tables:
        try:
            from fedml_tpu.utils.tracing import device_scopes
        except ImportError:
            return _nothing("this program hands out no scope map")
        maps = device_scopes()
        if not maps:
            return _nothing("the program handed out no scope map")
        tables[key] = by_chain(ctx.trace, ctx.trace_window, maps,
                               within_modules, outside_spans)
        _print(tables[key], ctx.trace_rounds)
    table = tables[key]
    if not table["whole"]:
        return None
    if table["known"] < COVERAGE * table["whole"]:
        return _nothing(
            f"the map knows {100 * table['known'] / table['whole']:.2f} % of "
            "the round programs' self time in the slice")
    if not unscoped and not set(scope) & table["tokens"]:
        return _nothing(f"no instruction of the map is under {list(scope)}")
    seconds = sum(
        s for chain, (s, _) in table["chains"].items()
        if (not chain if unscoped
            else set(chain) & set(scope) and not set(chain) & set(exclude)))
    return 1e3 * seconds / ctx.trace_rounds
