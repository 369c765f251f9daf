"""Layer 2 — jaxpr audit of registered hot entry points.

Where the AST lint sees spelling, this layer sees the program XLA will
actually receive: each registered entry point (analysis/registry.py) is
traced with ``jax.make_jaxpr`` over its declared argument sweep and the
closed jaxpr is walked recursively (through pjit / scan / while /
custom-vjp sub-jaxprs) for the hazard classes the project has been
bitten by:

- **FT101** — a float64 aval anywhere under x64-off intent: under
  x64-off jax truncates it silently (an intent bug wearing f32
  clothes); under x64-on it is a 2x bandwidth tax.
- **FT102** — ``pure_callback`` / ``io_callback`` / ``debug_callback``
  inside a ``scan``/``while`` body: a host round-trip per iteration,
  i.e. a fused R-round scan degenerates to R host syncs.
- **FT103** — ``convert_element_type`` float upcasts inside a
  grad-declared program (accidental mixed-precision promotion on the
  backward path; checked more strictly than forward-only entries,
  which only flag upcasts landing in f64).
- **FT104** — distinct lowering keys across the declared sweep: the
  r5 bench artifact class. The key is the tuple of input avals
  (shape, dtype, weak_type) — exactly what jit caches on — so a weak
  vs strong scalar, a flipped dtype, or a shape drift between rounds
  shows up as key count > ``max_lowerings`` and fails CI instead of a
  bench window.
- **FT105/FT106** — collective-signature drift: each entry's traced
  program yields a *collective signature* — every ``psum`` /
  ``all_gather`` / ``ppermute`` / ``reduce_scatter`` / ... eqn with its
  axis names, eqn count, and estimated output bytes — checked against
  the fingerprinted ``ci/collective_baseline.json``. A new unsolicited
  collective, a changed axis, or a changed count is FT105; a bytes
  estimate drifting beyond ``BYTES_TOLERANCE`` is FT106. This is the
  ROADMAP SPMD item's CI guard: when the multi-chip mesh lands, a
  sharded lowering that silently grows an all-gather fails lint, not a
  bench. Regenerate deliberately with ``--write-collective-baseline``.
"""

from __future__ import annotations

import hashlib
import json
import logging
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.extend import core as _jcore

from fedml_tpu.analysis.finding import Finding, audit_finding
from fedml_tpu.analysis.registry import AuditSpec, load_entry_points

LOOP_PRIMITIVES = frozenset({"scan", "while"})
CALLBACK_PRIMITIVES = frozenset(
    {"pure_callback", "io_callback", "debug_callback"})

#: cross-device communication primitives (the collective signature),
#: keyed by traced primitive name -> the public name the baseline records.
#: Inside ``shard_map`` JAX 0.9 traces ``lax.psum`` as ``psum_invariant``
#: (and the typed all_gather/reduce_scatter forms as the siblings below);
#: they are the same wire operation, so they report under the public name.
COLLECTIVE_PRIMITIVES = {
    **{name: name for name in (
        "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
        "all_to_all", "reduce_scatter", "psum_scatter", "pgather")},
    "psum_invariant": "psum",
    "unreduced_psum": "psum",
    "all_gather_invariant": "all_gather",
    "all_gather_reduced": "all_gather",
    "unreduced_reduce_scatter": "reduce_scatter",
}

#: FT106 fires when an entry's per-(op, axes) bytes estimate grows or
#: shrinks beyond this factor vs the baseline (shape-tolerant: model or
#: batch tweaks within 1.5x pass; a 4x all-gather blowup does not)
BYTES_TOLERANCE = 1.5

COLLECTIVE_BASELINE_VERSION = 1


def _sub_jaxprs(eqn) -> List[Any]:
    """Every Jaxpr/ClosedJaxpr nested in an eqn's params (pjit's
    ``jaxpr``, scan's ``jaxpr``, while's ``cond_jaxpr``/``body_jaxpr``,
    custom-vjp's ``fun_jaxpr``, branches tuples, ...)."""
    out: List[Any] = []
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, (_jcore.Jaxpr, _jcore.ClosedJaxpr)):
                out.append(v)
    return out


def _as_jaxpr(j):
    return j.jaxpr if isinstance(j, _jcore.ClosedJaxpr) else j


def _walk(jaxpr, in_loop: bool, visit) -> None:
    """DFS over eqns; ``visit(eqn, in_loop)``; loop flag set below
    scan/while."""
    for eqn in _as_jaxpr(jaxpr).eqns:
        visit(eqn, in_loop)
        child_in_loop = in_loop or eqn.primitive.name in LOOP_PRIMITIVES
        for sub in _sub_jaxprs(eqn):
            _walk(sub, child_in_loop, visit)


def _aval_key(aval) -> Tuple:
    return (str(getattr(aval, "shape", None)),
            str(getattr(aval, "dtype", None)),
            bool(getattr(aval, "weak_type", False)))


def signature_key(closed) -> Tuple:
    """The lowering key of a traced call: input avals incl. weak_type —
    the same equivalence jit's compile cache uses."""
    return tuple(_aval_key(v.aval) for v in _as_jaxpr(closed).invars)


def _is_f64(aval) -> bool:
    return str(getattr(aval, "dtype", "")) == "float64"


def _float_width(dtype) -> Optional[int]:
    s = str(dtype)
    if s in ("float16", "bfloat16"):
        return 16
    if s == "float32":
        return 32
    if s == "float64":
        return 64
    return None


def audit_spec(name: str, spec: AuditSpec) -> Tuple[List[Finding], Dict]:
    """Trace + walk one entry point. Returns (findings, report) where
    report carries the evidence CI artifacts and tests assert on:
    ``n_lowering_keys``, ``n_eqns``, ``sweep_len``."""
    findings: List[Finding] = []
    keys = []
    jaxprs = []
    for args in spec.sweep:
        closed = jax.make_jaxpr(spec.fn)(*args)
        jaxprs.append(closed)
        keys.append(signature_key(closed))
    distinct = sorted(set(keys), key=keys.index)
    if len(distinct) > spec.max_lowerings:
        findings.append(audit_finding(
            "FT104", name,
            f"{len(distinct)} distinct lowering keys across the declared "
            f"{len(spec.sweep)}-point sweep (contract: "
            f"<= {spec.max_lowerings}) — each extra key is a recompile "
            "landing at an uncontrolled moment",
            hint="align the callers' arg dtypes/weak-types (jnp-typed "
                 "scalars) or mark program-variant args static",
            detail="; ".join(repr(k) for k in distinct[:4])))

    f64_seen: List[str] = []
    callback_in_loop: List[str] = []
    upcasts: List[str] = []
    #: (op, axes) -> [eqn count, output bytes] — the collective
    #: signature, collected from the FIRST trace only so the numbers do
    #: not scale with sweep length (signature stability across the
    #: sweep is FT104's job)
    collectives: Dict[Tuple[str, Tuple[str, ...]], List[int]] = {}
    _first_walk = [True]

    def _collective_axes(eqn) -> Tuple[str, ...]:
        axes = eqn.params.get("axes", eqn.params.get("axis_name"))
        if axes is None:
            return ()
        if not isinstance(axes, (tuple, list)):
            axes = (axes,)
        return tuple(sorted(str(a) for a in axes))

    def visit(eqn, in_loop: bool) -> None:
        prim = eqn.primitive.name
        if prim in COLLECTIVE_PRIMITIVES and _first_walk[0]:
            key = (COLLECTIVE_PRIMITIVES[prim], _collective_axes(eqn))
            entry = collectives.setdefault(key, [0, 0])
            entry[0] += 1
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                shape = getattr(aval, "shape", None)
                dtype = getattr(aval, "dtype", None)
                if shape is None or dtype is None:
                    continue
                n = 1
                for d in shape:
                    n *= int(d)
                entry[1] += n * dtype.itemsize
        if prim in CALLBACK_PRIMITIVES and in_loop:
            callback_in_loop.append(prim)
        if not spec.allow_f64:
            for v in eqn.outvars:
                if _is_f64(getattr(v, "aval", None)):
                    f64_seen.append(prim)
                    break
        if prim == "convert_element_type":
            old = _float_width(getattr(eqn.invars[0].aval, "dtype", None))
            new = _float_width(eqn.params.get("new_dtype"))
            if old and new and new > old and (spec.grad_path or new == 64):
                upcasts.append(
                    f"{eqn.invars[0].aval.dtype}->{eqn.params['new_dtype']}")

    # hazard-walk ONE representative jaxpr per distinct lowering key —
    # with max_lowerings > 1 a hazard may live only in the program a
    # later sweep point traces (different branch/shape), and walking
    # only jaxprs[0] would report the entry clean
    walked_keys = set()
    for key, closed in zip(keys, jaxprs):
        if key in walked_keys:
            continue
        walked_keys.add(key)
        _walk(closed, False, visit)
        _first_walk[0] = False
        if not spec.allow_f64:
            for v in _as_jaxpr(closed).invars + _as_jaxpr(closed).outvars:
                if _is_f64(getattr(v, "aval", None)):
                    f64_seen.append("(entry boundary)")
                    break
    closed = jaxprs[0]  # report shape metadata from the first trace

    if f64_seen:
        findings.append(audit_finding(
            "FT101", name,
            f"float64 result(s) in the traced program (first at: "
            f"{f64_seen[0]}) under x64-off intent — silently truncated "
            "today, a 2x bandwidth tax the day x64 is enabled",
            hint="pin the literal/dtype to f32, or set allow_f64=True on "
                 "the AuditSpec if this entry means it",
            detail=",".join(f64_seen[:6])))
    if callback_in_loop:
        findings.append(audit_finding(
            "FT102", name,
            f"host callback ({callback_in_loop[0]}) inside a scan/while "
            "body — one host round-trip per iteration defeats the fused "
            "round scan",
            hint="hoist the callback out of the loop body, or debug with "
                 "jax.debug.print only in non-fused paths",
            detail=",".join(sorted(set(callback_in_loop)))))
    if upcasts:
        findings.append(audit_finding(
            "FT103", name,
            f"float upcast(s) on the traced path of a grad-declared "
            f"entry: {', '.join(sorted(set(upcasts))[:4])}",
            hint="make the accumulation dtype explicit at the cast site "
                 "(preferred) or declare the entry forward-only",
            detail=",".join(sorted(set(upcasts)))))

    report = {"entry": name, "sweep_len": len(spec.sweep),
              "n_lowering_keys": len(distinct),
              "max_lowerings": spec.max_lowerings,
              "n_eqns": len(_as_jaxpr(closed).eqns),
              "grad_path": spec.grad_path,
              "collectives": [
                  {"op": op, "axes": list(axes), "count": cnt,
                   "bytes": nbytes}
                  for (op, axes), (cnt, nbytes) in sorted(
                      collectives.items())]}
    return findings, report


# -- collective-signature baseline (FT105/FT106) -----------------------------

def collective_signature(report: Dict) -> List[Dict]:
    return report.get("collectives", [])


def _signature_fingerprint(collectives: List[Dict]) -> str:
    blob = json.dumps(collectives, sort_keys=True)
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def write_collective_baseline(path: Path, reports: Sequence[Dict]) -> None:
    """Snapshot every audited entry's collective signature (op + axes +
    count + bytes, fingerprinted) — the deliberate, reviewable way to
    accept a collective change."""
    entries = {}
    for rep in reports:
        sig = collective_signature(rep)
        entries[rep["entry"]] = {
            "collectives": sig,
            "fingerprint": _signature_fingerprint(sig)}
    payload = {"version": COLLECTIVE_BASELINE_VERSION, "entries": entries}
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")


def check_collective_baseline(reports: Sequence[Dict], path: Path
                              ) -> Tuple[List[Finding], List[str]]:
    """-> (findings, stale_entry_names) vs ``ci/collective_baseline.json``.

    A missing or unreadable baseline is a LOUD FT105 — a deleted
    snapshot must fail CI, never silently skip the drift check. A
    baseline entry whose entry point no longer exists is stale (warn,
    like stale finding-baseline entries)."""
    path = Path(path)
    regen = ("accept deliberately: python -m fedml_tpu.analysis "
             "--write-collective-baseline")
    if not path.exists():
        return [audit_finding(
            "FT105", "<baseline>",
            f"collective baseline {path} is MISSING — collective-"
            "signature drift cannot be checked, and a silently skipped "
            "check is the failure mode this audit exists to prevent",
            hint=regen)], []
    try:
        data = json.loads(path.read_text())
        if data.get("version") != COLLECTIVE_BASELINE_VERSION:
            raise ValueError(
                f"unsupported version {data.get('version')!r}")
        baseline = data["entries"]
    except (OSError, ValueError, KeyError) as exc:
        return [audit_finding(
            "FT105", "<baseline>",
            f"collective baseline {path} is unreadable ({exc}) — "
            "regenerate it", hint=regen)], []
    findings: List[Finding] = []
    seen = set()
    for rep in reports:
        name = rep["entry"]
        seen.add(name)
        sig = collective_signature(rep)
        base = baseline.get(name)
        if base is None:
            findings.append(audit_finding(
                "FT105", name,
                "entry point has no collective-baseline entry — every "
                "registered hot entry point must be covered so a new "
                "collective cannot land unreviewed", hint=regen,
                detail=_signature_fingerprint(sig)))
            continue
        if base.get("fingerprint") == _signature_fingerprint(sig):
            continue
        by_key_new = {(c["op"], tuple(c["axes"])): c for c in sig}
        by_key_old = {(c["op"], tuple(c["axes"])): c
                      for c in base.get("collectives", [])}
        for key in sorted(set(by_key_new) - set(by_key_old)):
            c = by_key_new[key]
            findings.append(audit_finding(
                "FT105", name,
                f"NEW collective {c['op']} over axes {c['axes']} "
                f"({c['count']} eqn(s), ~{c['bytes']} bytes) not in the "
                "baseline — an unsolicited cross-device transfer on the "
                "hot path", hint=regen,
                detail=f"+{c['op']}{c['axes']}"))
        for key in sorted(set(by_key_old) - set(by_key_new)):
            c = by_key_old[key]
            findings.append(audit_finding(
                "FT105", name,
                f"collective {c['op']} over axes {c['axes']} DISAPPEARED "
                "from the traced program — an aggregation the protocol "
                "depends on may have been sharded away", hint=regen,
                detail=f"-{c['op']}{c['axes']}"))
        for key in sorted(set(by_key_old) & set(by_key_new)):
            new, old = by_key_new[key], by_key_old[key]
            if new["count"] != old["count"]:
                    findings.append(audit_finding(
                    "FT105", name,
                    f"collective {new['op']} over axes {new['axes']} "
                    f"changed eqn count {old['count']} -> "
                    f"{new['count']}", hint=regen,
                    detail=f"{new['op']}{new['axes']} "
                           f"count {old['count']}->{new['count']}"))
            elif old["bytes"] and not (
                    1.0 / BYTES_TOLERANCE
                    <= new["bytes"] / old["bytes"]
                    <= BYTES_TOLERANCE):
                    findings.append(audit_finding(
                    "FT106", name,
                    f"collective {new['op']} over axes {new['axes']} "
                    f"bytes estimate drifted {old['bytes']} -> "
                    f"{new['bytes']} (tolerance {BYTES_TOLERANCE}x) — "
                    "a sharding or batching change moved real "
                    "interconnect traffic", hint=regen,
                    detail=f"{new['op']}{new['axes']} "
                           f"{old['bytes']}->{new['bytes']}"))
        # fingerprint moved but no per-key drift: bytes changed WITHIN
        # tolerance — exactly what BYTES_TOLERANCE exists to absorb, so
        # not a finding (the per-key checks above are the real compare;
        # the fingerprint is only a fast-path short-circuit, and the
        # stored one re-pins on the next deliberate regen)
    stale = sorted(set(baseline) - seen)
    return findings, stale


def run_audit(only: Optional[Sequence[str]] = None
              ) -> Tuple[List[Finding], List[Dict]]:
    """Build + audit every registered entry point (or the ``only``
    subset). A builder/trace crash is a loud FT100 finding, never a
    silently shorter audit."""
    entries = load_entry_points()
    findings: List[Finding] = []
    reports: List[Dict] = []
    for name in sorted(entries):
        if only and name not in only:
            continue
        try:
            spec = entries[name]()
            got, report = audit_spec(name, spec)
        except Exception as exc:
            logging.exception("jaxpr audit: entry %s failed", name)
            findings.append(audit_finding(
                "FT100", name,
                f"entry point failed to build/trace: {type(exc).__name__}: "
                f"{exc}",
                hint="an auditable entry must stay traceable on the CPU CI "
                     "backend; fix the builder or the program"))
            continue
        findings.extend(got)
        reports.append(report)
    return findings, reports
