"""CLI: ``python -m fedml_tpu.analysis [paths...]``.

Default paths are ``fedml_tpu/`` and ``tests/`` under the repo root
(auto-detected: the cwd if it contains ``fedml_tpu/``, else the
package's parent). Five passes share one parse of the tree:

1. AST lint (FT001–FT015, FT020–FT024) + unused-pragma detection
   (FT012 under ``--strict-pragmas``; a warning otherwise);
2. whole-program protocol conformance (FT2xx) with the sender→handler
   graph emitted to ``runs/protocol_graph.json`` and drift-checked
   against the ``ci/protocol_graph.json`` snapshot;
3. round-shape conformance over the ``algorithms/`` driver zoo (FT30x)
   plus flag/env conformance (FT016): the round-engine map lands in
   ``runs/round_engine_map.json`` and is drift-checked against the
   ``ci/round_engine_map.json`` snapshot (accept with
   ``--write-round-map``);
4. resource-lifecycle extraction (FT025): the worker/resource shutdown
   graph lands in ``runs/shutdown_graph.json`` and is drift-checked
   against the ``ci/shutdown_graph.json`` snapshot (accept with
   ``--write-shutdown-graph``);
5. jaxpr audit of registered hot entry points (FT10x) incl. the
   collective-signature check against ``ci/collective_baseline.json``.

``--changed-only [REF]`` lints only files touched vs a git ref
(default HEAD) — the sub-second pre-commit lane; the whole-program
protocol/round-shape/flag passes and the jaxpr audit are skipped there
by construction.

Exit codes: 0 clean (all findings fixed, pragma'd or baselined), 1
active findings, 2 internal error. Human output goes to stdout in
``--format text`` (the default), one JSON report object in ``--format
json``, GitHub Actions ``::error`` annotations in ``--format github``;
``--output`` additionally writes the JSON report as a CI artifact in
any mode.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path
from typing import List, Optional, Set

from fedml_tpu.analysis.baseline import (apply_baseline, load_baseline,
                                         save_baseline)
from fedml_tpu.analysis.lint import (SKIP_DIRS, build_contexts,
                                     lint_contexts, unused_pragmas)


def _repo_root() -> Path:
    cwd = Path.cwd()
    if (cwd / "fedml_tpu").is_dir():
        return cwd
    import fedml_tpu
    return Path(fedml_tpu.__file__).resolve().parent.parent


def _changed_files(root: Path, ref: str,
                   scope: List[Path]) -> Optional[List[Path]]:
    """Python files touched vs ``ref`` (committed diffs, working-tree
    edits, and untracked files), restricted to the requested scope and
    the walker's skip rules. None = git unavailable (caller falls back
    to a full lint, loudly)."""
    def run(*args: str) -> Optional[List[str]]:
        try:
            r = subprocess.run(["git", *args], cwd=root,
                               capture_output=True, text=True, timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            return None
        return r.stdout.split("\n") if r.returncode == 0 else None

    diffed = run("diff", "--name-only", ref, "--")
    if diffed is None:
        return None
    # git reports names relative to the TOPLEVEL, which is not
    # necessarily the analysis root (a repo vendoring the project one
    # level down would otherwise silently lint nothing and pass)
    top = run("rev-parse", "--show-toplevel")
    base = Path(top[0].strip()) if top and top[0].strip() else root
    untracked = run("ls-files", "--others", "--exclude-standard") or []
    scope_resolved = [p.resolve() for p in scope]
    out: List[Path] = []
    for name in sorted({*diffed, *untracked}):
        if not name.endswith(".py"):
            continue
        path = (base / name).resolve()
        if not path.is_file():
            continue  # deleted
        if any(part in SKIP_DIRS for part in path.parts):
            continue
        if scope_resolved and not any(
                p == path or p in path.parents for p in scope_resolved):
            continue
        out.append(path)
    return out


def _print_github(findings, stale, pragma_warnings) -> None:
    for f in findings:
        loc = (f"file={f.path},line={f.line}" if f.line
               else f"file={f.path}")
        msg = f.message.replace("\n", " ")
        print(f"::error {loc},title={f.rule}::{msg}")
    for e in stale:
        print(f"::warning file={e.get('path', '?')},title=stale-baseline::"
              f"baseline entry {e['rule']} ({e['fingerprint']}) matches "
              "nothing — remove it")
    for w in pragma_warnings:
        print(f"::warning file={w['path']},line={w['line']},"
              f"title=unused-pragma::allow[{w['rule']}] suppresses "
              "nothing — delete it")


def main(argv: List[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m fedml_tpu.analysis",
        description="JAX-aware static analysis: AST lint + protocol "
                    "conformance + jaxpr/collective audit")
    parser.add_argument("paths", nargs="*", type=Path,
                        help="files/dirs to lint (default: fedml_tpu/ and "
                             "tests/ under the repo root)")
    parser.add_argument("--format", choices=("text", "json", "github"),
                        default="text")
    parser.add_argument("--baseline", type=Path, default=None,
                        help="baseline JSON; matching findings are "
                             "suppressed, unmatched entries warn stale "
                             "(default: ci/analysis_baseline.json under "
                             "the repo root, when present)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore the default repo baseline")
    parser.add_argument("--write-baseline", type=Path, default=None,
                        help="write the active findings to this baseline "
                             "file and exit 0 (tool-adoption escape hatch)")
    parser.add_argument("--prune-stale", action="store_true",
                        help="rewrite the baseline minus entries that no "
                             "longer match anything (notes on live "
                             "entries preserved) and exit 0")
    parser.add_argument("--no-audit", action="store_true",
                        help="skip the jaxpr audit layer")
    parser.add_argument("--audit-only", action="store_true",
                        help="jaxpr audit only (no lint, no protocol)")
    parser.add_argument("--no-protocol", action="store_true",
                        help="skip the whole-program protocol pass")
    parser.add_argument("--no-roundshape", action="store_true",
                        help="skip the round-shape conformance pass "
                             "(FT30x)")
    parser.add_argument("--no-flags", action="store_true",
                        help="skip the flag/env conformance pass (FT016)")
    parser.add_argument("--no-lifecycle", action="store_true",
                        help="skip the shutdown-graph extraction / drift "
                             "pass (FT025; the FT020-FT024 rules run in "
                             "the lint pass regardless)")
    parser.add_argument("--changed-only", nargs="?", const="HEAD",
                        default=None, metavar="GITREF",
                        help="lint only python files changed vs GITREF "
                             "(default HEAD) — the fast pre-commit lane; "
                             "implies --no-audit --no-protocol (both are "
                             "whole-program passes)")
    parser.add_argument("--strict-pragmas", action="store_true",
                        help="unused pragmas become FT012 findings "
                             "instead of warnings")
    parser.add_argument("--write-protocol-graph", action="store_true",
                        help="refresh ci/protocol_graph.json from the "
                             "current tree (the deliberate way to accept "
                             "a protocol change)")
    parser.add_argument("--write-collective-baseline", action="store_true",
                        help="refresh ci/collective_baseline.json from "
                             "the current audit (accept a collective "
                             "change)")
    parser.add_argument("--write-round-map", action="store_true",
                        help="refresh ci/round_engine_map.json from the "
                             "current tree (the deliberate way to accept "
                             "a round-shape change)")
    parser.add_argument("--write-shutdown-graph", action="store_true",
                        help="refresh ci/shutdown_graph.json from the "
                             "current tree (the deliberate way to accept "
                             "a worker/resource lifecycle change)")
    parser.add_argument("--shutdown-graph-snapshot", type=Path,
                        default=None,
                        help="shutdown-graph snapshot path (default: "
                             "ci/shutdown_graph.json under the root)")
    parser.add_argument("--round-map-snapshot", type=Path, default=None,
                        help="round-shape snapshot path (default: "
                             "ci/round_engine_map.json under the root)")
    parser.add_argument("--protocol-snapshot", type=Path, default=None,
                        help="protocol snapshot path (default: "
                             "ci/protocol_graph.json under the root)")
    parser.add_argument("--collective-baseline", type=Path, default=None,
                        help="collective baseline path (default: "
                             "ci/collective_baseline.json under the root)")
    parser.add_argument("--output", type=Path, default=None,
                        help="also write the JSON report here (CI artifact)")
    parser.add_argument("--list-rules", action="store_true")
    args = parser.parse_args(argv)

    if args.list_rules:
        from fedml_tpu.analysis.rules import rule_table
        for row in rule_table():
            print(f"{row['id']}  {row['title']}\n       fix: {row['hint']}")
        return 0

    root = _repo_root()
    paths = args.paths or [p for p in (root / "fedml_tpu", root / "tests")
                           if p.exists()]
    if args.baseline is None and not args.no_baseline:
        default_bl = root / "ci" / "analysis_baseline.json"
        if default_bl.exists():
            args.baseline = default_bl
    elif args.no_baseline:
        args.baseline = None
    protocol_snapshot = (args.protocol_snapshot
                         or root / "ci" / "protocol_graph.json")
    collective_baseline = (args.collective_baseline
                           or root / "ci" / "collective_baseline.json")
    round_map_snapshot = (args.round_map_snapshot
                          or root / "ci" / "round_engine_map.json")
    shutdown_graph_snapshot = (args.shutdown_graph_snapshot
                               or root / "ci" / "shutdown_graph.json")

    changed_only = args.changed_only is not None
    if changed_only:
        changed = _changed_files(root, args.changed_only, paths)
        if changed is None:
            print(f"WARNING: git diff vs {args.changed_only!r} failed — "
                  "falling back to a full lint", file=sys.stderr)
            changed_only = False
        else:
            paths = changed

    run_lint = not args.audit_only
    run_protocol = (not args.audit_only and not args.no_protocol
                    and not changed_only)
    run_roundshape = (not args.audit_only and not args.no_roundshape
                      and not changed_only)
    run_flags = (not args.audit_only and not args.no_flags
                 and not changed_only)
    run_lifecycle = (not args.audit_only and not args.no_lifecycle
                     and not changed_only)
    run_audit_pass = not args.no_audit and not changed_only

    # the snapshot-refresh flags must apply or fail loudly — a silently
    # ignored --write-* leaves the developer believing a protocol or
    # collective change was accepted when the snapshot never moved
    if args.write_protocol_graph and (not run_protocol or args.paths):
        print("--write-protocol-graph needs the default whole-tree "
              "protocol pass (no explicit paths, no --changed-only / "
              "--no-protocol / --audit-only)", file=sys.stderr)
        return 2
    if args.write_collective_baseline and not run_audit_pass:
        print("--write-collective-baseline needs the audit pass (drop "
              "--no-audit / --changed-only)", file=sys.stderr)
        return 2
    if args.write_round_map and (not run_roundshape or args.paths):
        print("--write-round-map needs the default whole-tree "
              "round-shape pass (no explicit paths, no --changed-only / "
              "--no-roundshape / --audit-only)", file=sys.stderr)
        return 2
    if args.write_shutdown_graph and (not run_lifecycle or args.paths):
        print("--write-shutdown-graph needs the default whole-tree "
              "lifecycle pass (no explicit paths, no --changed-only / "
              "--no-lifecycle / --audit-only)", file=sys.stderr)
        return 2

    findings = []
    ctxs = []
    if run_lint:
        ctxs, findings = build_contexts(paths, root=root)
        from fedml_tpu.analysis.rules import all_rules
        rules = all_rules()
        findings.extend(lint_contexts(ctxs, rules=rules))
        active_rule_ids: Set[str] = {r.id for r in rules}
    else:
        active_rule_ids = set()

    graph = None
    full_walk = not args.paths
    if run_protocol:
        # snapshot comparison + the runs/ artifact only make sense for
        # the DEFAULT whole-tree walk: a partial graph from explicit
        # paths would always "drift" (and must not clobber the artifact)
        if full_walk:
            from fedml_tpu.analysis.protocol import check_protocol
            proto_findings, graph = check_protocol(
                ctxs, protocol_snapshot,
                artifact_path=root / "runs" / "protocol_graph.json",
                write_snapshot=args.write_protocol_graph)
        else:
            from fedml_tpu.analysis.lint import is_test_path
            from fedml_tpu.analysis.protocol import (conformance_findings,
                                                     extract_protocol)
            lib_ctxs = [c for c in ctxs if not is_test_path(c.relpath)]
            graph = extract_protocol(lib_ctxs)
            proto_findings = conformance_findings(graph, lib_ctxs)
        findings.extend(proto_findings)
        active_rule_ids |= {"FT201", "FT202", "FT203"}

    round_map = None
    if run_roundshape:
        from fedml_tpu.analysis import roundshape as rs
        if full_walk:
            # artifact + snapshot only make sense for the default walk
            # (a partial map would always "drift")
            rs_findings, round_map = rs.check_round_shapes(
                ctxs, round_map_snapshot,
                artifact_path=root / "runs" / "round_engine_map.json",
                write_snapshot=args.write_round_map)
            if args.write_round_map:
                print(f"wrote round-engine map snapshot "
                      f"({len(round_map['drivers'])} drivers) to "
                      f"{round_map_snapshot}")
        else:
            analysis = rs.analyze(ctxs)
            rs_findings = rs.conformance_findings(ctxs, analysis=analysis)
            round_map = rs.extract_round_shapes(ctxs, analysis=analysis)
        findings.extend(rs_findings)
        active_rule_ids |= {"FT301", "FT302", "FT303", "FT304"}

    shutdown_graph = None
    if run_lifecycle:
        from fedml_tpu.analysis import lifecycle as lc
        if full_walk:
            # artifact + snapshot only make sense for the default walk
            # (a partial graph would always "drift")
            lc_findings, shutdown_graph = lc.check_lifecycle(
                ctxs, shutdown_graph_snapshot,
                artifact_path=root / "runs" / "shutdown_graph.json",
                write_snapshot=args.write_shutdown_graph)
            if args.write_shutdown_graph:
                print(f"wrote shutdown-graph snapshot "
                      f"({len(shutdown_graph['classes'])} owner classes) "
                      f"to {shutdown_graph_snapshot}")
        else:
            lc_findings = []
            shutdown_graph = lc.extract_shutdown_graph(ctxs)
        findings.extend(lc_findings)
        active_rule_ids |= {"FT025"}

    flags_summary = None
    if run_flags:
        from fedml_tpu.analysis import flagsconf
        from fedml_tpu.analysis.lint import is_test_path
        lib_ctxs = [c for c in ctxs if not is_test_path(c.relpath)]
        extraction = flagsconf.extract_flags(lib_ctxs)
        findings.extend(flagsconf.conformance_findings(
            lib_ctxs, root=root, extraction=extraction))
        flags_summary = flagsconf.flags_report(lib_ctxs,
                                               extraction=extraction)
        active_rule_ids |= {"FT016"}

    audit_reports: List[dict] = []
    collective_stale: List[str] = []
    if run_audit_pass:
        from fedml_tpu.analysis.jaxpr_audit import (
            check_collective_baseline, run_audit,
            write_collective_baseline)
        audit_findings, audit_reports = run_audit()
        findings.extend(audit_findings)
        if args.write_collective_baseline:
            write_collective_baseline(collective_baseline, audit_reports)
            print(f"wrote collective baseline for {len(audit_reports)} "
                  f"entries to {collective_baseline}")
        else:
            coll_findings, collective_stale = check_collective_baseline(
                audit_reports, collective_baseline)
            findings.extend(coll_findings)

    pragma_warnings, pragma_findings = unused_pragmas(
        ctxs, active_rule_ids, strict=args.strict_pragmas)
    findings.extend(pragma_findings)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))

    stale: List[dict] = []
    suppressed = []
    entries: List[dict] = []
    if args.baseline is not None:
        entries = load_baseline(args.baseline)
        findings, suppressed, stale = apply_baseline(findings, entries)
        if changed_only:
            # entries for unscanned files cannot match anything — stale
            # reporting is only meaningful on a full walk
            stale = []

    if args.prune_stale:
        if args.baseline is None:
            print("--prune-stale needs a baseline (none found)",
                  file=sys.stderr)
            return 2
        stale_fps = {e["fingerprint"] for e in stale}
        kept = [e for e in entries if e["fingerprint"] not in stale_fps]
        args.baseline.write_text(json.dumps(
            {"version": 1, "entries": kept}, indent=2) + "\n")
        print(f"pruned {len(stale)} stale entr"
              f"{'y' if len(stale) == 1 else 'ies'} from {args.baseline} "
              f"({len(kept)} kept, notes preserved)")
        return 0

    if args.write_baseline is not None:
        # active AND currently-suppressed findings: refreshing an
        # existing baseline must not drop the entries that are still
        # live in the tree (they were filtered out of `findings` above)
        adopted = sorted(findings + suppressed,
                         key=lambda f: (f.path, f.line, f.rule))
        save_baseline(args.write_baseline, adopted, note="adopted",
                      notes_by_fingerprint={e["fingerprint"]: e.get("note", "")
                                            for e in entries})
        print(f"wrote {len(adopted)} entries to {args.write_baseline}")
        return 0

    report = {
        "findings": [f.to_json() for f in findings],
        "suppressed": [f.to_json() for f in suppressed],
        "stale_baseline": stale,
        "unused_pragmas": pragma_warnings,
        "audit": audit_reports,
        "collective_stale": collective_stale,
        "protocol": ({"types": len(graph["types"]),
                      "senders": sum(len(t["senders"])
                                     for t in graph["types"]),
                      "handlers": sum(len(t["handlers"])
                                      for t in graph["types"])}
                     if graph is not None else None),
        "roundshape": ({"drivers": len(round_map["drivers"]),
                        "kinds": {k: sum(1 for d in round_map["drivers"]
                                         if d["kind"] == k)
                                  for k in sorted({d["kind"] for d in
                                                   round_map["drivers"]})}}
                       if round_map is not None else None),
        "lifecycle": ({"classes": len(shutdown_graph["classes"]),
                       "workers": sum(len(c["workers"]) for c in
                                      shutdown_graph["classes"]),
                       "resources": sum(len(c["resources"]) for c in
                                        shutdown_graph["classes"])}
                      if shutdown_graph is not None else None),
        "flags": flags_summary,
        "counts": {"active": len(findings), "suppressed": len(suppressed),
                   "stale_baseline": len(stale),
                   "unused_pragmas": len(pragma_warnings)},
    }
    if args.output is not None:
        args.output.parent.mkdir(parents=True, exist_ok=True)
        args.output.write_text(json.dumps(report, indent=2) + "\n")

    if args.format == "json":
        print(json.dumps(report, indent=2))
    elif args.format == "github":
        _print_github(findings, stale, pragma_warnings)
        print(f"{len(findings)} active finding(s), "
              f"{len(suppressed)} baselined")
    else:
        for f in findings:
            print(f.format_text())
        for e in stale:
            print(f"WARNING: stale baseline entry {e['rule']} "
                  f"{e.get('path', '?')} ({e['fingerprint']}) matches "
                  "nothing — the code was fixed; remove the entry "
                  "(or run --prune-stale)")
        for w in pragma_warnings:
            print(f"WARNING: unused pragma {w['path']}:{w['line']} "
                  f"allow[{w['rule']}] suppresses nothing — delete it "
                  "(--strict-pragmas makes this a finding)")
        for name in collective_stale:
            print(f"WARNING: collective baseline entry {name} matches "
                  "no registered entry point — refresh with "
                  "--write-collective-baseline")
        if graph is not None:
            dest = (" -> runs/protocol_graph.json" if full_walk
                    else " (partial walk: no artifact/snapshot check)")
            print(f"protocol: {report['protocol']['types']} msg types, "
                  f"{report['protocol']['senders']} send site(s), "
                  f"{report['protocol']['handlers']} handler(s){dest}")
        if round_map is not None:
            dest = (" -> runs/round_engine_map.json" if full_walk
                    else " (partial walk: no artifact/snapshot check)")
            kinds = report["roundshape"]["kinds"]
            print(f"round-shape: {report['roundshape']['drivers']} "
                  f"driver(s) ("
                  + ", ".join(f"{v} {k}" for k, v in kinds.items())
                  + f"){dest}")
        if shutdown_graph is not None:
            dest = (" -> runs/shutdown_graph.json" if full_walk
                    else " (partial walk: no artifact/snapshot check)")
            print(f"lifecycle: {report['lifecycle']['classes']} owner "
                  f"class(es), {report['lifecycle']['workers']} "
                  f"worker(s), {report['lifecycle']['resources']} "
                  f"resource(s){dest}")
        if flags_summary is not None:
            print(f"flags: {flags_summary['flags_defined']} defined "
                  f"({flags_summary['flags_shared']} shared), "
                  f"{len(flags_summary['env_reads'])} documented-env "
                  "knob(s)")
        for rep in audit_reports:
            coll = ", ".join(
                f"{c['op']}{tuple(c['axes'])}x{c['count']}"
                for c in rep.get("collectives", [])) or "none"
            print(f"audit: {rep['entry']}: {rep['n_lowering_keys']} "
                  f"lowering key(s) over {rep['sweep_len']}-point sweep, "
                  f"{rep['n_eqns']} top-level eqns, collectives: {coll}")
        n = len(findings)
        print(f"{n} active finding(s), {len(suppressed)} baselined, "
              f"{len(stale)} stale baseline entr"
              f"{'y' if len(stale) == 1 else 'ies'}, "
              f"{len(pragma_warnings)} unused pragma(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except KeyboardInterrupt:  # pragma: no cover
        sys.exit(130)
    except Exception:  # the documented "internal error" exit: a crash
        import traceback  # (malformed baseline, unreadable output dir)
        traceback.print_exc()  # must be distinguishable from "findings"
        sys.exit(2)
