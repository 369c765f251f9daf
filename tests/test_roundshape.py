"""Round-shape conformance (FT30x), determinism lints (FT013-FT015),
and flag/env conformance (FT016) — the pass-level behavior the corpus
pairs cannot express: whole-map coverage over the shipped driver zoo,
snapshot presence/drift (FT300/FT305), inheritance resolution, and the
flags extractor's AST-level read detection.
"""

import json
from pathlib import Path

import pytest

from fedml_tpu.analysis import flagsconf
from fedml_tpu.analysis import roundshape as rs
from fedml_tpu.analysis.lint import build_contexts, lint_contexts
from fedml_tpu.analysis.rules.determinism import (FsEnumOrderRule,
                                                  SetIterationOrderRule,
                                                  WallClockControlFlowRule)

REPO = Path(__file__).resolve().parent.parent
ALGOS = REPO / "fedml_tpu" / "algorithms"


def _tree_ctxs():
    ctxs, errs = build_contexts([REPO / "fedml_tpu"], root=REPO)
    assert errs == []
    return ctxs


@pytest.fixture(scope="module")
def shipped_map():
    return rs.extract_round_shapes(_tree_ctxs())


class TestShippedMap:
    """The acceptance bar: the map covers every algorithms/ file with
    every stage resolved — no 'unknown' anywhere."""

    def test_covers_all_driver_files(self, shipped_map):
        mapped = {d["module"].rsplit(".", 1)[-1] if not
                  d["module"].endswith("algorithms") else "__init__"
                  for d in shipped_map["drivers"]}
        on_disk = {p.stem for p in ALGOS.glob("*.py")}
        assert mapped == on_disk
        assert len(shipped_map["drivers"]) == len(list(ALGOS.glob("*.py")))

    def test_no_unknown_stages(self, shipped_map):
        for d in shipped_map["drivers"]:
            for stage, info in d["stages"].items():
                assert info["hook"] != "unknown", (d["module"], stage)
                assert info["via"] != "unresolved", (d["module"], stage)

    def test_flagship_driver_shape(self, shipped_map):
        by_mod = {d["module"].rsplit(".", 1)[-1]: d
                  for d in shipped_map["drivers"]}
        fedavg = by_mod["fedavg"]["stages"]
        assert fedavg["sampling"]["hook"] == "seeded_host_sampler"
        assert fedavg["pack"]["hook"] == "pad_and_mask_pack"
        assert "RoundPrefetcher" in fedavg["pack"]["prefetch"]
        assert fedavg["aggregate"]["hook"] == "sample_weighted_mean"
        cs = by_mod["fedavg_cross_silo"]["stages"]
        assert cs["comm"]["hook"] == "actor_messages"
        assert cs["failure"]["hook"] == "liveness_deadline_rejoin"
        for h in ("liveness", "deadline", "rejoin", "heartbeat"):
            assert h in cs["failure"]["hooks"]
        assert by_mod["fednova"]["stages"]["aggregate"]["hook"] == \
            "normalized_grad_recombination"
        assert by_mod["turboaggregate"]["stages"]["aggregate"]["hook"] == \
            "secure_additive_shares"

    def test_subclass_drivers_inherit_skeleton_stages(self, shipped_map):
        by_mod = {d["module"].rsplit(".", 1)[-1]: d
                  for d in shipped_map["drivers"]}
        for name in ("fedopt", "fedavg_robust", "fedseg"):
            samp = by_mod[name]["stages"]["sampling"]
            assert samp["hook"] == "seeded_host_sampler"
            assert samp["via"].startswith("inherited:"), (name, samp)
            assert samp["via"].endswith(".fedavg")

    def test_shipped_snapshot_matches_tree(self, shipped_map):
        snap = json.loads((REPO / "ci" / "round_engine_map.json")
                          .read_text())
        assert snap["fingerprint"] == \
            rs.normalize_map(shipped_map)["fingerprint"]

    def test_snapshot_is_line_free(self):
        snap = json.loads((REPO / "ci" / "round_engine_map.json")
                          .read_text())
        blob = json.dumps(snap)
        assert '"line"' not in blob and '"path"' not in blob


class TestSnapshotFindings:
    def test_missing_snapshot_is_loud_ft300(self, shipped_map, tmp_path):
        findings = rs.snapshot_findings(shipped_map,
                                        tmp_path / "missing.json")
        assert [f.rule for f in findings] == ["FT300"]
        assert "MISSING" in findings[0].message

    def test_unreadable_snapshot_is_ft300(self, shipped_map, tmp_path):
        bad = tmp_path / "map.json"
        bad.write_text("{not json")
        findings = rs.snapshot_findings(shipped_map, bad)
        assert [f.rule for f in findings] == ["FT300"]

    def test_drift_is_ft305_with_driver_detail(self, shipped_map,
                                               tmp_path):
        norm = rs.normalize_map(shipped_map)
        for d in norm["drivers"]:
            if d["module"].endswith(".fednova"):
                d["stages"]["aggregate"]["hook"] = "sample_weighted_mean"
        # the stored fingerprint must describe the stored stages, as a
        # real (drifted) snapshot's would
        norm["fingerprint"] = rs.normalize_map(
            {"drivers": [dict(d) for d in norm["drivers"]]})["fingerprint"]
        snap = tmp_path / "map.json"
        snap.write_text(json.dumps(norm))
        findings = rs.snapshot_findings(shipped_map, snap)
        assert [f.rule for f in findings] == ["FT305"]
        assert "fednova" in findings[0].message
        assert "aggregate" in findings[0].message

    def test_matching_snapshot_is_clean(self, shipped_map, tmp_path):
        snap = tmp_path / "map.json"
        snap.write_text(json.dumps(rs.normalize_map(shipped_map)))
        assert rs.snapshot_findings(shipped_map, snap) == []

    def test_fingerprint_survives_line_shifts(self, tmp_path):
        # the snapshot must not drift when a driver gains comment lines
        src = ("FT_ROUNDSHAPE_DRIVER = True\n"
               "from fedml_tpu.core.sampling import sample_clients\n"
               "class A:\n"
               "    def run_round(self, r):\n"
               "        return sample_clients(r, 10, 4)\n")
        d1 = tmp_path / "a"
        d1.mkdir()
        (d1 / "drv.py").write_text(src)
        d2 = tmp_path / "b"
        d2.mkdir()
        (d2 / "drv.py").write_text("# pad\n# pad\n" + src)
        fp = []
        for d in (d1, d2):
            ctxs, _ = build_contexts([d], root=tmp_path)
            m = rs.extract_round_shapes(ctxs)
            norm = rs.normalize_map(m)
            # path differs (a/ vs b/) but module name is what's keyed;
            # normalize module to compare shape-only
            for drv in norm["drivers"]:
                drv["module"] = "drv"
            blob = json.dumps(
                {"drivers": sorted(norm["drivers"],
                                   key=lambda x: x["module"])},
                sort_keys=True)
            fp.append(blob)
        assert fp[0] == fp[1]


class TestConformanceRules:
    def _findings(self, tmp_path, src):
        p = tmp_path / "driver.py"
        p.write_text(src)
        ctxs, _ = build_contexts([p], root=tmp_path)
        return rs.conformance_findings(ctxs)

    def test_non_driver_modules_are_exempt(self, tmp_path):
        # same violation, no driver marker, not under algorithms/
        src = ("import os\n"
               "KNOB = os.environ.get('X')\n")
        assert self._findings(tmp_path, src) == []

    def test_ft304_fires_under_algorithms_dir(self, tmp_path):
        algos = tmp_path / "algorithms"
        algos.mkdir()
        (algos / "drv.py").write_text(
            "import os\nKNOB = os.environ.get('X')\n")
        ctxs, _ = build_contexts([algos], root=tmp_path)
        assert [f.rule for f in rs.conformance_findings(ctxs)] == ["FT304"]

    def test_ft303_sees_every_same_named_hook_and_kwonly(self, tmp_path):
        # two classes defining the same hook name: the weight-dropping
        # SECOND one must still be checked; keyword-only weights count
        algos = tmp_path / "algorithms"
        algos.mkdir()
        (algos / "drv.py").write_text(
            "class A:\n"
            "    def aggregate_hook(self, stacked, weights):\n"
            "        return (stacked * weights).sum(0) / weights.sum()\n"
            "class B:\n"
            "    def aggregate_hook(self, stacked, *, weights):\n"
            "        return stacked.mean(0)\n")
        ctxs, _ = build_contexts([algos], root=tmp_path)
        findings = rs.conformance_findings(ctxs)
        assert [f.rule for f in findings] == ["FT303"]
        assert findings[0].line == 5

    def test_ft301_home_module_is_exempt(self, tmp_path):
        # fedavg.py defining make_vmapped_body is the canonical home
        algos = tmp_path / "algorithms"
        algos.mkdir()
        (algos / "fedavg.py").write_text(
            "def make_vmapped_body(local_train):\n    return local_train\n")
        assert rs.conformance_findings(
            build_contexts([algos], root=tmp_path)[0]) == []

    def test_shipped_drivers_have_no_active_findings(self):
        # FT30x true positives in the shipped tree are fixed or carry a
        # rationale pragma — the acceptance criterion for this pass
        ctxs = _tree_ctxs()
        assert rs.conformance_findings(ctxs) == []

    def test_pragmas_on_shipped_divergences_are_consumed(self):
        # fednova + hierarchical carry FT302 pragmas, robust an FT303 —
        # the rule must still FIRE there (else strict pragmas go stale)
        ctxs = _tree_ctxs()
        rs.conformance_findings(ctxs)  # pragma use is recorded per run
        fired = {}
        for ctx in ctxs:
            for line, rules in ctx.pragmas_used.items():
                for r in rules:
                    if r.startswith("FT30"):
                        fired.setdefault(r, set()).add(
                            Path(ctx.relpath).stem)
        assert "fednova" in fired.get("FT302", set())
        assert "hierarchical" in fired.get("FT302", set())
        assert "fedavg_robust" in fired.get("FT303", set())


class TestFlagsConformance:
    def _findings(self, tmp_path, files):
        for name, src in files.items():
            (tmp_path / name).write_text(src)
        ctxs, _ = build_contexts([tmp_path], root=tmp_path)
        return flagsconf.conformance_findings(ctxs, root=tmp_path)

    def test_dead_flag_fires(self, tmp_path):
        findings = self._findings(tmp_path, {"launch.py": (
            "import argparse\n"
            "p = argparse.ArgumentParser()\n"
            "p.add_argument('--dead', type=int)\n")})
        assert [f.rule for f in findings] == ["FT016"]
        assert "--dead" in findings[0].message

    def test_multiline_getattr_read_counts(self, tmp_path):
        # the regression that motivated AST-based reads: a getattr split
        # across lines (experiments/main_fedavg.py's idiom)
        findings = self._findings(tmp_path, {"launch.py": (
            "import argparse\n"
            "p = argparse.ArgumentParser()\n"
            "p.add_argument('--eval_sub', type=int)\n"
            "args = p.parse_args()\n"
            "v = getattr(\n"
            "    args, 'eval_sub', None)\n")})
        assert findings == []

    def test_dest_override_is_respected(self, tmp_path):
        findings = self._findings(tmp_path, {"launch.py": (
            "import argparse\n"
            "p = argparse.ArgumentParser()\n"
            "p.add_argument('--flag-name', dest='alias', type=int)\n"
            "args = p.parse_args()\n"
            "print(args.alias)\n")})
        assert findings == []

    def test_undocumented_env_knob_fires_with_readme(self, tmp_path):
        (tmp_path / "README.md").write_text("# docs\nFEDML_TPU_GOOD\n")
        findings = self._findings(tmp_path, {"mod.py": (
            "import os\n"
            "A = os.environ.get('FEDML_TPU_GOOD')\n"
            "B = os.environ.get('FEDML_TPU_SECRET')\n")})
        assert [f.rule for f in findings] == ["FT016"]
        assert "FEDML_TPU_SECRET" in findings[0].message

    def test_env_read_through_module_constant_resolves(self, tmp_path):
        (tmp_path / "README.md").write_text("# docs\n")
        findings = self._findings(tmp_path, {"mod.py": (
            "import os\n"
            "ENV_VAR = 'FEDML_TPU_CONST_KNOB'\n"
            "A = os.environ.get(ENV_VAR)\n")})
        assert [f.rule for f in findings] == ["FT016"]
        assert "FEDML_TPU_CONST_KNOB" in findings[0].message

    def test_no_readme_skips_doc_checks(self, tmp_path):
        findings = self._findings(tmp_path, {"mod.py": (
            "import os\n"
            "B = os.environ.get('FEDML_TPU_SECRET')\n")})
        assert findings == []

    def test_attribute_store_is_not_a_read(self, tmp_path):
        # a config field ASSIGNMENT of the same name must not launder a
        # dead flag — only Load contexts count as consumption
        findings = self._findings(tmp_path, {"launch.py": (
            "import argparse\n"
            "p = argparse.ArgumentParser()\n"
            "p.add_argument('--totally_dead', type=int)\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.totally_dead = 1\n")})
        assert [f.rule for f in findings] == ["FT016"]

    def test_shipped_tree_is_conformant(self):
        # every shared flag read + in the README table; every
        # $FEDML_TPU_* env read documented — the FT016 acceptance bar
        ctxs = _tree_ctxs()
        assert flagsconf.conformance_findings(ctxs, root=REPO) == []

    def test_shipped_env_knobs_are_extracted(self):
        report = flagsconf.flags_report(_tree_ctxs())
        assert report["flags_shared"] >= 44
        assert set(report["env_reads"]) >= {
            "FEDML_TPU_COMPRESSION",
            "FEDML_TPU_PREFETCH", "FEDML_TPU_AUTOTUNE",
            "FEDML_TPU_AUTOTUNE_CACHE",
            "FEDML_TPU_VIRTUAL_SAMPLE_THRESHOLD"}


class TestDeterminismRuleEdges:
    def _lint(self, tmp_path, src):
        p = tmp_path / "mod.py"
        p.write_text(src)
        ctxs, _ = build_contexts([p], root=tmp_path)
        return lint_contexts(ctxs, rules=[FsEnumOrderRule(),
                                          SetIterationOrderRule(),
                                          WallClockControlFlowRule()])

    def test_sorted_and_set_wrappers_clear_ft013(self, tmp_path):
        assert self._lint(tmp_path, (
            "import os\n"
            "a = sorted(os.listdir('.'))\n"
            "b = set(os.listdir('.'))\n"
            "c = sorted(x for x in os.listdir('.'))\n")) == []

    def test_path_glob_fires_ft013(self, tmp_path):
        findings = self._lint(tmp_path, (
            "from pathlib import Path\n"
            "def f(d):\n"
            "    return [p for p in Path(d).glob('*.npz')]\n"))
        assert [f.rule for f in findings] == ["FT013"]

    def test_self_attr_set_iteration_fires_ft014(self, tmp_path):
        findings = self._lint(tmp_path, (
            "class T:\n"
            "    def __init__(self):\n"
            "        self._live = set()\n"
            "    def emit(self, send):\n"
            "        for w in self._live:\n"
            "            send(w)\n"))
        assert [f.rule for f in findings] == ["FT014"]

    def test_membership_only_set_loop_is_quiet(self, tmp_path):
        # no accumulation/emission in the body: order cannot matter
        assert self._lint(tmp_path, (
            "def f(items):\n"
            "    s = set(items)\n"
            "    for x in s:\n"
            "        if x is None:\n"
            "            return True\n"
            "    return False\n")) == []

    def test_bare_import_monotonic_fires_ft015(self, tmp_path):
        findings = self._lint(tmp_path, (
            "from time import monotonic\n"
            "def f(deadline):\n"
            "    if monotonic() > deadline:\n"
            "        return 'late'\n"))
        assert [f.rule for f in findings] == ["FT015"]

    def test_clock_through_local_variable_fires_ft015(self, tmp_path):
        findings = self._lint(tmp_path, (
            "import time\n"
            "def f(t0):\n"
            "    waited = time.monotonic() - t0\n"
            "    if waited > 3:\n"
            "        return 'late'\n"))
        assert [f.rule for f in findings] == ["FT015"]

    def test_clockish_names_are_scope_local_ft015(self, tmp_path):
        # one function's clock local must not taint another function's
        # (or a nested def's) unrelated comparisons
        assert self._lint(tmp_path, (
            "import time\n"
            "def a():\n"
            "    start = time.monotonic()\n"
            "    return start\n"
            "def b(start, limit):\n"
            "    if start > limit:\n"
            "        return 'over'\n")) == []
        assert self._lint(tmp_path, (
            "import time\n"
            "def outer(t, limit):\n"
            "    def inner():\n"
            "        t = time.monotonic()\n"
            "        return t\n"
            "    if t > limit:\n"
            "        return inner()\n")) == []

    def test_set_names_are_scope_local_ft014(self, tmp_path):
        # a nested def rebinding the outer scope's set name to a list
        # must not inherit the outer 'set' classification
        assert self._lint(tmp_path, (
            "def outer():\n"
            "    xs = set()\n"
            "    def inner():\n"
            "        xs = [1, 2]\n"
            "        total = 0\n"
            "        for x in xs:\n"
            "            total += x\n"
            "        return total\n"
            "    return sorted(xs), inner\n")) == []

    def test_telemetry_only_clock_is_quiet(self, tmp_path):
        assert self._lint(tmp_path, (
            "import time\n"
            "def f(rec):\n"
            "    t0 = time.time()\n"
            "    rec['wall_s'] = time.time() - t0\n"
            "    return rec\n")) == []

    def test_tests_are_exempt(self, tmp_path):
        t = tmp_path / "tests"
        t.mkdir()
        p = t / "test_x.py"
        p.write_text("import os\nfor f in os.listdir('.'):\n    print(f)\n")
        ctxs, _ = build_contexts([p], root=tmp_path)
        assert lint_contexts(ctxs, rules=[FsEnumOrderRule()]) == []


class TestCliWiring:
    def _run(self, *args, cwd=REPO):
        import subprocess
        import sys
        return subprocess.run(
            [sys.executable, "-m", "fedml_tpu.analysis", *args],
            capture_output=True, text=True, cwd=cwd, timeout=300)

    def test_write_round_map_needs_full_walk(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text("x = 1\n")
        r = self._run(str(mod), "--no-audit", "--write-round-map")
        assert r.returncode == 2
        assert "--write-round-map" in r.stderr

    def test_deleting_snapshot_is_loud(self, tmp_path):
        # FT300 through the real CLI: point the snapshot path at a
        # nonexistent file on the default walk
        r = self._run("--no-audit", "--round-map-snapshot",
                      str(tmp_path / "gone.json"), "--format", "json")
        assert r.returncode == 1
        report = json.loads(r.stdout)
        assert "FT300" in {f["rule"] for f in report["findings"]}

    def test_changed_only_skips_roundshape_and_flags(self, tmp_path,
                                                     monkeypatch,
                                                     capsys):
        import subprocess
        pkg = tmp_path / "fedml_tpu"
        pkg.mkdir()
        # a file that would fire FT016 (dead flag) on the full walk
        (pkg / "mod.py").write_text(
            "import argparse\n"
            "p = argparse.ArgumentParser()\n"
            "p.add_argument('--dead', type=int)\n")
        def git(*a):
            assert subprocess.run(
                ["git", "-c", "user.email=t@t", "-c", "user.name=t", *a],
                cwd=tmp_path, capture_output=True).returncode == 0
        git("init", "-q")
        git("add", "-A")
        git("commit", "-qm", "seed")
        from fedml_tpu.analysis.__main__ import main
        monkeypatch.chdir(tmp_path)
        assert main(["--changed-only"]) == 0  # nothing touched: clean
        rc = main(["--no-audit", "--no-protocol", "--format", "json"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "FT016" in out
