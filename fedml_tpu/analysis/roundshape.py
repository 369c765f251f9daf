"""FT30x — round-shape conformance: the machine-checked map of the
driver zoo.

``algorithms/`` holds 17 files that hand-copy the same
sample→pack→train→aggregate skeleton; PRs 2/4/5 each re-wired prefetch,
compression, and fault tolerance through several of them by hand, and
the ROADMAP's round-engine unification refactor needs a ground truth to
hold parity against. This pass extracts, from the same one-parse
contexts every other pass shares, a normalized **round-shape IR** per
driver:

- **sampling** — the cohort-selection hook and its seed source
  (``core.sampling.sample_clients``'s seeded host contract, the fused
  scan's ``jax.random.choice``, a seeded ``np.random.RandomState``, the
  locked global stream, or structural full participation);
- **pack** — cohort packing (``pack_clients`` pad-and-mask, cohort
  bucketing) and the async **prefetch** binding (``RoundPrefetcher`` /
  the shared ``_host_round_inputs`` path);
- **train** — the local-train entry point (shared functional trainer,
  a module-local ``make_*_local_train``, a module-level jitted step);
- **aggregate** — the server combination rule (sample-weighted mean,
  robust unweighted rules, normalized-gradient recombination, secure
  additive shares, staleness-weighted async mix, server optimizer);
- **comm** — in-process vs actor messages, and the compression-policy
  hooks;
- **failure** — liveness beat, deadline close, rejoin/heartbeat, chaos
  hooks.

Stages a driver does not define locally resolve through its base
classes (``FedOptAPI(FedAvgAPI)`` inherits sampling/pack/prefetch from
``fedavg``), so the map records *where each driver really gets each
stage* — hand-copied divergence becomes a finding, not tribal
knowledge:

- **FT300** — the checked-in snapshot ``ci/round_engine_map.json`` is
  missing/unreadable: the drift check must fail loudly, never skip.
- **FT301** — a driver re-implements a skeleton helper the shared
  modules provide (a local ``def sample_clients``/``tree_weighted_mean``
  shadowing ``core.sampling``/``core.pytree``).
- **FT302** — the skeleton's prefetch wiring is absent in a driver that
  does its own per-round sample+pack (the exact class of divergence
  PRs 2/4/5 fixed piecemeal, one driver at a time).
- **FT303** — an aggregation hook that takes the reported client
  weights but never reads them (weight-dropping aggregation; the
  deliberately unweighted robust rules carry a pragma with the
  rationale).
- **FT304** — a driver-local env knob (``os.environ`` read inside
  ``algorithms/``) bypassing the shared arg set.
- **FT305** — the extracted map drifted from the snapshot; accept
  deliberately with ``--write-round-map``.

The ``runs/round_engine_map.json`` artifact (line-bearing evidence) is
the parity oracle the unification refactor will diff itself against —
exactly as ``ci/collective_baseline.json`` guards the SPMD item.

A module participates as a driver when it lives under an
``algorithms/`` directory or declares ``FT_ROUNDSHAPE_DRIVER = True``
(how the analysis corpus plants driver-shaped violations).
"""

from __future__ import annotations

import ast
import hashlib
import json
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from fedml_tpu.analysis.finding import Finding
from fedml_tpu.analysis.lint import FileContext, dotted_name, is_test_path

MAP_VERSION = 1

STAGES = ("sampling", "pack", "train", "aggregate", "comm", "failure")

_HINTS = {
    "FT300": ("regenerate the snapshot: python -m fedml_tpu.analysis "
              "--write-round-map"),
    "FT301": ("import the shared helper instead of redefining it — one "
              "definition is the parity contract the unification "
              "refactor diffs against"),
    "FT302": ("route the round's host side through the shared "
              "FedAvgAPI._host_round_inputs prefetch path (PRs 2/4/5 "
              "re-wired this per driver by hand), or pragma a driver "
              "whose round structure genuinely cannot pipeline: "
              "# ft: allow[FT302] why"),
    "FT303": ("weight the aggregation by the reported client sample "
              "counts, or pragma a deliberately unweighted rule with "
              "the rationale: # ft: allow[FT303] why"),
    "FT304": ("read config through the shared arg set / the driver's "
              "Config dataclass — driver-local env knobs are invisible "
              "to launchers and to the README flag table"),
    "FT305": ("review the round-shape change, then refresh the "
              "snapshot: python -m fedml_tpu.analysis --write-round-map"),
}

#: shared skeleton helpers a driver must import, not redefine
#: (helper name -> canonical home path suffix)
_SHARED_HELPERS = {
    "sample_clients": "core/sampling.py",
    "round_keys": "core/sampling.py",
    "eval_subsample": "core/sampling.py",
    "pack_clients": "data/base.py",
    "cohort_padded_len": "data/base.py",
    "client_weights": "data/base.py",
    "tree_weighted_mean": "core/pytree.py",
    "tree_weighted_mean_pallas": "ops/aggregate.py",
    "make_local_train": "trainer/functional.py",
    "make_eval": "trainer/functional.py",
    "make_batch_schedule": "trainer/functional.py",
    "resolve_compression": "comm/policy.py",
    "make_vmapped_body": "algorithms/fedavg.py",
}

#: aggregation-hook parameter names that carry reported client weights
_WEIGHT_PARAMS = frozenset({"weights", "ratios", "sample_nums",
                            "client_weights"})
_AGG_NAME_TOKENS = ("aggregate", "mean", "hook", "defended", "combine")


def _module_of(relpath: str) -> str:
    mod = relpath[:-3] if relpath.endswith(".py") else relpath
    mod = mod.replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


def _is_driver_module(ctx: FileContext, names: Set[str]) -> bool:
    parts = Path(ctx.relpath).parts
    if "algorithms" in parts:
        return True
    return "FT_ROUNDSHAPE_DRIVER" in names


class _ModuleFacts:
    """Everything the stage resolver needs about one module, from one
    AST walk: call names, attribute names, bare names, function defs,
    classes with their base-name spellings, and the import table."""

    def __init__(self, ctx: FileContext):
        self.ctx = ctx
        self.module = _module_of(ctx.relpath)
        self.calls: Dict[str, int] = {}        # dotted call name -> first line
        self.attrs: Set[str] = set()
        self.names: Set[str] = set()
        #: EVERY def per name — two classes may define same-named hooks
        #: and FT301/FT303 must see them all
        self.funcdefs: Dict[str, List[ast.AST]] = {}
        self.classes: Dict[str, List[str]] = {}  # class -> base spellings
        self.imports: Dict[str, str] = {}        # local name -> module
        self.env_reads: List[int] = []           # lines of os.environ reads
        self.range_over_client_num = False
        self._collect()

    def _collect(self) -> None:
        tree = self.ctx.tree
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                name = dotted_name(node.func)
                if name:
                    self.calls.setdefault(name, node.lineno)
                    if name in ("os.environ.get", "os.getenv"):
                        self.env_reads.append(node.lineno)
                if isinstance(node.func, ast.Name) \
                        and node.func.id == "range":
                    for arg in node.args:
                        for sub in ast.walk(arg):
                            d = dotted_name(sub) if isinstance(
                                sub, (ast.Attribute, ast.Name)) else None
                            if d and d.split(".")[-1] in (
                                    "client_num", "client_num_in_total",
                                    "worker_num"):
                                self.range_over_client_num = True
            elif isinstance(node, ast.Subscript) \
                    and dotted_name(node.value) == "os.environ":
                self.env_reads.append(node.lineno)
            elif isinstance(node, ast.Attribute):
                self.attrs.add(node.attr)
            elif isinstance(node, ast.Name):
                self.names.add(node.id)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.funcdefs.setdefault(node.name, []).append(node)
            elif isinstance(node, ast.ClassDef):
                self.classes[node.name] = [
                    b for b in (dotted_name(base) for base in node.bases)
                    if b]
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for alias in node.names:
                    self.imports[alias.asname or alias.name] = node.module

    # -- marker predicates ---------------------------------------------------
    def has_call(self, *suffixes: str) -> Optional[int]:
        """First line of a call whose last dotted component matches."""
        for name, line in self.calls.items():
            if name.split(".")[-1] in suffixes:
                return line
        return None

    def evidence(self) -> Set[str]:
        """The flat marker universe: call suffixes + attrs + names."""
        out = {name.split(".")[-1] for name in self.calls}
        out |= self.attrs
        out |= self.names
        return out


#: (stage, hook label, required marker sets) — a marker set matches when
#: ANY of its entries is in the module's evidence; rules are tried in
#: order, first hit is the primary hook (later hits join the evidence)
_STAGE_RULES: Dict[str, List[Tuple[str, Tuple[str, ...]]]] = {
    "sampling": [
        ("seeded_host_sampler", ("sample_clients", "client_sampling")),
        ("device_choice", ("__jax_choice__",)),
        ("locked_global_rng", ("locked_global_numpy_rng",)),
        ("full_population", ("__range_client_num__",)),
        ("seeded_host_rng", ("RandomState",)),
    ],
    "pack": [
        ("pad_and_mask_pack", ("pack_clients",)),
        ("shared_fedavg_pack", ("_host_round_inputs", "_pack_round",
                                "_pack_cohort")),
        ("per_client_host_batches", ("train_data_local_dict",)),
    ],
    "train": [
        ("shared_functional_local_train", ("make_local_train",
                                           "_shared_local_train")),
        ("module_local_train", ("__local_train_def__",)),
        ("module_jit_step", ("value_and_grad", "grad", "apply_updates")),
        ("flax_trainer", ("FlaxModelTrainer",)),
        ("pluggable_local_fn", ("local_compute", "local_fn", "_local_fn")),
    ],
    "aggregate": [
        ("robust_rules_unweighted", ("ROBUST_AGGREGATORS", "apply_defense")),
        ("secure_additive_shares", ("gen_additive_ss", "SecureAggregator",
                                    "lcc_encoding", "quantize")),
        ("staleness_weighted_mix", ("tree_axpy", "staleness_weight")),
        ("normalized_grad_recombination", ("tau_eff",)),
        ("sample_weighted_mean", ("tree_weighted_mean",
                                  "tree_weighted_mean_pallas")),
        ("gossip_mix", ("__gossip__",)),
        ("sum_reduce", ("_tree_sum", "tree_add")),
    ],
    "comm": [
        ("actor_messages", ("register_message_receive_handler",
                            "send_message", "launch_federation")),
    ],
    "failure": [
        ("liveness_deadline_rejoin", ("__ft_markers__",)),
    ],
}

#: a prefetch BINDING (not a mere config field: FedNovaConfig carries
#: prefetch_depth "for launcher symmetry" while packing serially — that
#: must count as absent, it is the FT302 divergence class itself)
_PREFETCH_MARKERS = ("RoundPrefetcher", "bind_prefetcher", "consume",
                     "resolve_prefetch_depth", "_round_prefetcher",
                     "_host_round_inputs")
_COMPRESSION_MARKERS = ("resolve_compression", "CompressionPolicy",
                        "compress_for_policy", "is_compressed",
                        "_decode_model_payload", "_encode_broadcast",
                        "compression")
_FT_MARKERS = {
    "liveness": ("SiloLivenessTable", "liveness", "observe_report_latency"),
    "deadline": ("_arm_deadline", "round_deadline_s",
                 "handle_round_timeout", "MSG_TYPE_ROUND_TIMEOUT"),
    "rejoin": ("handle_message_join", "MSG_TYPE_C2S_JOIN",
               "rejoin_idle_s", "rejoins"),
    "heartbeat": ("heartbeat_s", "handle_message_heartbeat",
                  "MSG_TYPE_C2S_HEARTBEAT"),
    "chaos": ("fault_plan", "FaultPlan"),
}
_SEED_MARKERS = (
    ("fold_in_keychain", ("round_keys", "fold_in", "key")),
    ("seeded_rng", ("RandomState", "SeedSequence")),
    ("locked_global_stream", ("locked_global_numpy_rng",)),
    ("global_seed", ("seed",)),
)


def _kind_of(facts: _ModuleFacts) -> str:
    if not facts.funcdefs and not facts.classes:
        return "reexport"
    ev = facts.evidence()
    if "register_message_receive_handler" in ev \
            or "launch_federation" in ev \
            or any("Manager" in b for bases in facts.classes.values()
                   for b in bases):
        return "actor"
    return "sim"


def _local_markers(facts: _ModuleFacts) -> Set[str]:
    ev = facts.evidence()
    if facts.range_over_client_num:
        ev.add("__range_client_num__")
    if "jax.random.choice" in facts.calls:
        # full dotted match: a host RandomState's .choice must not read
        # as device-side sampling
        ev.add("__jax_choice__")
    if any(name.startswith("make_") and "local" in name
           and "train" in name for name in facts.funcdefs):
        ev.add("__local_train_def__")
    if "einsum" in ev and ("TopologyManager" in " ".join(
            b for bases in facts.classes.values() for b in bases)
            or any("Topology" in c for c in facts.calls)):
        ev.add("__gossip__")
    if any(m in ev for group in _FT_MARKERS.values() for m in group):
        ev.add("__ft_markers__")
    return ev


class _Resolver:
    """Stage resolution with transitive base-module inheritance."""

    def __init__(self, facts_by_module: Dict[str, _ModuleFacts]):
        self.facts = facts_by_module
        #: class name -> defining module (last definition wins; driver
        #: class names are unique in this tree)
        self.class_home: Dict[str, str] = {}
        for mod, f in facts_by_module.items():
            for cls in f.classes:
                self.class_home[cls] = mod
        self._markers: Dict[str, Set[str]] = {
            mod: _local_markers(f) for mod, f in facts_by_module.items()}

    def base_modules(self, module: str) -> List[str]:
        """Modules (in the analyzed set) that define this module's base
        classes, transitively, nearest first."""
        out: List[str] = []
        seen = {module}
        frontier = [module]
        while frontier:
            mod = frontier.pop(0)
            f = self.facts.get(mod)
            if f is None:
                continue
            for bases in f.classes.values():
                for base in bases:
                    name = base.split(".")[-1]
                    home = self.class_home.get(name)
                    if home is None and name in f.imports:
                        home = f.imports[name]
                    if home and home in self.facts and home not in seen:
                        seen.add(home)
                        out.append(home)
                        frontier.append(home)
        return out

    def resolve_stage(self, module: str, stage: str, kind: str
                      ) -> Dict[str, str]:
        chain = [(module, "local")] + [
            (b, f"inherited:{b}") for b in self.base_modules(module)]
        # rules outer, chain inner: a higher-priority hook anywhere in
        # the inheritance chain beats a lower-priority local one — a
        # subclass driver's incidental helper (fedavg_robust's poisoning
        # RandomState) must not shadow the skeleton stage it inherits
        for hook, wanted in _STAGE_RULES[stage]:
            for mod, via in chain:
                markers = self._markers.get(mod, set())
                if any(w in markers for w in wanted):
                    return {"hook": hook, "via": via}
        # structural defaults: explicit, never "unknown"
        if stage == "comm":
            return {"hook": "in_process", "via": "structural"}
        if stage == "failure":
            if kind == "actor":
                return {"hook": "none_strict_barrier", "via": "structural"}
            return {"hook": "n/a_in_process", "via": "structural"}
        if stage == "sampling":
            return {"hook": "n/a_no_cohort", "via": "structural"}
        if stage == "pack":
            return {"hook": "n/a_no_cohort_pack", "via": "structural"}
        if stage == "aggregate":
            return {"hook": "n/a_no_model_averaging", "via": "structural"}
        return {"hook": "unknown", "via": "unresolved"}

    def feature(self, module: str, markers: Tuple[str, ...]
                ) -> Tuple[str, str]:
        """(value, via) for a cross-cutting feature like prefetch."""
        chain = [(module, "local")] + [
            (b, f"inherited:{b}") for b in self.base_modules(module)]
        for mod, via in chain:
            got = sorted(m for m in markers
                         if m in self._markers.get(mod, set()))
            if got:
                return ("+".join(got), via)
        return ("none", "structural")

    def seed_source(self, module: str) -> str:
        markers = self._markers.get(module, set())
        for label, wanted in _SEED_MARKERS:
            if any(w in markers for w in wanted):
                return label
        for base in self.base_modules(module):
            bm = self._markers.get(base, set())
            for label, wanted in _SEED_MARKERS:
                if any(w in bm for w in wanted):
                    return f"{label} (inherited:{base})"
        return "none"

    def failure_hooks(self, module: str) -> Tuple[str, str]:
        chain = [(module, "local")] + [
            (b, f"inherited:{b}") for b in self.base_modules(module)]
        best: Optional[Tuple[str, str]] = None
        for mod, via in chain:
            markers = self._markers.get(mod, set())
            got = sorted(k for k, wanted in _FT_MARKERS.items()
                         if any(w in markers for w in wanted))
            if got:
                got_s = "+".join(got)
                if best is None:
                    best = (got_s, via)
                elif via.startswith("inherited") and best[0] != got_s:
                    # merge: a subclass driver keeps the base's hooks
                    merged = sorted(set(best[0].split("+")) | set(got))
                    best = ("+".join(merged), best[1])
        return best if best else ("none", "structural")


class _Analysis:
    """The one-build substrate both the map extractor and the
    conformance checker consume — module facts and the inheritance
    resolver are computed exactly once per run."""

    def __init__(self, ctxs: Sequence[FileContext]):
        lib = [c for c in ctxs if not is_test_path(c.relpath)]
        self.facts_by_module: Dict[str, _ModuleFacts] = {}
        self.drivers: List[_ModuleFacts] = []
        for ctx in lib:
            f = _ModuleFacts(ctx)
            self.facts_by_module[f.module] = f
            if _is_driver_module(ctx, f.names):
                self.drivers.append(f)
        self.resolver = _Resolver(self.facts_by_module)


def analyze(ctxs: Sequence[FileContext]) -> _Analysis:
    return _Analysis(ctxs)


def extract_round_shapes(ctxs: Sequence[FileContext],
                         analysis: Optional[_Analysis] = None) -> Dict:
    """-> the line-bearing round-engine map over every driver module in
    ``ctxs`` (the ``runs/`` artifact shape)."""
    analysis = analysis or _Analysis(ctxs)
    resolver = analysis.resolver

    drivers: List[Dict] = []
    for f in sorted(analysis.drivers, key=lambda f: f.module):
        module = f.module
        kind = _kind_of(f)
        entry: Dict = {"module": module, "path": f.ctx.relpath,
                       "kind": kind, "stages": {}}
        if kind == "reexport":
            entry["stages"] = {s: {"hook": "n/a_reexport",
                                   "via": "structural"} for s in STAGES}
            drivers.append(entry)
            continue
        for stage in STAGES:
            entry["stages"][stage] = resolver.resolve_stage(module, stage,
                                                            kind)
        entry["stages"]["sampling"]["seed_source"] = \
            resolver.seed_source(module)
        prefetch, pvia = resolver.feature(module, _PREFETCH_MARKERS)
        entry["stages"]["pack"]["prefetch"] = prefetch
        entry["stages"]["pack"]["prefetch_via"] = pvia
        comp, cvia = resolver.feature(module, _COMPRESSION_MARKERS)
        entry["stages"]["comm"]["compression"] = comp
        entry["stages"]["comm"]["compression_via"] = cvia
        hooks, hvia = resolver.failure_hooks(module)
        entry["stages"]["failure"]["hooks"] = hooks
        entry["stages"]["failure"]["hooks_via"] = hvia
        entry["bases"] = resolver.base_modules(module)
        drivers.append(entry)
    return {"version": MAP_VERSION, "drivers": drivers}


# -- conformance findings (FT301-FT304) --------------------------------------

def _finding(rule: str, path: str, line: int, message: str,
             snippet: str = "") -> Finding:
    return Finding(rule=rule, path=path, line=line, message=message,
                   hint=_HINTS[rule], snippet=snippet)


def conformance_findings(ctxs: Sequence[FileContext],
                         analysis: Optional[_Analysis] = None
                         ) -> List[Finding]:
    """FT301-FT304 over the driver modules, pragma suppression through
    each originating context."""
    analysis = analysis or _Analysis(ctxs)
    drivers = analysis.drivers
    resolver = analysis.resolver

    findings: List[Finding] = []

    def emit(rule: str, ctx: FileContext, line: int, message: str) -> None:
        if ctx.allowed(rule, line):
            return
        snippet = (ctx.lines[line - 1].strip()
                   if 0 < line <= len(ctx.lines) else "")
        findings.append(_finding(rule, ctx.relpath, line, message, snippet))

    for f in drivers:
        ctx = f.ctx
        # FT301: local redefinition of a shared skeleton helper
        for name, nodes in f.funcdefs.items():
            home = _SHARED_HELPERS.get(name)
            if home is None or ctx.relpath.endswith(home):
                continue
            for node in nodes:
                emit("FT301", ctx, node.lineno,
                     f"driver defines its own {name}() — the shared "
                     f"skeleton helper lives in {home}; a local copy "
                     "forks the parity contract the round-engine "
                     "unification will diff against")
        # FT302: per-round sample+pack without the prefetch pipeline
        if _kind_of(f) == "sim":
            sample_line = f.has_call("sample_clients")
            pack_line = f.has_call("pack_clients")
            prefetch, _ = resolver.feature(f.module, _PREFETCH_MARKERS)
            if sample_line and pack_line and prefetch == "none":
                emit("FT302", ctx, pack_line,
                     "driver samples and packs each round on the "
                     "critical path with NO prefetch binding — the "
                     "skeleton's async round pipeline "
                     "(FedAvgAPI._host_round_inputs / RoundPrefetcher) "
                     "is wired into every FedAvg-family driver; this is "
                     "the exact divergence class PRs 2/4/5 fixed "
                     "piecemeal")
        # FT303: aggregation hook that ignores its weights parameter
        for name, nodes in f.funcdefs.items():
            if not any(tok in name.lower() for tok in _AGG_NAME_TOKENS):
                continue
            for node in nodes:
                a = node.args
                params = {p.arg for p in
                          a.args + a.kwonlyargs
                          + getattr(a, "posonlyargs", [])}
                wparams = params & _WEIGHT_PARAMS
                if not wparams:
                    continue
                loaded = {n.id for n in ast.walk(node)
                          if isinstance(n, ast.Name)
                          and isinstance(n.ctx, ast.Load)}
                for w in sorted(wparams - loaded):
                    emit("FT303", ctx, node.lineno,
                         f"aggregation hook {name}() takes the reported "
                         f"client weights ({w!r}) but never reads them — "
                         "sample-count weighting is silently dropped "
                         "(deliberately unweighted robust rules pragma "
                         "this with the rationale)")
        # FT304: driver-local env knob
        for line in sorted(set(f.env_reads)):
            emit("FT304", ctx, line,
                 "driver reads an environment variable directly — "
                 "config must flow through the shared arg set / the "
                 "driver Config dataclass so launches are reproducible "
                 "from their recorded flags")
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


# -- snapshot (FT300/FT305) ---------------------------------------------------

def normalize_map(full_map: Dict) -> Dict:
    """Line-free, path-free shape for the checked-in snapshot."""
    drivers = []
    for d in full_map["drivers"]:
        drivers.append({
            "module": d["module"],
            "kind": d["kind"],
            "stages": {s: dict(d["stages"][s]) for s in STAGES
                       if s in d["stages"]},
        })
    payload = {"version": MAP_VERSION,
               "drivers": sorted(drivers, key=lambda d: d["module"])}
    blob = json.dumps(payload, sort_keys=True)
    payload["fingerprint"] = hashlib.sha1(blob.encode()).hexdigest()[:16]
    return payload


def snapshot_findings(full_map: Dict, snapshot_path: Path) -> List[Finding]:
    norm = normalize_map(full_map)
    path = Path(snapshot_path)
    if not path.exists():
        return [_finding(
            "FT300", str(snapshot_path), 0,
            "round-engine-map snapshot is MISSING — the unification "
            "refactor's parity oracle cannot drift-check, and a "
            "silently skipped check is the failure mode this pass "
            "exists to prevent")]
    try:
        old = json.loads(path.read_text())
    except (OSError, ValueError) as exc:
        return [_finding(
            "FT300", str(snapshot_path), 0,
            f"round-engine-map snapshot is unreadable ({exc}) — "
            "regenerate it")]
    if old.get("fingerprint") == norm["fingerprint"]:
        return []
    old_d = {d["module"]: d for d in old.get("drivers", [])}
    new_d = {d["module"]: d for d in norm["drivers"]}
    changes: List[str] = []
    for mod in sorted(set(new_d) - set(old_d)):
        changes.append(f"new driver {mod}")
    for mod in sorted(set(old_d) - set(new_d)):
        changes.append(f"removed driver {mod}")
    for mod in sorted(set(old_d) & set(new_d)):
        if old_d[mod] != new_d[mod]:
            diff_stages = [s for s in STAGES
                           if old_d[mod].get("stages", {}).get(s)
                           != new_d[mod].get("stages", {}).get(s)]
            changes.append(f"{mod}: {'/'.join(diff_stages) or 'kind'} "
                           "changed")
    detail = "; ".join(changes) or "map fingerprint changed"
    return [_finding(
        "FT305", str(snapshot_path), 0,
        f"round-shape map drifted from the checked-in snapshot: {detail}")]


def write_map(full_map: Dict, artifact_path: Path,
              snapshot_path: Optional[Path] = None) -> None:
    artifact_path = Path(artifact_path)
    artifact_path.parent.mkdir(parents=True, exist_ok=True)
    artifact_path.write_text(json.dumps(full_map, indent=2, sort_keys=True)
                             + "\n")
    if snapshot_path is not None:
        snapshot_path = Path(snapshot_path)
        snapshot_path.parent.mkdir(parents=True, exist_ok=True)
        snapshot_path.write_text(
            json.dumps(normalize_map(full_map), indent=2, sort_keys=True)
            + "\n")


def check_round_shapes(ctxs: Sequence[FileContext], snapshot_path: Path,
                       artifact_path: Optional[Path] = None,
                       write_snapshot: bool = False
                       ) -> Tuple[List[Finding], Dict]:
    """The CLI entry: extract, emit the artifact, check conformance +
    snapshot. ``write_snapshot`` refreshes instead of comparing
    (conformance findings still apply — a snapshot must never launder
    an FT301)."""
    analysis = _Analysis(ctxs)
    full_map = extract_round_shapes(ctxs, analysis=analysis)
    if artifact_path is not None:
        write_map(full_map, artifact_path)
    findings = conformance_findings(ctxs, analysis=analysis)
    if write_snapshot:
        snapshot_path = Path(snapshot_path)
        snapshot_path.parent.mkdir(parents=True, exist_ok=True)
        snapshot_path.write_text(
            json.dumps(normalize_map(full_map), indent=2, sort_keys=True)
            + "\n")
    else:
        findings.extend(snapshot_findings(full_map, snapshot_path))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, full_map
