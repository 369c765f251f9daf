"""One run of one cell: set-up, the correctness check, the measured window,
the metrics, the result line.

Set-up is everything before the first timed round: the federation from the
seed (the generator the configuration names), the model (the zoo entry and
arguments the configuration names), the driver (model init on the device),
a warm-up of exactly the shapes this cell's rounds will have, and the
comparison with the plain reference. ``run`` returns the object ``run.py``
prints as the last line.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import resource
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmark.harness import flops, loop, peaks, spec
from benchmark.harness import trace as tr

#: host spans the loop writes, for attributing idle gaps; time under none of
#: them is the loop's own bookkeeping between spans
LOOP_SPANS = ("bench.run_round", "bench.drain", "bench.evaluate")
OTHER_SPAN = "bench.loop"


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""

    cell: spec.Cell
    window: loop.Window
    counts: Dict[str, float]
    flops_per_row: float
    peak: Dict
    params: int
    cohort_per_chip: int
    memory_peak_bytes: Optional[int]
    host_rss_bytes: int
    trace: Optional[Dict] = None
    trace_window: Optional[tr.Interval] = None
    trace_rounds: int = 0


def _log(t_start: float, message: str) -> None:
    print(f"[bench +{time.time() - t_start:6.1f}s] {message}",
          file=sys.stderr, flush=True)


def sample_cohort(round_idx: int, total: int, per_round: int) -> np.ndarray:
    """The reference's cohort for a round, the same for every seed: numpy's
    legacy generator seeded with the round index, ``per_round`` of ``total``
    without replacement; everybody under full participation
    (FedML ``FedAVGAggregator.client_sampling``)."""
    if per_round >= total:
        return np.arange(total)
    return np.random.RandomState(round_idx).choice(
        total, per_round, replace=False)


def tree_rel_err(a, b) -> float:
    """``||a - b|| / ||a||`` over two trees of host arrays."""
    import jax

    num = sum(float(np.sum((np.asarray(x, np.float64)
                            - np.asarray(y, np.float64)) ** 2))
              for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    den = sum(float(np.sum(np.asarray(x, np.float64) ** 2))
              for x in jax.tree.leaves(a))
    return float(np.sqrt(num) / max(np.sqrt(den), 1e-30))


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak bytes of device memory held on the fullest device, where the
    backend says: the arrays' peak (``peak_bytes_in_use``) plus the peak
    the runtime reserved for running programs' temporaries
    (``peak_bytes_reserved``, which the TPU runtime counts apart)."""
    held = []
    for device in devices:
        stats = device.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            held.append(int(stats["peak_bytes_in_use"])
                        + int(stats.get("peak_bytes_reserved", 0)))
    return max(held) if held else None


class CompileCounter:
    """Counts the compilations JAX starts (one ``backend_compile`` event
    each, persistent-cache hits included) and the cache's misses."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, _seconds, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def make_model(config: Dict):
    """The zoo module the configuration names, with the arguments it
    gives."""
    from fedml_tpu.models import create_model

    model = config["model"]
    return create_model(model["create_model"],
                        output_dim=int(model["output_dim"]),
                        **model.get("kwargs", {}))


def compare_parameters(what: str, init, got, want, rule: Dict,
                       log: Callable[[str], None]) -> List[str]:
    """One round took ``init`` to ``got`` where the reference says ``want``:
    the two may differ by ``param_fraction`` of how far the round moved the
    parameters, and that by less than ``max_param_change`` of their norm
    (PR 21's rule). A ``param_fraction`` of null records the reading and
    holds the round to nothing."""
    err = tree_rel_err(want, got)
    change = tree_rel_err(init, want)
    bound = rule["param_fraction"]
    failures = []
    if not change < rule["max_param_change"]:
        failures.append(f"{what}: the round moved the parameters "
                        f"{change:.3e} of their norm "
                        f"(>= {rule['max_param_change']})")
    if bound is not None and not err <= bound * change:
        failures.append(f"{what}: parameters {err:.3e} from the "
                        f"reference's, more than {bound} x the change "
                        f"{change:.3e}")
    log(f"check {what}: param err {err:.3e}, change {change:.3e}, ratio "
        f"{err / max(change, 1e-30):.3e} (bound {bound}); failures "
        f"{failures}")
    return failures


def check_against_reference(cell: spec.Cell, driver, build_args: Dict,
                            dataset, module, init, timed_round0,
                            first_cohort, seed: int,
                            log: Callable[[str], None]) -> Dict:
    """The comparisons with the plain reference, made in set-up; the
    configuration's ``check`` block holds their bounds.

    ``timed``: the timed driver's own round 0, as the warm-up ran it - the
    cell's cohort, programs and matmul precision - against the reference's
    round over the same cohort at ``highest`` precision. The bound is a
    fraction of the change wide enough for what default precision costs, so
    it fails a round that did not train, a wrong scale or a wrong cohort,
    not a rounding mode. The reference's local loss over that cohort is
    what (b) holds round 0 of the window to.

    ``small`` (where the configuration has it): one round of a second
    driver, built like the cell's but on a small cohort and run at
    ``highest``, against the reference's - both sides in float32, so the
    bound is tight enough to fail one client's weight or one batch. It may
    have training settings of its own (``train``), which the reference then
    shares. It costs one more compilation of the round."""
    import jax

    rule = cell.config["check"]
    reference = cell.module("references", cell.config["reference"])
    task, train = cell.config["model"]["task"], cell.config["train"]
    first_cohort = [int(c) for c in first_cohort]
    ref = reference.run_round(module, task, train, init, dataset, seed=seed,
                              round_idx=0, clients=first_cohort,
                              aggregate=True)
    failures = compare_parameters("timed", init, timed_round0,
                                  ref["variables"], rule["timed"], log)
    loss = sum(ref["loss_sum"].values()) / sum(ref["count"].values())

    if "small" in rule:
        small_rule = rule["small"]
        small_train = {**train, **small_rule.get("train", {})}
        small = sample_cohort(0, dataset.client_num,
                              int(small_rule["cohort"]))
        with jax.default_matmul_precision("highest"):
            api = driver.build(dataset, module, task, **{
                **build_args, "train": small_train, "cohort": len(small),
                "eval_every": 1, "rounds": 1})
            small_init = jax.device_get(api.variables)
            cohort, _ = api.run_round(0)
            got = jax.device_get(api.variables)
        api.release_prefetch()
        del api
        ref = reference.run_round(module, task, small_train, small_init,
                                  dataset, seed=seed, round_idx=0,
                                  clients=small, aggregate=True)
        failures += compare_parameters("small", small_init, got,
                                       ref["variables"], small_rule, log)
        if sorted(int(c) for c in cohort) != sorted(int(c) for c in small):
            failures.append("small: the driver trained another cohort than "
                            "the reference sampling gives")
        if tree_rel_err(init, small_init) != 0.0:
            failures.append("small: the second driver's initial parameters "
                            "are not the timed driver's")
    return {"failures": failures, "first_round_loss": loss}


def plan_rounds(dataset, n_train, clients: int, cohort_size: int, bsz: int,
                round_bound: int):
    """Every round's cohort (the reference's sampling), its real rows (our
    count from the federation's sizes) and its padded length (the program's
    packer policy): the counts to hold the rounds to, the shapes to warm up.
    Returns ``(cohorts, real_rows, padded_len, warm_rounds)``; the warm
    rounds are round 0 and the first round of every other shape."""
    cohorts = [sample_cohort(r, clients, cohort_size)
               for r in range(round_bound)]
    real_rows = np.array([int(n_train[c].sum()) for c in cohorts])
    padded_len = np.array([dataset.cohort_padded_len(c, bsz)
                           for c in cohorts])
    first_of_shape = {int(n): r for r, n in reversed(
        list(enumerate(padded_len)))}
    return (cohorts, real_rows, padded_len,
            sorted(set(first_of_shape.values()) | {0}))


def check_window(window: loop.Window, cohorts, real_rows, ref_loss: float,
                 loss_rel_tol: float, log: Callable[[str], None]):
    """(b) of the correctness rule, on the timed path itself: every round
    trained the cohort the reference sampling gives, counted exactly its
    real rows and returned a finite loss; round 0's local loss is within
    the tolerance of the reference's over the same cohort.
    Returns ``(failures, bad_rounds)``."""
    bad_rounds = []
    for r in range(window.rounds):
        stats = window.stats[r]
        same_cohort = sorted(int(c) for c in window.cohorts[r]) == sorted(
            int(c) for c in cohorts[r])
        if not (np.isfinite(stats["loss_sum"]) and same_cohort
                and stats["count"] == float(real_rows[r])):
            bad_rounds.append(r)
    failures = []
    if bad_rounds:
        failures.append(f"rounds {bad_rounds[:10]} had a wrong cohort, a "
                        "wrong real-row count or a loss that is not finite")
    first = window.stats[0]
    loss = first["loss_sum"] / max(1.0, first["count"])
    log(f"check (b): round 0 loss {loss:.6f} vs reference {ref_loss:.6f} "
        f"(rel {abs(loss - ref_loss) / ref_loss:.3e}, tolerance "
        f"{loss_rel_tol}); bad rounds {bad_rounds[:10]}")
    if not abs(loss - ref_loss) <= loss_rel_tol * abs(ref_loss):
        failures.append(f"round 0's local loss {loss:.6f} is further than "
                        f"{loss_rel_tol} from the reference's "
                        f"{ref_loss:.6f}")
    return failures, bad_rounds


def run(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, out_dir: str) -> Dict:
    """Run the cell once on the devices JAX has; returns the result
    object. ``t_start`` is the process's start on ``time.time()``."""
    import jax

    log = functools.partial(_log, t_start)
    config, traffic = cell.config, cell.traffic
    data, train = config["data"], config["train"]
    task = config["model"]["task"]
    devices = jax.devices()[:cell.chips]
    peak = peaks.lookup(spec.find_file(cell.paths, "", "peaks", ".json"),
                        devices[0].device_kind)
    counter = CompileCounter()

    clients = cell.clients
    cohort_size = int(traffic["cohort"])
    eval_every = int(traffic["eval_every"])
    round_bound = int(traffic["round_bound"])
    dataset, n_train = cell.module("generators", data["generator"]).build(
        data, clients, seed)
    log(f"federation: {clients} clients, {dataset.train_data_num} training "
        f"rows, {dataset.test_data_num} test rows")

    module = make_model(config)
    driver = cell.module("drivers", traffic["driver"])
    build_args = dict(train=train, cohort=cohort_size, eval_every=eval_every,
                      rounds=round_bound, seed=seed, devices=devices)
    api = driver.build(dataset, module, task, **build_args)
    params = sum(int(np.prod(leaf.shape))
                 for leaf in jax.tree.leaves(api.variables))
    if params != int(config["model"]["parameters"]):
        raise ValueError(f"the model has {params} parameters, the "
                         f"configuration file says "
                         f"{config['model']['parameters']}")
    log(f"driver {traffic['driver']!r} built, {params} parameters")

    cohorts, real_rows, padded_len, warm_rounds = plan_rounds(
        dataset, n_train, clients, cohort_size, int(train["batch_size"]),
        round_bound)
    init = jax.device_get(api.variables)
    init_device = jax.tree.map(lambda a: a.copy(), api.variables)
    timed_round0 = None
    for r in warm_rounds:  # round 0 first, from the initial parameters
        api.run_round(r)
        jax.block_until_ready(api.variables)
        if r == 0:
            timed_round0 = jax.device_get(api.variables)
    driver.evaluate(api, 0)
    api.variables = init_device
    log(f"warmed up rounds {warm_rounds} (padded lengths "
        f"{sorted(set(padded_len.tolist()))}) and the evaluation; "
        f"{counter.compiles} compilations, {counter.cache_misses} cache "
        "misses so far")

    check = check_against_reference(cell, driver, build_args, dataset,
                                    module, init, timed_round0, cohorts[0],
                                    seed, log)
    failures = list(check["failures"])
    flops_per_row = cell.module(
        "references", config["reference"]).flops_per_row(
            module, task, train, init, dataset.train_data_global[0][:1],
            flops.count)
    log(f"{flops_per_row / 1e6:.3f} MFLOP per training row; "
        f"{counter.compiles} compilations, {counter.cache_misses} cache "
        "misses in set-up")

    tracer = None
    trace_dir = os.path.join(out_dir, "trace", cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = loop.Tracer(trace_dir)
    # the warm-up's speculative cohorts go; the worker has long been idle
    api.release_prefetch()
    setup_s = time.time() - t_start
    window = loop.measure(api, driver.evaluate, eval_every=eval_every,
                          seconds=seconds, round_bound=round_bound,
                          compiles=lambda: counter.compiles, tracer=tracer)
    api.release_prefetch()
    log(f"window: {window.rounds} rounds in {window.wall_s:.3f} s, "
        f"{len(window.eval_walls)} evaluations (quartiles "
        f"{np.percentile(window.eval_walls, [25, 50, 75]).round(5).tolist()}"
        f" s), profiler {window.profiler_s:.3f} s, {window.compiles} "
        f"compilations; device 0 memory {devices[0].memory_stats()}")

    late, bad_rounds = check_window(window, cohorts, real_rows,
                                    check["first_round_loss"],
                                    config["check"]["loss_rel_tol"], log)
    failures += late
    for message in failures:
        log(f"FAILED {message}")

    slots = -(-cohort_size // cell.chips) * cell.chips  # mesh-padded cohort
    ran = slice(0, window.rounds)
    ctx = Context(
        cell=cell, window=window,
        counts={"recompiles": window.compiles,
                "real_rows": float(real_rows[ran].sum()),
                "packed_rows": float(slots * padded_len[ran].sum())},
        flops_per_row=flops_per_row, peak=peak, params=params,
        cohort_per_chip=slots // cell.chips,
        memory_peak_bytes=memory_peak_bytes(devices),
        host_rss_bytes=resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024)
    result = {"correct": not failures,
              "attempted": cohort_size * window.rounds,
              "failed": cohort_size * len(bad_rounds),
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": ctx.memory_peak_bytes}}
    if trace:
        add_traced_results(result, ctx, trace_dir, log)
    else:
        values = {"rounds_per_s": window.rounds / window.train_wall_s,
                  "eval_s": float(np.median(window.eval_walls)),
                  "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
    return result


def add_traced_results(result: Dict, ctx: Context, trace_dir: str,
                       log: Callable[[str], None]) -> None:
    """Reduce the profiler's trace (kept as ``<trace_dir>.json.gz``; the raw
    one, tens to hundreds of MB, goes) and add the per-layer metrics, the
    device's busy time and the breakdown to ``result``."""
    ctx.trace = tr.extract(tr.newest_xplane(trace_dir))
    tr.save(ctx.trace, trace_dir + ".json.gz")
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx.trace_window = lo, hi = tr.window_of(ctx.trace)
    ctx.trace_rounds = len(tr.spans_in(ctx.trace, "bench.run_round",
                                       ctx.trace_window))
    result["metrics"] = read_metrics(ctx, ctx.cell.per_layer, log)
    result["device"]["busy_s"] = tr.busy_seconds(ctx.trace, ctx.trace_window)
    result["device"]["window_s"] = hi - lo
    result["breakdown"] = {
        "device_ops": [list(op) for op in tr.top_ops(
            ctx.trace, ctx.trace_window, k=10)],
        "idle_gaps": [list(gap) for gap in tr.idle_by_span(
            ctx.trace, ctx.trace_window, LOOP_SPANS, OTHER_SPAN)[:10]],
    }


def read_metrics(ctx: Context, metrics: List[Dict], log) -> Dict:
    """Each per-layer metric through the reader its file names; a reader
    that finds nothing to read returns nothing and the metric is left out."""
    out = {}
    for metric in metrics:
        entry = spec.load_json(ctx.cell.find("metrics", metric["name"],
                                             ".json"))
        value = ctx.cell.module("readers", entry["reader"]).read(
            ctx, **entry.get("args", {}))
        if value is None:
            log(f"metric {metric['name']}: nothing to read, left out")
            continue
        out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out
