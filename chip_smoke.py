#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof the system still starts on the chip.

One process, no flags. It drives the FedAvg main path the way a user would
— ``data.registry.load_data`` -> ``models.create_model`` ->
``experiments.flagship_scale.run_driver`` -> ``FedAvgAPI`` /
``DistributedFedAvgAPI`` -> ``trainer/functional`` -> ``ops/*`` — at the full
published width and depth of ResNet-18-GroupNorm on ``fed_cifar100_gen``
(24x24x3, 100 classes) with the reference round shape (10 clients per round,
B=20, E=1), weights from seed 0, on a federation small enough to generate in
seconds:

1. trainer leg — the ``simulation`` driver on one chip, three rounds;
2. mesh leg — the same model on the ``spmd`` driver over every local
   device, with the devices that held the packed cohort and the aggregated
   model read back from ``array.sharding.device_set``;
3. parity leg — both drivers again for one round at ``highest`` matmul
   precision (the setting the repo's own parity tests run in), with
   ``sim_spmd_param_rel_err`` held to a tolerance;
4. kernel leg — every Pallas kernel the main path or its flags can select,
   compiled (never interpreted on the chip) at a production shape and
   compared on the chip with its ``jnp`` reference.

It exits non-zero, and prints no result line, unless JAX finds a TPU; it
sets no ``JAX_PLATFORMS`` and catches nothing. Everything it writes lands
under ``runs/chip_smoke/``; it reads nothing an earlier run left. The full
report (legs, walls, cache entries, kernel errors) goes to
``runs/chip_smoke/summary.json`` and to the last-but-one line of stdout; the
LAST line of stdout is the result and nothing else — one JSON object with
exactly the keys ``{"ok": true|false, "device": {"platform": ..., "kind":
..., "count": ...}}``, the device as JAX reports it. Exit code 0 only if
every leg passed.

The legs are importable functions that take sizes, so a CPU test runs them
tiny (``tests/test_chip_smoke.py``); there the one backend rule
(``fedml_tpu.utils.on_tpu``) interprets the kernels.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import time
from typing import Any, Dict, Sequence, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "runs", "chip_smoke")

#: sim==spmd parameter parity after ONE round at ``highest`` matmul
#: precision, as a fraction of how far that round moved the parameters
#: (``sim_spmd_param_rel_err <= PARITY_FRACTION * param_change_rel``).
#: Why not the three default-precision rounds of legs 1-2: the two drivers
#: compile different programs, and ResNet rounds from a fresh init amplify
#: their rounding differences ~1e4x in three rounds. On a v5e the 3-round
#: parity read 2.6e-3 (one chip) and 4.9e-3 (four chips) at the TPU's
#: default precision (one bf16 pass per product) and 8.3e-4 at ``highest``,
#: against a parameter change of 9.0e-3 — 0.3, 0.5 and 0.1 of the change:
#: noise that cannot tell a wrong cohort from rounding; even ONE
#: default-precision round read 1.2e-3 against a change of 6.6e-3 on one
#: chip (0.18 of it), so the precision, not the round count, has to
#: change, and that costs two more compiles. One f32 round read
#: 3.2e-5 against a change of 6.4e-3 on four chips (0.005 of it; all my
#: chip runs, PR 21), so a tenth of the change passes with 20x margin and
#: still catches a device's clients missing from the psum (~1/4 of the
#: change), a wrong scale (>= 1/2) and an aggregation rounded to bf16
#: (~2^-9 of the parameters, 0.3 of the change — the defect this smoke's
#: first run found in both drivers).
PARITY_FRACTION = 0.1

#: the parity tolerance is a fraction of the parameter change, so it only
#: means something while that change is one round's: 6.4e-3 of the norm
#: for ResNet-18-GN on the v5e (my chip runs, PR 21). train_leg measures
#: the change from an init it re-derives; should that ever stop being the
#: API's init, two independent inits are ~1.4 apart and the leg fails here
#: instead of passing everything.
MAX_PARAM_CHANGE = 0.5

#: kernel-vs-reference tolerances; references run at ``highest`` matmul
#: precision. The aggregation is f32 end to end on both backends
#: (tests/test_ops.py's tolerance, of the reference's largest magnitude).
#:
#: Flash attention multiplies on the MXU at the backend's default
#: precision, by design: it is the XLA attention's default too, and the
#: kernel exists for speed. On the CPU that is exact f32 and FLASH_F32_TOL
#: (forward, backward) are tests/test_flash_attention.py's tolerances. On a
#: TPU it is ONE bf16 pass even for f32 operands, so no fixed bound is both
#: passable and tight. Instead the XLA attention (``reference_attention``)
#: at that same default precision on the same chip measures what the
#: rounding costs, and the kernel may be FLASH_VS_XLA_ERR times as far
#: from the oracle as the XLA attention is, in two norms (attention_errors):
#: the largest element error (of max|ref|), which the first positions
#: dominate, and the largest per-row relative error past them, which sees
#: the late rows. On the v5e (my chip runs, PR 21) the kernel's largest
#: element error is 0.91-1.03x the XLA attention's. An XLA attention with
#: a tile edge mis-masked by one key in the late rows reads 1.0x in that
#: norm — invisible — and 3.4x (forward) to 6.3x (dk) in the row norm; one
#: with a key block dropped reads 14-21x and 55-128x.
AGG_TOL = 1e-5
FLASH_F32_TOL = (2e-5, 2e-4)
FLASH_VS_XLA_ERR = 2.0


def _log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}",
          flush=True)


_T0 = time.perf_counter()


def device_report() -> Dict:
    """The device as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def result_line(ok: bool, device: Dict) -> str:
    """The last line of stdout: the result and the device, no other key
    (whoever checks the smoke parses this line alone; the report with
    everything else is the line before it and ``summary.json``)."""
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["kind"]),
                   "count": int(device["count"])}})


def peak_memory() -> Dict[str, int]:
    """Process-lifetime peak bytes in use per device (empty where the
    backend reports no memory stats)."""
    import jax

    out = {}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            out[str(d.id)] = int(stats["peak_bytes_in_use"])
    return out


def cache_entries(cache_dir: str) -> set:
    """Names of the persistent compile cache's entries."""
    try:
        return {f for f in os.listdir(cache_dir) if f.endswith("-cache")}
    except FileNotFoundError:
        return set()


def _programs(entries: set) -> Dict[str, int]:
    """Cache entry names -> {program name: count} (the key hash dropped)."""
    out: Dict[str, int] = {}
    for name in entries:
        prog = name.rsplit("-", 2)[0]
        out[prog] = out.get(prog, 0) + 1
    return dict(sorted(out.items()))


def load_federation(dataset: str, clients: int):
    """(dataset, model name, task) through the registry, as the launchers
    do."""
    from fedml_tpu.data.registry import DEFAULT_MODEL_AND_TASK, load_data

    ds = load_data(dataset, "", client_num_in_total=clients)
    model_name, task = DEFAULT_MODEL_AND_TASK[dataset]
    return ds, model_name, task


def train_leg(kind: str, ds, model_name: str, task: str, *, rounds: int,
              per_round: int, batch_size: int, out_dir: str,
              lr: float = 0.03, seed: int = 0) -> Tuple[Dict, object]:
    """One driver (``"sim"`` | ``"spmd"``) of the main path for ``rounds``
    rounds with an evaluation after each, through
    ``experiments.flagship_scale.run_driver``. Returns (report, final
    variables); ``report["failures"]`` lists every check that failed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.experiments.flagship_scale import param_rel_err, run_driver
    from fedml_tpu.models import create_model
    from fedml_tpu.utils import on_tpu
    from fedml_tpu.utils.flops import count_params

    os.makedirs(out_dir, exist_ok=True)
    hist_path = os.path.join(out_dir, f"{kind}_history.jsonl")
    open(hist_path, "w").close()
    model = create_model(model_name, output_dim=ds.class_num)
    api, stats = run_driver(kind, ds, model, task, rounds, per_round,
                            eval_every=1, batch_size=batch_size, lr=lr,
                            seed=seed, history_path=hist_path)
    hist = api.history
    failures = []
    if len(hist) != rounds:
        failures.append(f"{len(hist)} history rows for {rounds} rounds")
    # local-SGD and post-aggregation test loss from both drivers; the sim
    # driver also evaluates the train union
    losses = {k: [float(h[k]) for h in hist if k in h]
              for k in ("train_loss_local", "train_loss", "test_loss")}
    for name, vals in losses.items():
        if name == "train_loss" and kind == "spmd":
            continue
        if len(vals) != len(hist) or not np.all(np.isfinite(vals)):
            failures.append(f"{name} missing or not finite: {vals}")
    # the API's own init (same module, seed and sample), to show training
    # moved the parameters and left them finite
    init = model.init(jax.random.key(seed),
                      jnp.asarray(ds.train_data_global[0][:1]), train=False)
    change = param_rel_err(init, api.variables)
    if not (np.isfinite(change) and change > 0.0):
        failures.append(f"parameters did not change (rel change {change})")
    walls = [float(h["wall_s"]) for h in hist]
    later = [b - a for a, b in zip(walls, walls[1:])]
    report = {
        "driver": kind, "model": model_name,
        "params": count_params(api.variables),
        "rounds": rounds, "clients_per_round": per_round,
        "batch_size": batch_size,
        # host wall clock per round INCLUDING its evaluation (eval_every=1
        # blocks on the device each round); the first round also compiles
        "first_round_s": round(walls[0], 3) if walls else None,
        "later_round_s": [round(w, 3) for w in later],
        "wall_s": stats["wall_s"],
        "phase_ms": stats["phase_ms"],
        "losses": losses,
        "final": {k: hist[-1][k] for k in ("train_acc", "test_acc")
                  if hist and k in hist[-1]},
        "param_change_rel": change,
    }
    # placement: re-pack the last round's cohort (a pure function of the
    # round index — exactly what the round uploaded) and read where it and
    # the aggregated model live
    _, _, packed = api._pack_round(rounds - 1)
    cohort_devs = sorted(d.id for d in packed[0].sharding.device_set)
    model_devs = sorted(set.intersection(*(
        {d.id for d in leaf.sharding.device_set}
        for leaf in jax.tree.leaves(api.variables))))
    report["devices"] = {"cohort": cohort_devs, "model": model_devs}
    if kind == "spmd":
        everyone = sorted(d.id for d in jax.devices())
        for what, got in report["devices"].items():
            if got != everyone:
                failures.append(
                    f"{what} lives on devices {got}, not all of {everyone}")
    else:
        # which aggregation the compiled sim round carries: the Pallas
        # kernel on tpu, the jnp tree mean on cpu (the one backend rule)
        jaxpr = jax.make_jaxpr(api._round_fn_py)(
            api.variables, *packed, jnp.uint32(rounds - 1))
        report["aggregation"] = ("pallas" if "pallas_call" in str(jaxpr)
                                 else "jnp")
        if on_tpu() and report["aggregation"] != "pallas":
            failures.append("the sim round did not use the Pallas "
                            "aggregation kernel on tpu")
    api.release_prefetch()
    report["failures"] = failures
    return report, api.variables


def parity_check(err: float, param_change_rel: float) -> Dict:
    """Hold ``sim_spmd_param_rel_err`` to its tolerance: PARITY_FRACTION
    of how far training moved the parameters (itself held under
    MAX_PARAM_CHANGE, or the tolerance means nothing)."""
    tol = PARITY_FRACTION * param_change_rel
    _log(f"sim_spmd_param_rel_err {err:.3e} (tolerance {tol:.3e} = "
         f"{PARITY_FRACTION} x the {param_change_rel:.3e} the round moved "
         "the parameters)")
    failures = []
    if not param_change_rel < MAX_PARAM_CHANGE:
        failures.append(
            f"the round moved the parameters {param_change_rel:.3e} of "
            f"their norm (>= {MAX_PARAM_CHANGE}): not the API's init, or "
            "training diverged")
    if not err <= tol:
        failures.append(
            f"sim_spmd_param_rel_err {err:.3e} > tolerance {tol:.3e}")
    return {"sim_spmd_param_rel_err": err,
            "sim_spmd_param_rel_err_tol": tol,
            "param_change_rel": param_change_rel,
            "failures": failures}


def parity_leg(ds, model_name: str, task: str, *, per_round: int,
               batch_size: int, out_dir: str) -> Dict:
    """sim==spmd after one round of each driver at ``highest`` matmul
    precision (what tests/conftest.py sets for every parity test): the
    same entry point as the train legs, f32 products, no time for
    rounding differences to grow."""
    import jax

    from fedml_tpu.experiments.flagship_scale import param_rel_err

    reports, finals = {}, {}
    with jax.default_matmul_precision("highest"):
        for kind in ("sim", "spmd"):
            reports[kind], finals[kind] = train_leg(
                kind, ds, model_name, task, rounds=1, per_round=per_round,
                batch_size=batch_size, out_dir=out_dir)
        out = parity_check(param_rel_err(finals["sim"], finals["spmd"]),
                           reports["sim"]["param_change_rel"])
    out["failures"] = [f"{kind}: {msg}" for kind, rep in reports.items()
                       for msg in rep["failures"]] + out["failures"]
    out["first_round_s"] = {kind: rep["first_round_s"]
                            for kind, rep in reports.items()}
    return out


def _max_abs(a) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float64))))


def _check(name: str, err: float, tol: float, **extra) -> Dict:
    return {"kernel": name, "max_err": float(f"{err:.4g}"),
            "tol": float(f"{tol:.4g}"), "ok": bool(err <= tol), **extra}


def attention_errors(got, ref) -> Tuple[float, float]:
    """Two distances of a [B, S, H, D] attention output or gradient from
    its oracle: (largest element error as a fraction of max|ref|, largest
    per-row relative error past the first S/16 positions).

    A row is one position's D-vector; its error is taken against its own
    norm, floored at the mean row norm. The first norm sees the first
    positions, where values and errors are largest; it is blind to the
    late rows, averages over hundreds of keys a fraction of that size.
    The second sees those. It leaves the first positions to the first
    norm: there the softmax is over a handful of keys, dq is a difference
    of nearly equal terms (exactly zero for the first query), and the
    kernel's delta = sum(o * do) and XLA's sum(p * dp) round that
    difference differently — rounding against a norm of nearly nothing."""
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    norm = np.linalg.norm(ref, axis=-1)
    rows = (np.linalg.norm(got - ref, axis=-1)
            / np.maximum(norm, norm.mean()))
    return (float(np.max(np.abs(got - ref)) / np.max(np.abs(ref))),
            float(np.max(rows[:, ref.shape[1] // 16:])))


def kernel_leg(*, cohorts: Sequence[Tuple[int, Any]], dims: Sequence[int],
               topk_frac: float, attn_shape: Tuple[int, int, int, int],
               block_grid: Sequence[Tuple[int, int]]) -> Dict:
    """Every Pallas kernel in ``fedml_tpu/ops`` against its reference, on
    this backend: compiled on tpu, interpreted on cpu — never a choice
    made here (``fedml_tpu.utils.on_tpu``).

    ``cohorts`` are (clients, a model's tree of shapes) pairs for the
    stacked mean; ``dims`` are flat parameter counts for the quantize /
    top-k kernels; ``attn_shape`` is
    (B, S, H, D) for flash attention, run forward and backward at every
    ``block_grid`` pair against the ``highest``-precision oracle, held to
    a multiple of the default-precision XLA attention's own error (see
    FLASH_VS_XLA_ERR)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.aggregate import (mean_kernel_params,
                                         tree_weighted_mean_pallas,
                                         weighted_mean_flat_reference)
    from fedml_tpu.ops.flash_attention import flash_attention
    from fedml_tpu.ops.quantize import BLOCK, dequantize_int8, quantize_int8
    from fedml_tpu.ops.sparsify import (k_for, topk_dequantize,
                                        topk_quantize,
                                        topk_sparsify_reference)
    from fedml_tpu.parallel.sequence import reference_attention
    from fedml_tpu.utils import on_tpu

    interpret = not on_tpu()
    checks = []

    def timed(fn, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        return out, round(time.perf_counter() - t0, 3)

    for clients, shapes in cohorts:
        leaves, treedef = jax.tree.flatten(shapes)

        @jax.jit
        def make_cohort(key):
            # parameter-scale values; integer sample counts as weights
            k_w, *k_x = jax.random.split(key, len(leaves) + 1)
            return (treedef.unflatten(
                [0.05 * jax.random.normal(k, (clients,) + a.shape, a.dtype)
                 for k, a in zip(k_x, leaves)]),
                jax.random.randint(k_w, (clients,), 50, 150).astype(
                    jnp.float32))

        @jax.jit
        def distance(stacked, got, weights):
            # the oracle a leaf at a time: the stack may be gigabytes
            wants = [weighted_mean_flat_reference(a.reshape(clients, -1),
                                                  weights)
                     for a in jax.tree.leaves(stacked)]
            errs = [jnp.max(jnp.abs(b.reshape(-1) - want), initial=0.0)
                    for b, want in zip(jax.tree.leaves(got), wants)]
            return (jnp.max(jnp.stack(errs)), jnp.max(jnp.stack(
                [jnp.max(jnp.abs(want), initial=0.0) for want in wants])))

        stacked, weights = make_cohort(jax.random.key(clients))
        got, first_s = timed(jax.jit(lambda s, w: tree_weighted_mean_pallas(
            s, w, interpret=interpret)), stacked, weights)
        err, scale = distance(stacked, got, weights)
        kernel, xla = mean_kernel_params(shapes, clients)
        checks.append(_check(
            "aggregate.tree_weighted_mean_pallas", float(err),
            AGG_TOL * float(scale), shape=[clients, kernel + xla],
            kernel_params=kernel, first_call_s=first_s))
        del stacked, got

    for d in dims:
        key = jax.random.key(d)
        k_x, k_q = jax.random.split(key)
        x = 0.05 * jax.random.normal(k_x, (d,), jnp.float32)
        (q, scales), first_s = timed(lambda v, k: quantize_int8(
            v, k, interpret=interpret), x, k_q)
        deq, deq_s = timed(lambda a, b: dequantize_int8(
            a, b, d, interpret=interpret), q, scales)
        # stochastic rounding lands on one of the two neighbouring levels:
        # every element within one scale step of its block
        step = jnp.repeat(scales, BLOCK)[:d]
        checks.append(_check(
            "quantize.int8_round_trip",
            _max_abs(jnp.abs(deq - x) / step), 1.0 + 1e-6, shape=[d],
            unit="scale steps", first_call_s=first_s,
            dequant_first_call_s=deq_s))

        k = k_for(d, topk_frac)
        (idx, tq, tscales, residual), first_s = timed(
            lambda v, kk: topk_quantize(v, kk, k, interpret=interpret),
            x, k_q)
        dense, deq_s = timed(lambda a, b, c: topk_dequantize(
            a, b, c, d, interpret=interpret), idx, tq, tscales)
        ref_idx, _, _ = topk_sparsify_reference(np.asarray(x), k)
        off_support = len(np.setxor1d(np.asarray(idx), ref_idx))
        kept_step = jnp.repeat(tscales, BLOCK)[:k]
        kept_err = _max_abs(jnp.abs(dense[idx] - x[idx]) / kept_step)
        # wire + residual must rebuild the delta (error feedback's premise)
        rebuild = _max_abs(dense + residual - x) / _max_abs(x)
        checks.append(_check(
            "sparsify.topk_int8_round_trip", kept_err, 1.0 + 1e-6,
            shape=[d], k=k, unit="scale steps", first_call_s=first_s,
            dequant_first_call_s=deq_s))
        checks.append(_check("sparsify.topk_rebuild", rebuild, 1e-6,
                             shape=[d], k=k, unit="of max|x|"))
        checks.append(_check("sparsify.topk_support", off_support, 0,
                             shape=[d], k=k,
                             unit="indices off the numpy oracle's"))
        del x, q, scales, deq, idx, tq, tscales, residual, dense

    kq, kk, kv, kg = jax.random.split(jax.random.key(attn_shape[1]), 4)
    q_, k_, v_, g_ = (jax.random.normal(kx, attn_shape, jnp.float32)
                      for kx in (kq, kk, kv, kg))

    def fwd_bwd(attn):
        def f(q, k, v):
            out, vjp = jax.vjp(attn, q, k, v)
            return (out,) + tuple(vjp(g_))
        return jax.jit(f)

    def xla_attention(a, b, c):
        return reference_attention(a, b, c, causal=True)

    # the oracle (f32 products), and the same XLA attention at this
    # backend's default precision: what that precision costs, measured
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(fwd_bwd(xla_attention)(q_, k_, v_))
    xla = jax.block_until_ready(fwd_bwd(xla_attention)(q_, k_, v_))
    # [out, dq, dk, dv] x (element norm, row norm)
    xla_err = [attention_errors(x, w) for x, w in zip(xla, want)]
    for bq, bk in block_grid:
        got, first_s = timed(fwd_bwd(lambda a, b, c: flash_attention(
            a, b, c, True, bq, bk, interpret)), q_, k_, v_)
        err = [attention_errors(g, w) for g, w in zip(got, want)]
        for m, norm in enumerate(("", "_rows")):
            tol = [max(FLASH_F32_TOL[a > 0], FLASH_VS_XLA_ERR * x[m])
                   for a, x in enumerate(xla_err)]
            extra = dict(shape=list(attn_shape), block=[bq, bk],
                         unit=("of the row's norm" if m
                               else "of max|ref|"))
            checks.append(_check(
                "flash_attention.fwd" + norm, err[0][m], tol[0],
                xla_default_err=float(f"{xla_err[0][m]:.4g}"),
                first_call_s=first_s, **extra))
            # the gradient closest to (or furthest past) its tolerance
            worst = max((1, 2, 3), key=lambda a: err[a][m] / tol[a])
            checks.append(_check(
                "flash_attention.bwd" + norm, err[worst][m], tol[worst],
                dq_dk_dv_err=[float(f"{e[m]:.4g}") for e in err[1:]],
                xla_default_dq_dk_dv_err=[float(f"{x[m]:.4g}")
                                          for x in xla_err[1:]],
                **extra))
    failures = [f"{c['kernel']} {c.get('shape')} {c.get('block', '')}: "
                f"err {c['max_err']} > tol {c['tol']}"
                for c in checks if not c["ok"]]
    return {"interpreted": interpret, "checks": checks,
            "failures": failures}


def model_shapes(model_name: str, classes: int, sample_shape, **kwargs):
    """A zoo model's variables as shapes alone (no device work)."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models import create_model

    model = create_model(model_name, output_dim=classes, **kwargs)
    return jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros(sample_shape, jnp.float32),
        train=False))


def main() -> int:
    device = device_report()
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}",
          flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{device['platform']!r} ({device['kind']!r}) — no result",
              file=sys.stderr, flush=True)
        return 1

    from fedml_tpu.experiments.flagship_scale import param_rel_err
    from fedml_tpu.native import packer_status
    from fedml_tpu.ops.autotune import DEFAULT_BLOCK_GRID
    from fedml_tpu.utils import enable_persistent_compilation_cache

    logging.basicConfig(level=logging.INFO)
    # generate the federation from its seed; never read one an earlier run
    # cached under ~/.cache
    os.environ["FEDML_GEN_CACHE"] = ""
    cache_dir = enable_persistent_compilation_cache()
    cache_start = cache_entries(cache_dir)
    _log(f"compile cache {cache_dir}: {len(cache_start)} entries")

    seen = set(cache_start)

    def added_since_last_leg() -> Dict[str, int]:
        new = cache_entries(cache_dir) - seen
        seen.update(new)
        return _programs(new)

    ds, model_name, task = load_federation("fed_cifar100_gen", clients=50)
    legs: Dict[str, Dict] = {}
    finals = {}
    for kind in ("sim", "spmd"):
        _log(f"{kind} leg: {model_name} on fed_cifar100_gen, 3 rounds")
        report, finals[kind] = train_leg(
            kind, ds, model_name, task, rounds=3, per_round=10,
            batch_size=20, out_dir=OUT_DIR)
        report["cache_entries_added"] = added_since_last_leg()
        report["peak_bytes_in_use"] = peak_memory()
        legs[kind] = report
        added = sum(report["cache_entries_added"].values())
        _log(f"{kind} leg: first round {report['first_round_s']}s, later "
             f"{report['later_round_s']}s, devices {report['devices']}, "
             f"added cache entries {added}, "
             f"failures {report['failures']}")
    _log(f"cohort packer: {packer_status()}")
    # informational: where three default-precision rounds leave the two
    # drivers (see PARITY_FRACTION for why no tolerance applies to it)
    legs["spmd"]["sim_spmd_param_rel_err_3_rounds_default_precision"] = \
        param_rel_err(finals["sim"], finals["spmd"])
    del finals

    _log("parity leg: one round of each driver at highest precision")
    legs["parity"] = parity_leg(
        ds, model_name, task, per_round=10, batch_size=20,
        out_dir=os.path.join(OUT_DIR, "parity"))
    legs["parity"]["cache_entries_added"] = sum(
        added_since_last_leg().values())

    _log("kernel leg")
    from fedml_tpu.utils.flops import count_params
    # the benchmark's two stacked cohorts (the published 7x7 stem)
    resnet = model_shapes("resnet18_gn", 100, (1, 24, 24, 3),
                          small_images=False)
    cnn = model_shapes("cnn", 62, (1, 28, 28, 1))
    legs["kernels"] = kernel_leg(
        cohorts=((104, resnet), (256, cnn)),
        dims=(count_params(resnet), count_params(cnn)),
        topk_frac=0.01, attn_shape=(4, 2048, 4, 64),
        block_grid=DEFAULT_BLOCK_GRID)
    legs["kernels"]["cache_entries_added"] = sum(
        added_since_last_leg().values())
    legs["kernels"]["peak_bytes_in_use"] = peak_memory()
    for c in legs["kernels"]["checks"]:
        _log(f"kernel {c}")

    failures = [f"{leg}: {msg}" for leg, rep in legs.items()
                for msg in rep["failures"]]
    summary = {
        "ok": not failures,
        "device": device,
        "failures": failures,
        "packer": packer_status(),
        "compile_cache": {"dir": cache_dir,
                          "entries_before": len(cache_start),
                          "entries_after": len(seen)},
        "wall_s": round(time.perf_counter() - _T0, 1),
        "legs": legs,
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=2)
    for msg in failures:
        print(f"chip_smoke: FAILED {msg}", file=sys.stderr, flush=True)
    print("chip_smoke: report " + json.dumps(summary), flush=True)
    print(result_line(summary["ok"], device), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
