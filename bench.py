"""Benchmark suite v3 — flagship FedAvg throughput with MFU, heavier
conv/LM workloads, packing/fusion evidence, and time-to-target rows.

Workloads (BASELINE.md rows):
1. ``fedavg_femnist_cnn`` (headline): 10 clients/round, B=20, E=1, the
   2-conv CNN_DropOut (~1.2M params, 62 classes), ~340 samples/client — one
   full FedAvg round = host packing + transfer + local SGD for every sampled
   client + weighted aggregation, all one jitted program. Reported with the
   XLA cost model's FLOPs/round (utils/flops.cost_analysis) and MFU against
   the chip's bf16 peak (plus a bf16-compute variant).
2. ``resnet18_gn_fedcifar100``: same round shape at fed-CIFAR100 scale
   (ResNet-18 + GroupNorm, 24x24x3, B=20) — the heavier conv workload.
3. ``transformer_flash_s2048``: causal LM train step (4-layer, width 256,
   S=2048) with the Pallas flash-attention kernel; tokens/s plus the
   speedup over the XLA reference attention.
4. ``fedavg_powerlaw_1000``: the reference flagship shape (1000 power-law
   clients, 10/round, B=10, LR) — serial vs pipelined rounds/sec (the
   async round pipeline overlapping next-round pack+upload with the
   current dispatch, ``prefetch_hidden_ms`` = host time taken off the
   critical path), cohort-bucket packing wall-clock vs global-max
   packing, plus the padded-row reduction.
5. ``fedavg_fused_rounds``: R sampled rounds as one fused BLOCK (host-
   presampled cohorts at the block's cohort bucket under one lax.scan —
   both throughput levers composed) vs the cohort-packed host loop;
   ``fedavg_fused_device_sampling`` is the in-scan sampling variant as
   its own stage (its global-max compile must not delay the contract
   number).
6. ``federated_parallel_axes``: tokens/s of the ('clients','seq') and
   ('clients','tp') federated rounds (S=2048 on chip).
7. ``time_to_target_mnist_lr``: seconds/rounds to the reference's >75%
   MNIST+LR anchor at its exact config (benchmark/README.md:12).
8. ``time_to_target_acc``: seconds for the seeded blob federation to reach
   92% test accuracy (the fast trend metric; fully reproducible, seed=3).
0. ``smoke_chip`` (runs first in this process, also ``--smoke-chip``
   alone): a <=60 s stage — headline rounds/s + MFU + bf16 + one
   flash-attention step — persisted immediately. Every row carries a
   ``host`` tag.

``--stages=resnet,flash,...`` runs only the named stages. No device, no
measurement: a failed device probe, a stage that raises and a stage that
overruns its timeout each make the exit code non-zero; the rows of the
stages that did run are still printed. One process per chip: stages
whose legs are child processes that open the device run before this
process first touches JAX (``_CHILD_PROCESS_STAGES``); everything else
runs here.

``vs_baseline`` on the headline metric is measured against a faithful
reference-style sequential torch simulation **on this machine's CPU**
(fedml_api/standalone/fedavg/fedavg_api.py:46-141 semantics). The
reference's published hardware (4x RTX 2080Ti / A100s) is not reachable
from this box, so that ratio is a trend-tracking number, NOT an
8xA100 claim — it is labeled ``torch_cpu_this_host`` in the extras.

Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", "extra": {...per-workload...}}.
Full details land in runs/bench_details.json.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

CLIENTS_PER_ROUND = 10
SAMPLES_PER_CLIENT = 340
BATCH = 20
CLASSES = 62
BASELINE_ROUNDS = 2

# bf16 peak TFLOP/s per chip by device_kind substring (public specs).
# MFU is reported against bf16 peak even for f32 programs — conservative.
_PEAK_TFLOPS = [("v6", 918.0), ("v5p", 459.0), ("v5", 197.0),
                ("v4", 275.0), ("v3", 61.4), ("v2", 23.0)]

# HBM bandwidth GB/s per chip by device_kind substring (public specs);
# feeds the roofline note on the fused-headline stage.
_HBM_GBPS = [("v6", 1640.0), ("v5p", 2765.0), ("v5", 819.0),
             ("v4", 1228.0), ("v3", 900.0), ("v2", 700.0)]


def _device_hbm_gbps() -> float:
    import jax
    if os.environ.get("FEDML_TPU_HBM_GBPS"):
        return float(os.environ["FEDML_TPU_HBM_GBPS"])
    kind = jax.devices()[0].device_kind.lower()
    for key, bw in _HBM_GBPS:
        if key in kind:
            return bw
    return float("nan")


def _device_peak_tflops() -> float:
    import jax
    if os.environ.get("FEDML_TPU_PEAK_TFLOPS"):
        return float(os.environ["FEDML_TPU_PEAK_TFLOPS"])
    kind = jax.devices()[0].device_kind.lower()
    for key, peak in _PEAK_TFLOPS:
        if key in kind:
            return peak
    return float("nan")  # CPU or unknown: MFU not meaningful


#: the one backend rule's answer (utils.on_tpu): main() stores what the
#: probe child found, so no stage asks JAX again — and a stage that must
#: not open the device in this process can still size itself
_ON_TPU: "bool | None" = None


def _is_tpu() -> bool:
    """tpu -> real shapes, cpu -> smoke shapes, anything else -> an error
    (raised by utils.on_tpu, in the probe child under main())."""
    global _ON_TPU
    if _ON_TPU is None:  # a stage called without main()
        from fedml_tpu.utils import on_tpu
        _ON_TPU = on_tpu()
    return _ON_TPU


def _log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:8.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()


def make_data(seed: int = 0, hw: int = 28, chans: int = 1,
              classes: int = CLASSES, samples: int = SAMPLES_PER_CLIENT):
    rng = np.random.RandomState(seed)
    x = rng.randn(CLIENTS_PER_ROUND, samples, hw, hw, chans).astype(
        np.float32)
    y = rng.randint(0, classes,
                    (CLIENTS_PER_ROUND, samples)).astype(np.int32)
    return x, y


def _make_api(model_name: str, hw: int, chans: int, classes: int,
              timed_rounds: int, samples: int = SAMPLES_PER_CLIENT,
              compute_dtype=None, clients: int = CLIENTS_PER_ROUND):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.data.base import FederatedDataset
    from fedml_tpu.models import create_model
    from fedml_tpu.trainer.functional import TrainConfig

    x, y = make_data(hw=hw, chans=chans, classes=classes, samples=samples)
    train_local = {c: (x[c], y[c]) for c in range(clients)}
    ds = FederatedDataset.from_client_arrays(
        train_local, {c: None for c in range(clients)}, classes)
    model = create_model(model_name, output_dim=classes)
    api = FedAvgAPI(ds, model, config=FedAvgConfig(
        comm_round=timed_rounds, client_num_per_round=clients,
        frequency_of_the_test=10**9,
        train=TrainConfig(epochs=1, batch_size=BATCH, lr=0.1,
                          compute_dtype=compute_dtype)))
    return api


def _round_costs(api) -> "tuple[float, float, str | None]":
    """(FLOPs, bytes accessed, error) of the compiled round program — the
    XLA cost model's post-fusion accounting, so the bytes figure is the
    compiler's own HBM-traffic estimate for the exact program that runs.
    ``error`` carries the probe failure instead of swallowing it: a
    ResNet18-GN stage once silently nulled its flops/MFU because this
    except hid the cause."""
    import jax.numpy as jnp

    _, _, args = api._pack_round(0)
    try:
        # lower the EXACT jitted round program run_round dispatches —
        # round_idx is its final traced operand (lr_decay_round schedule);
        # re-jitting a wrapper would constant-fold it and pay a second
        # trace+compile of the round
        analysis = (api._round_fn.lower(api.variables, *args, jnp.uint32(0))
                    .compile().cost_analysis())
        costs = dict(analysis or {})
        flops = float(costs.get("flops", float("nan")))
        bytes_acc = float(costs.get("bytes accessed", float("nan")))
        err = ("cost model returned no flops for the lowered round "
               "program" if flops != flops else None)
        return flops, bytes_acc, err
    except Exception as exc:  # noqa: BLE001 — reported, not swallowed
        return float("nan"), float("nan"), repr(exc)


def _analytic_round_flops(api) -> float:
    """The conv/GroupNorm analytic cost model (utils/flops.analytic_flops)
    applied to the exact round program: jaxpr-traced matmul/conv terms,
    scan trip counts multiplied in (XLA's cost model bills a scan body
    ONCE regardless of trip count, so on multi-batch local loops the
    analytic figure is the honest per-round count)."""
    import jax.numpy as jnp

    from fedml_tpu.utils.flops import analytic_flops

    _, _, args = api._pack_round(0)
    return analytic_flops(api._round_fn_py, api.variables, *args,
                          jnp.uint32(0))


def _round_flops(api) -> "tuple[float, str]":
    """(FLOPs, source) of the round program: the XLA cost model when it
    answers, else the analytic conv/GroupNorm jaxpr count — the cost
    model has returned nothing for some conv programs, and a null where
    a number is expected must not serialize as honest-looking evidence.
    Raises only when BOTH models fail on chip."""
    flops, _, err = _round_costs(api)
    if not err:
        return flops, "xla_cost_model"
    try:
        return _analytic_round_flops(api), "analytic_conv_gn_jaxpr"
    except Exception as exc:  # noqa: BLE001
        if _is_tpu():
            raise RuntimeError(
                f"round cost probes failed on chip: xla={err}; "
                f"analytic={exc!r}") from exc
        return float("nan"), f"unavailable ({err})"


def _nonfinite(x) -> bool:
    """Shared nan/inf predicate for JSON sanitizing — emitted artifacts
    must stay RFC-8259 valid (bare NaN/Infinity literals break every
    strict parser — jq, JSON.parse, Go/Rust)."""
    return isinstance(x, float) and (x != x or x in (float("inf"),
                                                     float("-inf")))


def _nn(x):
    """nan/inf -> None (same predicate as the recursive _no_nan)."""
    return None if _nonfinite(x) else x


def _round_timeline(timer, last: int = 10) -> list:
    """The newest per-round snapshot-delta records from the timer's
    flight-recorder ring (utils/tracing.py begin/end_round) — stage rows
    carry a per-round phase timeline in runs/*_details.json instead of
    only run-lifetime means, so an MFU/rounds-per-sec regression is
    attributable to WHICH rounds, not just the total."""
    return timer.round_records()[-last:]


def _bench_rounds(api, timed_rounds: int) -> float:
    import jax

    api.run_round(0)  # compile
    jax.block_until_ready(api.variables)
    t0 = time.perf_counter()
    for r in range(1, timed_rounds + 1):
        api.run_round(r)
    jax.block_until_ready(api.variables)
    return timed_rounds / (time.perf_counter() - t0)


def bench_fedavg_cnn() -> dict:
    # CPU smoke: XLA-CPU conv backward runs ~1000x below the chip, so shrink
    # to 2 clients x 2 batches — the CPU numbers are only a does-it-run
    # check; the driver measures on the real chip
    tpu = _is_tpu()
    timed = 100 if tpu else 2
    api = _make_api("cnn", 28, 1, CLASSES, timed + 1,
                    samples=SAMPLES_PER_CLIENT if tpu else 2 * BATCH,
                    clients=CLIENTS_PER_ROUND if tpu else 2)
    flops, flops_src = _round_flops(api)
    rps = _bench_rounds(api, timed)
    achieved = rps * flops  # FLOP/s through the round program
    peak = _device_peak_tflops() * 1e12
    return {
        "rounds_per_sec": round(rps, 3),
        "round_flops": _nn(flops),
        "round_flops_source": flops_src,
        "achieved_tflops": _nn(round(achieved / 1e12, 3)),
        "mfu": _nn(round(achieved / peak, 4)) if peak == peak else None,
        "phase_ms": {k: round(v * 1e3, 3)
                     for k, v in api.timer.means().items()},
        "round_timeline": _round_timeline(api.timer),
    }


def bench_fedavg_cnn_bf16() -> dict:
    """Flagship workload with the bf16 compute path (MXU-native inputs;
    masters stay f32). TPU-only — CPU bf16 is emulated and meaningless."""
    if not _is_tpu():
        return {"skipped": "bf16 path is TPU-only"}
    api = _make_api("cnn", 28, 1, CLASSES, 101, compute_dtype="bfloat16")
    rps = _bench_rounds(api, 100)
    return {"rounds_per_sec": round(rps, 3)}


def bench_fedavg_cnn_fused_headline() -> dict:
    """Headline workload with both throughput levers composed: R rounds
    per dispatch under one ``lax.scan`` and bf16 compute with f32
    aggregation. Emits the XLA-cost-model roofline alongside the
    MFU figure so the measured ceiling travels with the claim: the FEMNIST
    CNN (reference arch: fedml_api/model/cv/cnn.py CNN_DropOut) is a
    small-operand workload — conv1 contracts only 9 values per output
    (3x3 kernel, C_in=1) against a 128x128 MXU, batch rows fill 20/128 of
    the dense layers' systolic input — so its MFU ceiling is set by
    workload geometry and HBM traffic, not dispatch count."""
    import jax

    import jax

    tpu = _is_tpu()
    R = 20 if tpu else 3
    # one dtype per backend: bf16 IS the chip headline (the f32 per-round
    # number is its own stage); a single program keeps the stage inside
    # one timeout and avoids losing a finished measurement to a later
    # phase's failure
    which = "bf16" if tpu else "f32"
    api = _make_api("cnn", 28, 1, CLASSES, 10**9,
                    samples=SAMPLES_PER_CLIENT if tpu else 2 * BATCH,
                    clients=CLIENTS_PER_ROUND if tpu else 2,
                    compute_dtype="bfloat16" if tpu else None)
    fused = api.fused_rounds()
    fused.run_rounds(0, R)  # compile + warm
    jax.block_until_ready(api.variables)
    best = 0.0
    for i in (1, 2):  # best of two blocks (a recompile can hit one)
        t0 = time.perf_counter()
        fused.run_rounds(i * R, R)
        jax.block_until_ready(api.variables)
        best = max(best, R / (time.perf_counter() - t0))
    # cost model of the SAME scan body the timing dispatched, taken at
    # trip count 1: XLA's cost analysis counts a scan body ONCE regardless
    # of trip count (verified: identical totals for R=1/3/6), so the R=1
    # block IS the per-round accounting, with no ambiguity if a future
    # XLA starts multiplying by trip count. Runs after the timed blocks
    # are banked (it costs an extra compile).
    try:
        round_costs = fused.cost_analysis(rounds=1)
        flops = float(round_costs.get("flops", float("nan")))
        bytes_acc = float(round_costs.get("bytes accessed", float("nan")))
    except Exception as exc:  # noqa: BLE001
        if tpu:  # a null where a number is expected must fail loudly
            raise RuntimeError(
                f"fused-round cost probe failed on chip: {exc!r}") from exc
        flops = bytes_acc = float("nan")
    if tpu and flops != flops:
        raise RuntimeError("fused-round cost probe returned no flops on "
                           "chip (nulls must not pass)")
    peak = _device_peak_tflops() * 1e12
    bw = _device_hbm_gbps() * 1e9
    ok = flops == flops
    achieved = best * flops if ok else float("nan")
    out: dict = {
        "rounds_per_scan": R,
        f"rounds_per_sec_fused_{which}": round(best, 3),
        "mfu_program": which,
        "round_flops": flops if ok else None,
        "achieved_tflops": round(achieved / 1e12, 3) if ok else None,
        "mfu": (round(achieved / peak, 4)
                if ok and peak == peak else None),
    }
    roofline = _roofline(flops, bytes_acc, peak, bw)
    if roofline is not None:
        out["roofline"] = roofline
    return out


def _roofline(flops: float, bytes_acc: float, peak: float,
              bw: float) -> "dict | None":
    """Roofline verdict from the XLA cost model's post-fusion accounting:
    arithmetic intensity vs the HBM ridge, and the MFU ceiling the
    measured AI permits. None when any input is unavailable (NaN)."""
    if not (flops == flops and bytes_acc == bytes_acc
            and bw == bw and peak == peak and bytes_acc > 0 and bw > 0
            and peak > 0):
        return None
    ai = flops / bytes_acc
    ridge = peak / bw
    return {
        "peak_tflops_bf16": round(peak / 1e12, 1),
        "hbm_gbps": round(bw / 1e9),
        "bytes_accessed_per_round": bytes_acc,
        "arithmetic_intensity_flop_per_byte": round(ai, 2),
        "ridge_flop_per_byte": round(ridge, 2),
        "memory_bound": bool(ai < ridge),
        "mfu_ceiling_at_measured_ai": round(min(1.0, ai * bw / peak), 4),
        "note": ("XLA post-fusion accounting. Roofline MFU ceiling = "
                 "AI*BW/peak when AI < ridge (memory-bound). On top of "
                 "bandwidth, MXU granularity caps useful occupancy: "
                 "conv1 contraction dim 9 (<128 rows), B=20 batch rows "
                 "(<128) on the dense layers — the small-CNN headline "
                 "cannot approach matmul-workload MFU regardless of "
                 "dispatch amortization."),
    }


def bench_resnet18_gn() -> dict:
    """Heavier conv workload; the FLOPs column now carries an analytic
    conv/GroupNorm fallback (utils/flops.analytic_flops) so the row
    reports MFU like the headline even when the XLA cost model returns
    nothing for the conv round program. The analytic jaxpr count is
    always emitted alongside for cross-checking — unlike XLA's cost
    model it multiplies scan trip counts, so on multi-batch local loops
    it is the honest per-round figure."""
    tpu = _is_tpu()
    timed = 20 if tpu else 2
    api = _make_api("resnet18_gn", 24, 3, 100, timed + 1,
                    samples=5 * BATCH if tpu else BATCH,
                    clients=CLIENTS_PER_ROUND if tpu else 2)
    flops, flops_src = _round_flops(api)
    if flops_src == "analytic_conv_gn_jaxpr":
        analytic = flops  # already computed as the fallback — don't retrace
    else:
        try:
            analytic = _analytic_round_flops(api)
        except Exception:  # noqa: BLE001 — cross-check only, never fatal
            analytic = float("nan")
    rps = _bench_rounds(api, timed)
    achieved = rps * flops
    peak = _device_peak_tflops() * 1e12
    return {
        "rounds_per_sec": round(rps, 3),
        "round_flops": _nn(flops),
        "round_flops_source": flops_src,
        "round_flops_analytic": _nn(analytic),
        "achieved_tflops": _nn(round(achieved / 1e12, 3)),
        "mfu": _nn(round(achieved / peak, 4)) if peak == peak else None,
    }


def bench_transformer_flash(seq_len: int = 2048, batch: int = 4,
                            steps: int = 10) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.models.transformer import TransformerLM

    interpret = not _is_tpu()
    if interpret:
        seq_len, batch, steps = 512, 2, 2  # CPU smoke shapes

    vocab, width, num_heads = 1024, 256, 4
    head_dim = width // num_heads  # the autotune key derives from THESE
    tokens = np.random.RandomState(0).randint(
        0, vocab, (batch, seq_len)).astype(np.int32)

    def tokens_per_sec(attn_fn) -> float:
        model = TransformerLM(vocab_size=vocab, width=width, depth=4,
                              num_heads=num_heads, max_len=seq_len,
                              attn_fn=attn_fn)
        variables = model.init(jax.random.key(0), jnp.asarray(tokens[:1]),
                               train=False)

        @jax.jit
        def step(v, x):
            def loss(params):
                logits = model.apply({"params": params}, x, train=False)
                return jnp.mean(
                    optax.softmax_cross_entropy_with_integer_labels(
                        logits[:, :-1], x[:, 1:]))
            g = jax.grad(loss)(v["params"])
            return {"params": jax.tree.map(
                lambda p, gg: p - 1e-3 * gg, v["params"], g)}

        x = jnp.asarray(tokens)
        variables = step(variables, x)  # compile
        jax.block_until_ready(variables)
        t0 = time.perf_counter()
        for _ in range(steps):
            variables = step(variables, x)
        jax.block_until_ready(variables)
        return steps * batch * seq_len / (time.perf_counter() - t0)

    # shape-aware auto-selection: earlier chip runs disagreed (the
    # 128x128 kernel read 1.376x OVER reference attention once and
    # 0.70x/0.895x UNDER it later; those records predate this
    # installation), so one fixed block shape can't be presumed optimal
    # — or Pallas presumed the winner at all. The ops.autotune subsystem
    # races the block grid against the
    # XLA reference with THIS stage's full LM-train-step timer, records
    # the decision in the persistent cache (so launchers dispatch the
    # same winner), and the row reports winner + block per shape: either
    # speedup >= 1.0 or the row shows the auto-selected XLA winner — the
    # slower path is never silently dispatched.
    from fedml_tpu.ops import autotune as at

    grid = ((128, 128),) if interpret else at.DEFAULT_BLOCK_GRID
    tps_by_label = {}

    def measure(label, attn_fn):
        # autotune minimizes seconds; invert tokens/s so the recorded
        # decision IS the decision this row's tokens/s claim is made from
        tps = tokens_per_sec(None if label == "xla" else attn_fn)
        tps_by_label[label] = round(tps, 1)
        return 1.0 / max(tps, 1e-9)

    if not at.block_candidates(seq_len, grid):
        # indivisible seq_len: the kernel's grid requires s % block == 0
        # (its min(block, s) clamp only helps when s < block), so measure
        # the XLA reference only and say so, instead of crashing or
        # silently reporting zeros
        ref_tps = tokens_per_sec(None)
        return {
            "tokens_per_sec": round(ref_tps, 1),
            "seq_len": seq_len,
            "selected_impl": "xla",
            "flash_skipped_indivisible_seq_len": seq_len,
            "note": "no autotune block divides seq_len; reference "
                    "attention only",
        }
    # refresh=True: the bench is the evidence generator — re-time every
    # window so a stale cached decision can never hide a regression; the
    # fresh decision lands in the shared cache for every other consumer.
    # CPU smoke runs race INTERPRET-mode kernels, whose timings say
    # nothing about any deployment — keep those decisions out of the
    # shared cache (README: the CPU contract is untimed XLA fallback)
    if interpret:
        import tempfile
        cache = at.AutotuneCache(
            tempfile.mkdtemp(prefix="fedml_autotune_cpu_smoke_"))
    else:
        cache = at.default_cache()
    decision = at.autotune_attention(
        seq_len, head_dim, num_heads=num_heads, batch=batch,
        causal=True, grid=grid, measure=measure, interpret=interpret,
        cache=cache, refresh=True)
    if decision.label not in tps_by_label:
        # FEDML_TPU_AUTOTUNE=0: the kill switch won over refresh=True and
        # nothing was raced — time only the dispatched winner (cached or
        # the XLA default) so the row still carries throughput evidence
        from fedml_tpu.ops.flash_attention import make_flash_attention
        attn = (None if decision.impl == "xla" else
                make_flash_attention(decision.block_q, decision.block_k,
                                     interpret))
        tps_by_label[decision.label] = round(tokens_per_sec(attn), 1)
    ref_tps = tps_by_label.get("xla")
    flash_tps = max((v for k, v in tps_by_label.items() if k != "xla"),
                    default=None)
    return {
        "tokens_per_sec": tps_by_label[decision.label],
        "seq_len": seq_len,
        "selected_impl": decision.impl,
        "selected_block_qk": (f"{decision.block_q}x{decision.block_k}"
                              if decision.impl == "pallas" else None),
        "decision_source": decision.source,
        "tokens_per_sec_by_candidate": tps_by_label,
        "speedup_vs_reference_attention": (
            round(flash_tps / ref_tps, 3) if flash_tps and ref_tps
            else None),
        "autotune_cache": cache.path,
    }


def bench_powerlaw_1000() -> dict:
    """The reference flagship shape: 1000 power-law clients (LEAF MNIST
    size distribution), 10 sampled/round, B=10 — the workload where
    cohort-bucket packing matters. Reports serial vs PIPELINED rounds/s
    (the async round pipeline, parallel/prefetch.py: next round's pack +
    upload overlapped with the current dispatch), the hidden pack+upload
    time per round (``prefetch_hidden_ms``; ``prefetch_wait`` ≈ 0 once
    warm is the pipelined win condition), and the padded-row reduction vs
    global-max packing (a direct per-round FLOP proxy; contract: >=3x).
    The serial numbers come from ``prefetch_depth=0`` —
    provably today's path (same flag the ``FEDML_TPU_PREFETCH=0`` kill
    switch forces)."""
    import jax

    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.core.sampling import sample_clients
    from fedml_tpu.data.synthetic import make_powerlaw_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig

    tpu = _is_tpu()
    N = 1000
    timed = 50 if tpu else 8
    ds = make_powerlaw_blob_federated(client_num=N, dim=64, class_num=10,
                                      seed=2)

    def make_api(pack="cohort", prefetch_depth=0):
        return FedAvgAPI(ds, LogisticRegression(num_classes=10),
                         config=FedAvgConfig(
                             comm_round=timed + 1, client_num_per_round=10,
                             frequency_of_the_test=10**9, pack=pack,
                             prefetch_depth=prefetch_depth,
                             train=TrainConfig(epochs=1, batch_size=10,
                                               lr=0.03)))

    def timed_rounds(api):
        # warm every bucket shape before timing (bounded: <= log2 shapes)
        warmed = set()
        for r in range(timed + 1):
            n_pad = ds.cohort_padded_len(sample_clients(r, N, 10), 10)
            if n_pad not in warmed:
                warmed.add(n_pad)
                api.run_round(r)
        jax.block_until_ready(api.variables)
        before = api.prefetch_stats() or {}
        phases0 = dict(api.timer.totals)
        t0 = time.perf_counter()
        for r in range(1, timed + 1):
            api.run_round(r)
        jax.block_until_ready(api.variables)
        rps = timed / (time.perf_counter() - t0)
        after = api.prefetch_stats() or {}
        window = {k: after[k] - before.get(k, 0) for k in after}
        # the durations are the round timer's spans
        window.update({k: v - phases0.get(k, 0.0)
                       for k, v in dict(api.timer.totals).items()})
        return rps, window

    api_serial = make_api()
    rps_serial, _ = timed_rounds(api_serial)
    api_pipe = make_api(prefetch_depth=2)
    rps_pipe, pf = timed_rounds(api_pipe)
    glob = ds.padded_len(10)
    rows_g = rows_c = 0
    for r in range(1, timed + 1):
        idxs = sample_clients(r, N, 10)
        rows_g += glob * len(idxs)
        rows_c += ds.cohort_padded_len(idxs, 10) * len(idxs)
    # wall-clock under global-max packing on the SAME workload, so the
    # padding win is evidenced in measured time, not only the FLOP proxy
    # (serial on both sides: the packing comparison must not conflate the
    # pipeline lever)
    api_g = make_api(pack="global")
    # one warm round suffices: global pack has a single compiled shape
    rps_global = _bench_rounds(api_g, timed)
    return {
        # the default config is pipelined — that is the dispatched path
        "rounds_per_sec": round(rps_pipe, 3),
        "rounds_per_sec_serial": round(rps_serial, 3),
        "rounds_per_sec_pipelined": round(rps_pipe, 3),
        "pipeline_speedup_x": round(rps_pipe / rps_serial, 3),
        # host ms per round removed from the critical path (the worker's
        # produce time minus any wait the caller paid)
        "prefetch_hidden_ms": round(
            max(0.0, pf.get("produce", 0.0) - pf.get("prefetch_wait", 0.0))
            / timed * 1e3, 3),
        "prefetch_wait_ms": round(
            pf.get("prefetch_wait", 0.0) / timed * 1e3, 3),
        "prefetch_hits": pf.get("hits"),
        "prefetch_misses": pf.get("misses"),
        "rounds_per_sec_global_pack": round(rps_global, 3),
        "cohort_pack_speedup_x": round(rps_serial / rps_global, 2),
        "clients_total": N,
        "padded_row_reduction_vs_global": round(rows_g / rows_c, 2),
        "phase_ms": {k: round(v * 1e3, 3)
                     for k, v in api_pipe.timer.means().items()},
        "phase_ms_serial": {k: round(v * 1e3, 3)
                            for k, v in api_serial.timer.means().items()},
        "note": "serial = prefetch_depth 0, the pre-pipeline path. On a "
                "1-core CPU smoke host the prefetch worker timeshares "
                "with XLA compute and pipelined can read SLOWER; the "
                "overlap win is a chip-host claim (host cores idle during "
                "device dispatch) — judge tpu-tagged rows by "
                "prefetch_wait ≈ 0 with prefetch_hidden_ms > 0.",
    }


def bench_population_scale() -> dict:
    """The million-client population-virtualization axis (ROADMAP
    north-star): FedAvg rounds at population ∈ {1k, 100k, 1M} with a
    CONSTANT cohort, clients materialized through the tiered client-state
    store (fedml_tpu/state/) instead of resident dicts. Each leg runs in
    its own subprocess (``python -m fedml_tpu.state.population``) because
    peak host RSS is a process-lifetime high-water mark — sharing one
    process would let an earlier leg's peak mask a later leg's.

    Each leg opens the device itself, and a chip belongs to one process
    at a time: this function must run before its process first touches
    JAX (``_CHILD_PROCESS_STAGES``), so nothing here may import jax —
    ``_is_tpu()`` is the probe child's answer.

    Acceptance claims this stage measures:
    - **throughput parity at 1k**: virtualized rounds/sec within 10% of
      the resident-dict path on the SAME population/cohort/model
      (``virtual_vs_resident_1k_x``);
    - **flat memory**: peak RSS at 1M within 2x of 100k
      (``rss_1m_over_100k_x``) — population grew 10x, memory didn't,
      because residency is bounded by the cache budget;
    - store-tier evidence per leg: ``state_cache_hits/misses/evictions``,
      ``state_bytes_per_round``, ``host_rss_peak_mb``.
    """
    import subprocess

    rounds = 30 if _is_tpu() else 6
    cohort = 10

    def leg(population: int, mode: str, timeout_s: int = 240) -> dict:
        cmd = [sys.executable, "-m", "fedml_tpu.state.population",
               "--population", str(population), "--rounds", str(rounds),
               "--cohort", str(cohort), "--mode", mode]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=timeout_s)
        except subprocess.TimeoutExpired:
            return {"error": f"population leg {mode}@{population} hung "
                             f"for {timeout_s}s"}
        if proc.returncode != 0:
            return {"error": f"population leg {mode}@{population} "
                             f"failed: {proc.stderr[-500:]}"}
        try:
            return json.loads(proc.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            return {"error": f"population leg {mode}@{population} "
                             f"unparseable: {proc.stdout[-300:]}"}

    legs = {
        "resident_1k": leg(1_000, "resident"),
        "virtual_1k": leg(1_000, "virtual"),
        "virtual_100k": leg(100_000, "virtual"),
        "virtual_1m": leg(1_000_000, "virtual", timeout_s=360),
    }

    def rps(row):
        return row.get("rounds_per_sec") or float("nan")

    def rss(row):
        return row.get("host_rss_peak_mb") or float("nan")

    parity = rps(legs["virtual_1k"]) / rps(legs["resident_1k"])
    rss_ratio = rss(legs["virtual_1m"]) / rss(legs["virtual_100k"])
    out = {
        "legs": legs,
        "rounds_per_leg": rounds,
        "cohort": cohort,
        # the acceptance ratios, flat
        "virtual_vs_resident_1k_x": _nn(round(parity, 3)),
        "rss_1m_over_100k_x": _nn(round(rss_ratio, 3)),
        "rss_mb_by_population": {
            k: _nn(rss(v)) for k, v in legs.items()},
        "rounds_per_sec_by_population": {
            k: _nn(rps(v)) for k, v in legs.items()},
        "memory_flat_1m_within_2x_100k": bool(rss_ratio == rss_ratio
                                              and rss_ratio <= 2.0),
        "throughput_parity_within_10pct": bool(parity == parity
                                               and parity >= 0.9),
        "note": "each leg is its own subprocess (ru_maxrss is a process "
                "high-water mark); resident@1M is deliberately absent — "
                "the resident-dict path at 10^6 clients is the memory "
                "wall this subsystem removes",
    }
    failed = sorted(k for k, v in legs.items() if "error" in v)
    if failed:
        out["error"] = f"population legs failed: {', '.join(failed)}"
    # the dedicated artifact the acceptance criteria point at
    _write_artifact("population_scale.json", out)
    return out


def bench_cross_silo_compression() -> dict:
    """The cross-silo WIRE cost axis: the same federation run at policy
    ``none`` vs ``topk_ef_int8`` (top-k + error feedback uplink, mirror
    delta downlink — comm/policy.py), with ``comm_bytes_up``/
    ``comm_bytes_down`` measured from the ACTUAL encoded frames the
    transport ships (RoundTimer counters fed by the comm backends). The
    BENCH trajectory can now track bytes/round the way it tracks
    rounds/sec: on a WAN-bound cross-silo deployment the compression
    ratio IS the round-rate multiplier, so a regression here is a
    regression in the paper's own bottleneck dimension."""
    from fedml_tpu.algorithms.fedavg_cross_silo import run_fedavg_cross_silo
    from fedml_tpu.comm.policy import parse_policy
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig
    from fedml_tpu.utils.tracing import RoundTimer

    rounds, workers = 10, 4
    ds = make_blob_federated(client_num=workers, dim=256, class_num=10,
                             n_samples=800, seed=0, noise=10.0)
    tcfg = TrainConfig(epochs=1, batch_size=20, lr=0.05)

    def run(policy):
        timer = RoundTimer()
        t0 = time.perf_counter()
        _, history = run_fedavg_cross_silo(
            ds, LogisticRegression(num_classes=10), worker_num=workers,
            comm_round=rounds, train_cfg=tcfg, compression=policy,
            timer=timer)
        wall = time.perf_counter() - t0
        total = timer.comm_bytes_up + timer.comm_bytes_down
        return {
            "rounds_per_sec": round(rounds / wall, 3),
            "bytes_per_round_up": round(timer.comm_bytes_up / rounds, 1),
            "bytes_per_round_down": round(timer.comm_bytes_down / rounds,
                                          1),
            "bytes_per_round_total": round(total / rounds, 1),
            "final_test_loss": _nn(history[-1]["test_loss"]
                                   if history else float("nan")),
            "final_test_acc": _nn(history[-1]["test_acc"]
                                  if history else float("nan")),
            "round_timeline": _round_timeline(timer),
        }

    # resolved instances, not strings: a set $FEDML_TPU_COMPRESSION must
    # not silently override BOTH legs of the comparison into one policy
    none = run(parse_policy("none"))
    topk = run(parse_policy("topk_ef_int8:0.05"))
    return {
        "policy_none": none,
        "policy_topk_ef_int8": topk,
        "compression_ratio_x": round(none["bytes_per_round_total"]
                                     / max(1.0,
                                           topk["bytes_per_round_total"]),
                                     2),
        "loss_delta_vs_none": _nn(topk["final_test_loss"]
                                  - none["final_test_loss"]),
        "note": "INPROC wire-codec transport on one host: bytes are real "
                "encoded frames, rounds/sec excludes WAN latency — the "
                "ratio is the wire-bound speedup a DCN/WAN deployment "
                "realizes. Downlink round 0 is full precision (silos "
                "hold no base), amortized across the window.",
    }


def bench_round_overheads() -> dict:
    """Round-close I/O on vs off the critical path: the same federation
    schedule (seed, cohort sampling, compression policy) run with the
    synchronous control-plane checkpointer (``--checkpoint_sync``
    semantics: capture + serialize + fsync + publish all inline on the
    round thread) vs the async writer (round thread pays the host
    capture only; serialize/fsync ride the writer thread with depth-1
    newest-wins coalescing). Both legs must close every round on an
    identical ledger schedule — durability moved threads, the CONTENT
    that replay reads moved nowhere — so the artifact carries a
    ``ledger_replay_identical`` oracle next to the speedup. Also
    reports the codec (jitted donated-buffer top-k vs the numpy parity
    oracle) and the silo residual write-back (StoreFlusher) in
    microbench form, so every round-close overhead the async PR moved
    off the hot path has a number."""
    import shutil
    import tempfile

    from fedml_tpu.algorithms.fedavg_cross_silo import run_fedavg_cross_silo
    from fedml_tpu.comm.policy import parse_policy
    from fedml_tpu.control.checkpoint import ServerControlCheckpointer
    from fedml_tpu.control.failover_harness import ledger_schedule
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig
    from fedml_tpu.utils.tracing import RoundTimer

    rounds, workers = 10, 4
    ds = make_blob_federated(client_num=workers, dim=256, class_num=10,
                             n_samples=800, seed=0, noise=10.0)
    tcfg = TrainConfig(epochs=1, batch_size=20, lr=0.05)
    root = tempfile.mkdtemp(prefix="fedml_round_overheads_")

    def read_schedule(ckpt_dir):
        cp = ServerControlCheckpointer(ckpt_dir)
        try:
            return ledger_schedule(cp.read_ledger())
        finally:
            cp.close()

    def leg(name, sync):
        ckpt_dir = os.path.join(root, name, "server_ckpt")
        obs_dir = os.path.join(root, name, "obs")
        timer = RoundTimer()
        t0 = time.perf_counter()
        run_fedavg_cross_silo(
            ds, LogisticRegression(num_classes=10), worker_num=workers,
            comm_round=rounds, train_cfg=tcfg,
            compression=parse_policy("topk_ef_int8:0.05"),
            server_checkpoint_dir=ckpt_dir, checkpoint_sync=sync,
            obs_dir=obs_dir, timer=timer)
        wall = time.perf_counter() - t0
        g, c = timer.gauges, timer.counters
        cap = float(g.get("cp_capture_ms", 0.0))
        flush = float(g.get("cp_flush_ms", 0.0))
        # what the ROUND THREAD blocks on at close: sync runs capture
        # and flush inline; async hands off after the capture
        crit = (cap + flush) if sync else cap
        return {
            "rounds_per_sec": round(rounds / wall, 3),
            "cp_capture_ms": _nn(round(cap, 3)),
            "cp_flush_ms": _nn(round(flush, 3)),
            "critical_path_ms": _nn(round(crit, 3)),
            "codec_encode_ms": _nn(round(
                float(g.get("codec_encode_ms", 0.0)), 3)),
            "cp_fsync_total": int(c.get("cp_fsync_total", 0)),
            "cp_ledger_fsyncs": int(c.get("cp_ledger_fsyncs", 0)),
            "obs_fsync_batches": int(c.get("obs_fsync_batches", 0)),
            "cp_writer_queue_coalesced": int(
                c.get("cp_writer_queue_coalesced", 0)),
            "round_timeline": _round_timeline(timer),
        }, read_schedule(ckpt_dir)

    def codec_microbench():
        import jax
        import jax.numpy as jnp
        import numpy as np
        from fedml_tpu.ops.sparsify import (topk_densify,
                                            topk_sparsify_donated,
                                            topk_sparsify_reference)
        d, k, reps = 1 << 16, 1 << 12, 20
        x = np.random.default_rng(0).standard_normal(d).astype(np.float32)
        jx = jnp.asarray(x)
        idx, vals, _ = topk_sparsify_donated(jnp.asarray(x), k)  # warm jit
        jax.block_until_ready((idx, vals))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = topk_sparsify_donated(jnp.asarray(x), k)
            jax.block_until_ready(out)
        enc = (time.perf_counter() - t0) * 1e3 / reps
        t0 = time.perf_counter()
        for _ in range(reps):
            topk_sparsify_reference(x, k)
        enc_ref = (time.perf_counter() - t0) * 1e3 / reps
        jax.block_until_ready(topk_densify(idx, vals, d))  # warm jit
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(topk_densify(idx, vals, d))
        dec = (time.perf_counter() - t0) * 1e3 / reps
        r_idx, r_vals, _ = topk_sparsify_reference(x, k)
        return {
            "dim": d, "k": k,
            "encode_ms_jit": _nn(round(enc, 3)),
            "encode_ms_numpy_ref": _nn(round(enc_ref, 3)),
            "decode_ms_jit": _nn(round(dec, 3)),
            "parity_bit_exact": bool(
                np.array_equal(np.asarray(idx), r_idx)
                and np.array_equal(np.asarray(vals), r_vals)),
        }

    def writeback_microbench(async_wb):
        import numpy as np
        from fedml_tpu.state.residuals import SiloResidualStore
        store = SiloResidualStore(
            os.path.join(root, "wb_async" if async_wb else "wb_sync"),
            async_writeback=async_wb)
        resid = np.zeros(1 << 16, np.float32)
        reps = 20
        t0 = time.perf_counter()
        for r in range(reps):
            resid = resid + 1.0
            store.save(r, resid)
        blocked = (time.perf_counter() - t0) * 1e3 / reps
        stats = store.writeback_stats() or {}
        store.close()
        return {"save_blocked_ms": _nn(round(blocked, 3)),
                "flusher": stats or None}

    sync_leg, sync_sched = leg("sync", True)
    async_leg, async_sched = leg("async", False)
    # the replay oracle: both ledgers must dedup-replay to the SAME
    # full schedule — round indices AND cohorts (the bits restore reads)
    identical = (sync_sched == async_sched
                 and len(sync_sched) == rounds)
    sync_leg["ledger_replay_identical"] = identical
    async_leg["ledger_replay_identical"] = identical
    crit_sync = sync_leg["critical_path_ms"] or 0.0
    crit_async = max(async_leg["critical_path_ms"] or 0.0, 1e-3)
    out = {
        "sync": sync_leg,
        "async": async_leg,
        "rounds_per_sec": async_leg["rounds_per_sec"],
        "critical_path_reduction_x": _nn(round(crit_sync / crit_async,
                                               2)),
        "ledger_replay_identical": identical,
        "ledger_rounds": len(async_sched),
        "codec": codec_microbench(),
        "state_writeback_sync": writeback_microbench(False),
        "state_writeback_async": writeback_microbench(True),
        "note": "critical_path_ms is what the round thread blocks on at "
                "the durable round boundary (gauge = worst round): sync "
                "pays capture+serialize+fsync+publish inline; async "
                "pays the host capture only. Identical seed/schedule "
                "both legs; ledger_replay_identical pins that moving "
                "durability off-thread moved zero replayed bits.",
    }
    _write_artifact("round_overheads.json", out)
    shutil.rmtree(root, ignore_errors=True)
    return out


def bench_fanout_agg() -> dict:
    """The server round hot path: (a) parallel writer-thread fan-out vs
    the blocking sequential loop under ONE stalled peer (real TCP,
    kernel backpressure), (b) streaming-fold round close vs the legacy
    buffer-all close, and (c) a trend-gated federation round rate with
    a chaos-delayed straggler silo. Artifact: runs/fanout_agg.json."""
    import threading

    import jax

    from fedml_tpu.algorithms.fedavg_cross_silo import (
        FedAvgAggregator, run_fedavg_cross_silo)
    from fedml_tpu.comm.fanout_smoke import _HOST, _RawPeer
    from fedml_tpu.comm.message import Message
    from fedml_tpu.comm.serialization import SharedPayload
    from fedml_tpu.comm.tcp import TcpCommManager
    from fedml_tpu.core import pytree as pt
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig
    from fedml_tpu.utils.tracing import RoundTimer

    stall_s = 0.75
    payload_mb = 4
    port = [40720]

    def fanout_leg(n_peers: int, parallel: bool) -> dict:
        """Broadcast one shared payload to ``n_peers``; the FIRST
        destination stalls its reads for ``stall_s`` (head-of-line for
        the sequential loop — any stalled position delays every LATER
        peer there, so first is the honest worst case)."""
        base = port[0]
        port[0] += n_peers + 1
        addresses = {r: (_HOST, base + r) for r in range(n_peers + 1)}
        peers = {r: _RawPeer(base + r,
                             stall_s=stall_s if r == 1 else 0.0)
                 for r in range(1, n_peers + 1)}
        com = TcpCommManager(0, addresses)
        rng = np.random.default_rng(0)
        shared = SharedPayload({"w": rng.standard_normal(
            (payload_mb * (1 << 20) // 4,)).astype(np.float32)})
        msgs = []
        for r in range(1, n_peers + 1):
            msgs.append(Message(2, 0, r).add("model_params", shared)
                        .add("round_idx", 0))
        errors = []
        t0 = time.perf_counter()
        if parallel:
            com.broadcast(msgs,
                          on_error=lambda r, e: errors.append((r, e)))
        else:
            for msg in msgs:  # the pre-writer-thread behavior: each
                com.send_message(msg)  # send blocks through the queue
        wall_ms = (time.perf_counter() - t0) * 1e3
        deadline = time.monotonic() + stall_s + 30.0
        while any(p.done_t is None for p in peers.values()) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        com.stop_receive_message()
        assert not errors and all(p.done_t is not None
                                  for p in peers.values())
        return {"peers": n_peers, "broadcast_wall_ms": round(wall_ms, 2),
                "payload_encodes": shared.encode_count}

    fanout = {"parallel": [], "sequential": []}
    for n in (2, 4, 8):
        fanout["sequential"].append(fanout_leg(n, parallel=False))
        fanout["parallel"].append(fanout_leg(n, parallel=True))
    speedups = [round(s["broadcast_wall_ms"]
                      / max(0.01, p["broadcast_wall_ms"]), 1)
                for s, p in zip(fanout["sequential"], fanout["parallel"])]

    # -- round-close latency: streaming fold vs legacy buffer-all close --
    n_workers, leaf = 16, (1 << 20)
    rng = np.random.default_rng(1)
    reports = [({"w": rng.standard_normal((leaf,)).astype(np.float32)},
                float(10 + i)) for i in range(n_workers)]

    def agg_leg(streaming: bool) -> dict:
        agg = FedAvgAggregator(
            n_workers,
            aggregate_fn=None if streaming else pt.tree_weighted_mean)
        out = {}
        for _warm in range(2):  # round 0 pays the jit; round 1 measures
            t_add = 0.0
            for i, (m, w) in enumerate(reports):
                t0 = time.perf_counter()
                agg.add_local_trained_result(i, m, w)
                t_add += time.perf_counter() - t0
            t0 = time.perf_counter()
            model = agg.aggregate()
            jax.block_until_ready(model)
            close_ms = (time.perf_counter() - t0) * 1e3
            out = {"adds_total_ms": round(t_add * 1e3, 2),
                   "close_ms": round(close_ms, 2),
                   "total_ms": round(t_add * 1e3 + close_ms, 2)}
        return out

    agg_buffered = agg_leg(streaming=False)
    agg_streaming = agg_leg(streaming=True)

    # -- trend-gated leg: federation round rate with one straggler silo --
    delay_ms, rounds, workers = 300.0, 6, 4
    ds = make_blob_federated(client_num=workers, dim=8, class_num=3,
                             n_samples=128, seed=11)
    base = port[0]
    addresses = {r: (_HOST, base + r) for r in range(workers + 1)}
    timer = RoundTimer()
    t0 = time.perf_counter()
    _, history = run_fedavg_cross_silo(
        ds, LogisticRegression(num_classes=3), worker_num=workers,
        comm_round=rounds, train_cfg=TrainConfig(epochs=1, batch_size=8,
                                                 lr=0.1),
        backend="TCP", addresses=addresses, timer=timer,
        fault_plan=(f"seed=3;delay:p=1.0,delay_ms={delay_ms:.0f},"
                    f"msg_type=2,receiver={workers},direction=recv"),
        round_deadline_s=30.0, min_quorum_frac=0.5)
    wall = time.perf_counter() - t0
    out = {
        "rounds_per_sec": round(rounds / wall, 3),
        "fanout_one_stalled_peer": fanout,
        "fanout_speedup_x_by_peers": speedups,
        "agg_close_buffered": agg_buffered,
        "agg_close_streaming": agg_streaming,
        "close_latency_drop_x": round(
            agg_buffered["close_ms"] / max(0.01,
                                           agg_streaming["close_ms"]), 1),
        "straggler_federation": {
            "workers": workers, "rounds": len(history),
            "injected_recv_delay_ms": delay_ms,
            "bcast_fanout_ms": timer.gauges.get("bcast_fanout_ms"),
            "agg_fold_ms": timer.gauges.get("agg_fold_ms"),
            "agg_buffered_peak": timer.gauges.get("agg_buffered_peak"),
        },
        "note": "CPU host, loopback TCP. Fan-out legs: one peer stalls "
                f"its reads {stall_s}s against a {payload_mb} MB "
                "payload; the sequential leg reconstructs the "
                "pre-writer-thread path (stalled peer first = "
                "head-of-line worst case), so its wall time is "
                "stall-bound while the parallel enqueue stays ~flat in "
                "peer count — the sublinearity claim, capped by this "
                "host's loopback. Close legs: the streaming fold "
                "spreads per-report device adds across arrivals, so "
                "ROUND-CLOSE latency drops vs the buffer-all "
                "stack+reduce; total aggregate compute is similar and "
                "the fold matches the old stacked reduce only to ~1e-6 "
                "relative (XLA reassociates the stacked sum). The "
                "trend-gated rounds/sec carries a 300 ms recv-delayed "
                "straggler: training time dominates it on this host.",
    }
    _write_artifact("fanout_agg.json", out)
    return out


def bench_serving() -> dict:
    """The train->serve axis (fedml_tpu/serve): the same federation run
    (a) baseline, no serving, and (b) with the serving tier attached
    and closed-loop synthetic traffic hammering the TCP endpoint the
    whole time training runs. Emits served p50/p99 latency and
    throughput, steady-state hot-swap cost (vs mean round time), the
    training rounds/sec delta serving costs, and the PURE-OBSERVER
    verdict: the serving-ON leg's history and final model must be
    bit-exact vs the baseline. Artifact: runs/serving.json; the
    trend-gated rounds_per_sec is the SERVED requests/sec (closed-loop
    throughput is the inverse of latency, so a serving-latency
    regression gates exactly like a training-throughput drop)."""
    import shutil
    import tempfile
    import threading

    import numpy as np

    from fedml_tpu.algorithms.fedavg_cross_silo import run_fedavg_cross_silo
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.serve import build_serving, drive_traffic
    from fedml_tpu.trainer.functional import TrainConfig
    from fedml_tpu.utils.tracing import RoundTimer

    rounds, workers = 20, 3
    ds = make_blob_federated(client_num=workers, dim=64, class_num=8,
                             n_samples=workers * 640, seed=9)
    tcfg = TrainConfig(epochs=2, batch_size=32, lr=0.1)
    probe = ds.test_data_global[0][:16]
    root = tempfile.mkdtemp(prefix="fedml_serving_bench_")

    def leg(serve: bool) -> dict:
        import os as _os
        module = LogisticRegression(num_classes=8)
        timer = RoundTimer()
        ctrl = _os.path.join(root, "ctrl_serve" if serve else "ctrl_base")
        tier = None
        traffic_rows: list = []
        stop = threading.Event()

        def pump():
            # closed-loop traffic for the WHOLE training window: batches
            # of requests back-to-back, 4 concurrent connections
            while tier.rollout.served_round < 0 \
                    and not stop.is_set():
                time.sleep(0.01)
            while not stop.is_set():
                traffic_rows.append(drive_traffic(
                    tier.port, probe, requests=64, concurrency=4))

        pump_thread = None
        if serve:
            tier = build_serving(module, "classification",
                                 ds.train_data_global[0][:1],
                                 max_batch=16, timer=timer, port=0,
                                 checkpoint_dir=ctrl)
            pump_thread = threading.Thread(target=pump, daemon=True)
            pump_thread.start()
        t0 = time.perf_counter()
        model, history = run_fedavg_cross_silo(
            ds, module, worker_num=workers, comm_round=rounds,
            train_cfg=tcfg, seed=7, server_checkpoint_dir=ctrl,
            timer=timer, serving=tier)
        wall = time.perf_counter() - t0
        out = {
            "rounds_per_sec": round(rounds / wall, 3),
            "wall_s": round(wall, 3),
            "final_test_loss": _nn(history[-1]["test_loss"]
                                   if history else float("nan")),
            "final_test_acc": _nn(history[-1]["test_acc"]
                                  if history else float("nan")),
            "history": history,
            "model": model,
        }
        if serve:
            stop.set()
            pump_thread.join(timeout=30)
            tier.rollout.drain()
            slo = tier.slo_report()
            swaps = list(tier.endpoint.swap_ms_history)
            steady = swaps[1:] or swaps  # [0] is the flip after warmup
            ok = sum(t["ok"] for t in traffic_rows)
            req_wall = sum(t["wall_s"] for t in traffic_rows)
            lat50 = [t["latency_p50_ms"] for t in traffic_rows
                     if t["latency_p50_ms"] is not None]
            lat99 = [t["latency_p99_ms"] for t in traffic_rows
                     if t["latency_p99_ms"] is not None]
            out["serving"] = {
                "requests_ok": int(ok),
                "requests_shed": int(sum(t["shed"]
                                         for t in traffic_rows)),
                "requests_per_sec": (round(ok / req_wall, 2)
                                     if req_wall > 0 else None),
                "latency_p50_ms": (round(float(np.median(lat50)), 3)
                                   if lat50 else None),
                "latency_p99_ms": (round(float(max(lat99)), 3)
                                   if lat99 else None),
                "server_side_p50_ms": slo.get("latency_p50_ms"),
                "server_side_p99_ms": slo.get("latency_p99_ms"),
                "swaps": int(tier.endpoint.swaps),
                "swap_cost_ms_mean": (round(float(np.mean(steady)), 3)
                                      if steady else None),
                "swap_cost_ms_max": (round(float(np.max(steady)), 3)
                                     if steady else None),
                "served_final_round": slo.get("served_round"),
                "staleness_max": float(
                    timer.gauges.get("serve_staleness_rounds", 0.0)),
            }
            tier.close()
        return out

    try:
        # warm pre-pass: both legs share one jitted local_train/eval
        # (_LOCAL_TRAIN_CACHE keys by (module, task, cfg)); without it
        # the FIRST leg alone pays the XLA compile and the training
        # delta reads as a serving speedup (observed 1.28x — the exact
        # artifact the multi_tenancy stage warms away)
        run_fedavg_cross_silo(
            ds, LogisticRegression(num_classes=8), worker_num=workers,
            comm_round=2, train_cfg=tcfg, seed=7)
        base = leg(serve=False)
        served = leg(serve=True)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    # the pure-observer verdict: serving must not perturb training
    import jax
    hist_equal = base["history"] == served["history"]
    base_leaves = jax.tree.leaves(base["model"])
    serve_leaves = jax.tree.leaves(served["model"])
    model_equal = len(base_leaves) == len(serve_leaves) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(base_leaves, serve_leaves))
    sv = served["serving"]
    round_ms = 1000.0 * served["wall_s"] / rounds
    out = {
        # trend-gated: served throughput under the synthetic load
        "rounds_per_sec": sv["requests_per_sec"],
        "training_rounds_per_sec_serving": served["rounds_per_sec"],
        "training_rounds_per_sec_baseline": base["rounds_per_sec"],
        "training_throughput_x_vs_baseline": round(
            served["rounds_per_sec"] / max(1e-9,
                                           base["rounds_per_sec"]), 3),
        "serving": sv,
        "swap_cost_frac_of_round": (
            round(sv["swap_cost_ms_mean"] / round_ms, 5)
            if sv["swap_cost_ms_mean"] is not None and round_ms > 0
            else None),
        "pure_observer": {
            "history_identical": bool(hist_equal),
            "model_identical": bool(model_equal),
        },
        "baseline": {k: v for k, v in base.items()
                     if k not in ("history", "model")},
        "serving_leg": {k: v for k, v in served.items()
                        if k not in ("history", "model", "serving")},
        "note": "closed-loop traffic (4 connections) against the "
                "TCP/JSON endpoint for the whole training window on "
                "ONE host — requests timeshare the CPU with training, "
                "so the training delta is an upper bound on what a "
                "real deployment (serving replicas fed by checkpoint "
                "deltas) would pay. rounds_per_sec here is SERVED "
                "requests/sec (the latency gate); training rounds/sec "
                "travels in training_rounds_per_sec_*.",
    }
    _write_artifact("serving.json", out)
    return out


def bench_cross_silo_faults() -> dict:
    """The cross-silo RESILIENCE axis: the same federation run clean vs
    under a seeded chaos plan (comm/faults.py — duplicated uplink
    replies, delayed broadcasts, and a mid-run silo partition that
    forces a deadline eviction + JOIN rejoin). Emits the recovery
    counters (retries/evictions/rejoins/dedup) from RoundTimer next to
    rounds/sec and final loss, so a regression in ANY recovery path
    (dedup stops shedding duplicates, eviction stops closing rounds,
    rejoin stops landing) shows up as a bench delta, not a prod hang."""
    from fedml_tpu.algorithms.fedavg_cross_silo import run_fedavg_cross_silo
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig
    from fedml_tpu.utils.tracing import RoundTimer

    rounds, workers = 8, 3
    ds = make_blob_federated(client_num=workers, dim=64, class_num=10,
                             n_samples=600, seed=0, noise=5.0)
    tcfg = TrainConfig(epochs=1, batch_size=20, lr=0.05)
    # pacing delay keeps rounds long enough for the partition window +
    # rejoin to land inside the schedule (see tests/test_faults.py)
    chaos_plan = ("seed=11;"
                  "duplicate:p=0.5,msg_type=4;"
                  "delay:p=1.0,direction=send,sender=0,msg_type=2,"
                  "delay_ms=250;"
                  "disconnect:direction=recv,receiver=3,msg_type=2,"
                  "after=0,max_count=1,duration_ms=1500")

    def run(plan, deadline):
        timer = RoundTimer()
        t0 = time.perf_counter()
        _, history = run_fedavg_cross_silo(
            ds, LogisticRegression(num_classes=10), worker_num=workers,
            comm_round=rounds, train_cfg=tcfg, fault_plan=plan,
            round_deadline_s=deadline, min_quorum_frac=0.5,
            heartbeat_s=0.25, timer=timer)
        wall = time.perf_counter() - t0
        c = dict(timer.counters)
        return {
            "rounds_per_sec": round(rounds / wall, 3),
            "rounds_completed": len(history),
            "final_test_loss": _nn(history[-1]["test_loss"]
                                   if history else float("nan")),
            "final_test_acc": _nn(history[-1]["test_acc"]
                                  if history else float("nan")),
            "retries": c.get("ft_retries", 0),
            "dedup_drops": c.get("ft_dedup_drops", 0),
            "faults_injected": c.get("ft_faults_injected", 0),
            "evictions": c.get("ft_evictions", 0),
            "rejoins": c.get("ft_rejoins", 0),
            "partial_rounds": c.get("ft_partial_rounds", 0),
            "corrupt_frames": c.get("ft_corrupt_frames", 0),
        }

    clean = run(None, deadline=None)
    chaos = run(chaos_plan, deadline=0.8)
    ok = (chaos["rounds_completed"] == rounds
          and chaos["evictions"] >= 1 and chaos["rejoins"] >= 1
          and chaos["dedup_drops"] >= 1)
    return {
        "clean": clean,
        "chaos": chaos,
        "recovered_full_schedule": bool(ok),
        "loss_delta_vs_clean": _nn(chaos["final_test_loss"]
                                   - clean["final_test_loss"]),
        "note": "INPROC wire-codec transport, seeded FaultPlan: chaos "
                "rounds/sec includes the injected 250 ms broadcast "
                "pacing + the 1.5 s partition, so compare counters and "
                "loss, not wall-clock, against the clean leg.",
    }


def bench_server_failover() -> dict:
    """The control-plane RESILIENCE axis: the same federation run clean
    (control plane on, no kill) vs with the server process SIGKILLed
    mid-schedule and restarted (fedml_tpu/control/failover_harness.py —
    real subprocess over TCP, silo fleet flapping ~30% throughout). The
    kill leg must complete the FULL schedule with ``cp_restores >= 1``
    and its round/cohort ledger must match the clean leg's — a
    regression in snapshot coverage, restore, or the rejoin path shows
    up as ``recovered_full_schedule: false`` here, not as a dead
    production coordinator. Artifact: runs/server_failover.json."""
    import shutil
    import tempfile

    from fedml_tpu.control.failover_harness import (ledger_schedule,
                                                    run_failover_scenario,
                                                    run_simulated_failover)

    rounds = 8
    root = tempfile.mkdtemp(prefix="fedml_server_failover_")
    try:
        # clean leg: identical TCP topology + deadline config, no kill
        t0 = time.perf_counter()
        _, clean_ledger, clean_server = run_simulated_failover(
            os.path.join(root, "clean"), rounds=rounds,
            crash_at_round=10**9, backend="TCP", port_base=41110,
            deadline_s=2.0)
        clean_wall = time.perf_counter() - t0
        # kill leg: SIGKILL after round 2 closes, restart, 30% silo flap
        t0 = time.perf_counter()
        res = run_failover_scenario(
            os.path.join(root, "killed"), rounds=rounds,
            kill_after_round=2, port_base=41130, deadline_s=2.0,
            silo_fault_plan="seed=13;disconnect:direction=recv,"
                            "receiver=3,msg_type=2,p=0.3,duration_ms=800")
        kill_wall = time.perf_counter() - t0
        summary = res["summary"]
        cp = summary.get("cp_counters", {})
        ledger_ok = (ledger_schedule(res["ledger"])
                     == ledger_schedule(clean_ledger))
        ok = (summary.get("done") is True
              and summary.get("rounds_completed") == rounds
              and cp.get("restores", 0) >= 1 and ledger_ok)
        out = {
            "rounds": rounds,
            "clean": {
                "rounds_per_sec": round(rounds / clean_wall, 3),
                "cp_checkpoints": int(
                    clean_server.cp_counters.get("checkpoints", 0)),
                "ledger_rounds": len(clean_ledger),
            },
            "server_kill": {
                "rounds_per_sec": round(rounds / kill_wall, 3),
                "killed_at_round": res["killed_at_round"],
                "rounds_completed": summary.get("rounds_completed"),
                "cp_restores": cp.get("restores", 0),
                "cp_checkpoints": cp.get("checkpoints", 0),
                "evictions": summary.get("evictions", 0),
                "rejoins": summary.get("rejoins", 0),
                "partial_rounds": summary.get("ft_counters", {}).get(
                    "partial_rounds", 0),
            },
            "ledger_matches_clean": bool(ledger_ok),
            "recovered_full_schedule": bool(ok),
            "note": "TCP subprocess server, SIGKILL after round 2 + "
                    "restart (auto-restore from the control snapshot); "
                    "1 of 3 silos flaps on ~30% of broadcasts. Kill-leg "
                    "wall-clock includes the restart + JAX re-init, so "
                    "judge counters and ledger parity, not rounds/sec.",
        }
        _write_artifact("server_failover.json", out)
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_multi_tenancy() -> dict:
    """The federation-scheduler TENANCY axis (fedml_tpu/sched): three
    identical-shape jobs run (a) each solo through the scheduler and
    (b) concurrently over ONE shared fabric + ONE device with
    fair-share interleaving. Emits per-job rounds/sec (solo vs
    tenant), the fairness ratio (worst/best share-normalized device
    time — the starvation detector), solo-vs-tenant ledger AND
    final-model parity (the bit-exact isolation contract), and the
    per-job `obs report` summaries rendered from the one shared obs
    dir. Artifact: runs/multi_tenancy.json."""
    import shutil
    import tempfile

    from fedml_tpu.obs.report import summarize
    from fedml_tpu.sched import JobSpec, launch_jobs
    from fedml_tpu.sched.chaos import solo_parity

    # 30 rounds: the steady-state fairness window (past each tenant's
    # compile prologue — see sched.interleave.PROLOGUE_HOLDS) needs
    # enough post-prologue holds that a handful of noisy ones can't
    # swing the ratio
    rounds, workers = 30, 3
    # identical shapes (one shared jitted program), distinct seeds:
    # symmetric demand makes the fairness ratio a real signal instead
    # of a workload echo
    specs = [JobSpec(id=f"ten{i}", workers=workers, rounds=rounds,
                     seed=11 + i, dim=64, class_num=8, n_samples=1920,
                     batch_size=32, epochs=3, lr=0.1, share=1.0)
             for i in range(3)]
    root = tempfile.mkdtemp(prefix="fedml_multi_tenancy_")
    try:
        # warm pre-pass: the three specs share ONE jitted program
        # (_LOCAL_TRAIN_CACHE keys by (module, task, cfg) and the
        # shapes are identical), so without this the FIRST solo leg
        # alone pays the XLA compile and its solo rounds/sec reads
        # biased-low vs its co-tenants'
        import dataclasses
        warm = dataclasses.replace(specs[0], id="warmup", rounds=1,
                                   seed=7)
        launch_jobs([warm], os.path.join(root, "warmup"), obs=False)
        solo = {}
        solo_wall = {}
        for spec in specs:
            t0 = time.perf_counter()
            # obs ON, same as the shared leg: the solo-vs-tenant
            # throughput comparison must not attribute flight-recorder
            # write cost to the tenant leg alone
            res = launch_jobs([spec], os.path.join(root, "solo", spec.id),
                              obs=True)
            solo_wall[spec.id] = time.perf_counter() - t0
            solo[spec.id] = res["jobs"][spec.id]
        t0 = time.perf_counter()
        shared = launch_jobs(specs, os.path.join(root, "shared"),
                             obs=True)
        shared_wall = time.perf_counter() - t0
        report = summarize([os.path.join(root, "shared", "obs")])
        jobs = {}
        parity = True
        for spec in specs:
            ref, ten = solo[spec.id], shared["jobs"][spec.id]
            err, ledger_ok, model_ok = solo_parity(ref, ten)
            parity = parity and ledger_ok and model_ok
            rep = report["jobs"].get(spec.id, {})
            jobs[spec.id] = {
                "error": err,
                "solo_rounds_per_sec": round(
                    rounds / solo_wall[spec.id], 3),
                "tenant_rounds_per_sec": round(rounds / shared_wall, 3),
                "device_time_s": round(
                    shared["device_time_s"].get(spec.id, 0.0), 4),
                "ledger_identical_to_solo": bool(ledger_ok),
                "model_identical_to_solo": bool(model_ok),
                "obs_report": {
                    "rounds": rep.get("rounds"),
                    "rounds_per_sec": rep.get("rounds_per_sec"),
                    "wire_bytes_per_round": (rep.get("wire") or {}).get(
                        "bytes_per_round"),
                    "partial_rounds": rep.get("partial_rounds"),
                },
            }
        fairness = shared["fairness_ratio"]
        raw = shared.get("fairness_ratio_raw")
        out = {
            "jobs_n": len(specs),
            "rounds_per_job": rounds,
            "workers_per_job": workers,
            # the trend-gated figure: aggregate tenant throughput over
            # the shared leg (all jobs' rounds / shared wall)
            "rounds_per_sec": round(len(specs) * rounds / shared_wall, 3),
            # steady-state (past the per-tenant compile prologue);
            # fairness_ratio_raw includes the one-off JIT charges
            "fairness_ratio": (round(fairness, 4)
                               if fairness is not None else None),
            "fairness_ratio_raw": (round(raw, 4)
                                   if raw is not None else None),
            "solo_parity_all_jobs": bool(parity),
            "per_job": jobs,
            "obs_report_jobs": sorted(report["jobs"]),
            "note": "INPROC shared fabric (job-tagged frames over one "
                    "endpoint pair per rank), deficit-round-robin "
                    "device gate, equal shares; tenant rounds/sec is "
                    "per-job schedule length over the SHARED wall "
                    "clock, so 3 tenants near the solo figure means "
                    "the interleaver is hiding co-tenant gaps, not "
                    "that the chip tripled.",
        }
        _write_artifact("multi_tenancy.json", out)
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


#: shared shape for the fused-round stages (R=20 blocks on the
#: 1000-client power-law flagship). R=20 is also the
#: sweet spot: the block packs at the max cohort bucket over its R
#: cohorts, so very large R erodes the packing lever while small R
#: under-amortizes the host sync.
_FUSED_N, _FUSED_R = 1000, 20


def _fused_setup():
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.data.synthetic import make_powerlaw_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig

    ds = make_powerlaw_blob_federated(client_num=_FUSED_N, dim=64,
                                      class_num=10, seed=2)

    def make_api(pack="cohort"):
        return FedAvgAPI(ds, LogisticRegression(num_classes=10),
                         config=FedAvgConfig(
                             comm_round=10**9, client_num_per_round=10,
                             frequency_of_the_test=10**9, pack=pack,
                             train=TrainConfig(epochs=1, batch_size=10,
                                               lr=0.03)))
    return ds, make_api


def _fused_block_rps(api, device_sampling: bool) -> float:
    import jax

    R = _FUSED_R
    fused = api.fused_rounds(device_sampling=device_sampling)
    fused.run_rounds(0, R)  # compile + warm
    jax.block_until_ready(api.variables)
    # a later block can land on a different cohort bucket and recompile;
    # time two consecutive blocks and keep the best
    best = 0.0
    for i in (1, 2):
        t0 = time.perf_counter()
        fused.run_rounds(i * R, R)
        jax.block_until_ready(api.variables)
        best = max(best, R / (time.perf_counter() - t0))
    return best


def bench_wan_churn() -> dict:
    """The WAN-realism axis (fedml_tpu/wan): the same federation run
    (a) idealized — no churn, uniform clients — and (b) through a
    diurnal trough + flap burst + heterogeneous straggler profiles, all
    over real TCP endpoints. Chaos-grade verdicts, each a regression
    tripwire:

    - ``recovered_full_schedule``: the 50% trough degrades throughput
      but the FULL schedule completes (extension cap honored, partial
      rounds counted) — churn must never stall or crash the schedule;
    - ``ledger_replay_identical``: re-running the identical trace seed
      reproduces a bit-identical round/cohort ledger (the whole layer
      is a pure function of the seed);
    - ``steering.tracks_injected_p90``: with pace steering on and a
      known injected delay distribution, the steered deadline lands in
      a band around p90 x margin and UNDER the static base — the
      steerer tracks the straggler distribution instead of merely
      surviving it;
    - ``merge_verified``: the churn leg's flight timeline rebuilds
      cleanly and matches the control-plane ledger
      (`python -m fedml_tpu.obs merge --ledger`), committed under
      runs/wan_churn_obs/ as the evidence artifact;
    - ``population_1m``: the availability-restricted sampler at 10^6
      clients — O(cohort) rejection draws, microseconds per cohort, no
      per-client state.

    Artifact: runs/wan_churn.json; the trend row gates the churn leg's
    rounds/sec."""
    import shutil
    import subprocess
    import tempfile

    import numpy as np

    from fedml_tpu.wan import WanWorld, parse_wan_profiles, parse_wan_trace
    from fedml_tpu.wan.__main__ import (SMOKE_ROUNDS, cohorts_all_available,
                                        run_churn_leg, smoke_world)

    rounds = SMOKE_ROUNDS
    root = tempfile.mkdtemp(prefix="fedml_wan_churn_")
    obs_dir = os.path.join("runs", "wan_churn_obs")
    shutil.rmtree(obs_dir, ignore_errors=True)
    os.makedirs(obs_dir, exist_ok=True)
    try:
        # -- leg A: idealized (no WAN world, same schedule/transport) ------
        ideal = run_churn_leg(os.path.join(root, "ideal"), world=None,
                              port_base=41310)
        # -- leg B: churn (trough + flap + profiles), flight-recorded ------
        churn = run_churn_leg(os.path.join(root, "churn"),
                              world=smoke_world(), port_base=41330,
                              obs_dir=os.path.join(obs_dir, "flight"))
        # -- leg C: replay (identical seed) --------------------------------
        replay = run_churn_leg(os.path.join(root, "replay"),
                               world=smoke_world(), port_base=41350)
        replay_ok = (json.dumps(churn["ledger"], sort_keys=True)
                     == json.dumps(replay["ledger"], sort_keys=True))
        # -- leg D: steering tracks the injected straggler p90 -------------
        # flat trace (everyone always on) + lognormal compute profiles:
        # the only latency structure is the injected distribution
        prof_spec = "seed=5;compute_median_s=0.25;compute_sigma=0.5"
        steer_world = WanWorld(
            trace=parse_wan_trace("seed=1;peak=1.0;trough=1.0;"
                                  "duty_jitter=0.0"),
            profiles=parse_wan_profiles(prof_spec),
            round_s=60.0, delay_wall_cap_s=1.5)
        base_deadline = 2.0
        steer = run_churn_leg(os.path.join(root, "steer"),
                              world=steer_world, rounds=10,
                              port_base=41370, pace_steering=True,
                              deadline_s=base_deadline)
        p90_inj = steer_world.profiles.delay_quantile(
            0.9, 24, up_bytes=400.0, down_bytes=400.0)
        steered = steer["gauges"].get("cp_steered_deadline_s")
        # band: the steered deadline must cover the injected p90, sit
        # UNDER the static base (it adapted), and stay inside a loose
        # multiple of p90 x margin (host contention inflates measured
        # latencies above the injected floor, hence the 2.5x headroom)
        tracks = (steered is not None
                  and p90_inj <= steered < base_deadline
                  and steered <= p90_inj * 1.5 * 2.5)
        # -- leg E: 1M-client availability-restricted sampling -------------
        pop_world = WanWorld(trace=parse_wan_trace(
            "seed=9;period_s=86400;peak=0.95;trough=0.45;slot_s=600"),
            round_s=60.0, population=1_000_000)
        draws = 200
        t0 = time.perf_counter()
        all_avail = True
        for r in range(draws):
            cohort = pop_world.sample_cohort(r, 1_000_000, 10)
            all_avail &= bool(pop_world.trace.available(
                np.asarray(cohort), pop_world.t_of_round(r)).all())
        draw_wall = time.perf_counter() - t0
        # -- merge-verified flight timeline --------------------------------
        merge_cmd = [sys.executable, "-m", "fedml_tpu.obs", "merge",
                     os.path.join(obs_dir, "flight"),
                     "--ledger", os.path.join(root, "churn",
                                              "ledger.jsonl"),
                     "--output", os.path.join(obs_dir, "merged.json")]
        merge = subprocess.run(merge_cmd, capture_output=True, text=True,
                               env=dict(os.environ, JAX_PLATFORMS="cpu"))
        merge_ok = merge.returncode == 0
        # -- time-to-target ------------------------------------------------
        target = 0.9 * ideal["history"][-1]["test_acc"]

        def tta(leg):
            for rec in leg["history"]:
                if rec["test_acc"] >= target:
                    return (rec["round"],
                            leg["round_walls"].get(rec["round"]))
            return None, None

        ideal_r, ideal_t = tta(ideal)
        churn_r, churn_t = tta(churn)
        cc = churn["counters"]
        ok = (len(churn["history"]) == rounds
              and len(churn["ledger"]) == rounds
              and cc.get("ft_evictions", 0) >= 1
              and cc.get("ft_rejoins", 0) >= 1
              and cc.get("ft_partial_rounds", 0) >= 1
              and cc.get("wan_forced_cohorts", 0) == 0
              and cohorts_all_available(churn["ledger"], churn["world"]))
        out = {
            "rounds": rounds,
            "target_acc": _nn(round(target, 4)),
            "idealized": {
                "rounds_per_sec": ideal["rounds_per_sec"],
                "final_test_acc": _nn(ideal["history"][-1]["test_acc"]),
                "rounds_to_target": ideal_r,
                "wall_to_target_s": ideal_t,
            },
            "churn": {
                "rounds_per_sec": churn["rounds_per_sec"],
                "final_test_acc": _nn(churn["history"][-1]["test_acc"]),
                "rounds_to_target": churn_r,
                "wall_to_target_s": churn_t,
                "evictions": cc.get("ft_evictions", 0),
                "rejoins": cc.get("ft_rejoins", 0),
                "partial_rounds": cc.get("ft_partial_rounds", 0),
                "offline_drops": cc.get("wan_offline_drops", 0),
                "delay_injected_ms": cc.get("wan_delay_injected_ms", 0),
                "cohort_rejections": cc.get("wan_cohort_rejections", 0),
                "join_deferred": cc.get("wan_join_deferred", 0),
                "mass_joins": cc.get("wan_mass_joins", 0),
                "mass_leaves": cc.get("wan_mass_leaves", 0),
                "mass_join_throttled": cc.get("wan_mass_join_throttled",
                                              0),
                # trough depth recomputed from the trace (pure fn) — the
                # timer gauge is a HIGH-water mark (the peak), not this
                "min_available_frac": _nn(round(min(
                    churn["world"].available_frac(r)
                    for r in range(rounds)), 4)),
                "peak_available_frac": churn["gauges"].get(
                    "wan_available_frac"),
            },
            "steering": {
                "base_deadline_s": base_deadline,
                "injected_p90_s": _nn(round(p90_inj, 4)),
                "steered_deadline_s": steered,
                "deadline_adjustments": steer["counters"].get(
                    "cp_deadline_adjustments", 0),
                "resync_latency_skips": steer["counters"].get(
                    "cp_resync_latency_skips", 0),
                "tracks_injected_p90": bool(tracks),
            },
            "population_1m": {
                "cohort_draws": draws,
                "draws_per_sec": round(draws / max(draw_wall, 1e-9), 1),
                "all_sampled_available": bool(all_avail),
            },
            "recovered_full_schedule": bool(ok),
            "ledger_replay_identical": bool(replay_ok),
            "merge_verified": bool(merge_ok),
            "throughput_degradation_x": _nn(round(
                churn["rounds_per_sec"] / max(ideal["rounds_per_sec"],
                                              1e-9), 3)),
            "note": "TCP loopback endpoints; churn rounds are "
                    "deadline-paced (2 s) while trough silos are dark, "
                    "so the degradation factor measures the configured "
                    "deadline, not protocol overhead. Judge the "
                    "chaos verdicts and counters.",
        }
        if not merge_ok:
            out["merge_error"] = (merge.stderr or merge.stdout)[-500:]
        _write_artifact("wan_churn.json", out)
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_fused_rounds() -> dict:
    """Composed throughput levers: R sampled rounds as ONE
    fused BLOCK — host-presampled cohorts packed at the block's pow-2
    cohort bucket, scanned in one dispatch, trajectory-identical to the
    host loop — vs the cohort-packed host loop (the former contender).
    Win condition: fused block >= cohort-packed host loop at the
    1000-client power-law flagship. (The device-sampling scan variant is
    its own stage, bench_fused_device_sampling — it needs a global-max
    compile that shouldn't be paid before the contract number lands.)"""
    import jax

    from fedml_tpu.core import pytree as pt

    R = _FUSED_R
    _, make_api = _fused_setup()

    # the PARITY pass doubles as the warmup: the fused api's block-0 run
    # compiles its scan, the host api's rounds 0..R-1 compile every
    # cohort-bucket shape the timed loop will hit, and comparing their
    # variables right here gives the trajectory-parity evidence with ZERO
    # extra compiles (jit caches are per-API-instance, so a separate
    # parity pass on fresh APIs would recompile everything — compiles
    # are what blow the stage budget)
    api_f, api_h = make_api(), make_api()
    fused_driver = api_f.fused_rounds()
    fused_driver.run_rounds(0, R)
    for r in range(R):
        api_h.run_round(r)
    jax.block_until_ready(api_h.variables)
    parity = float(pt.tree_norm(pt.tree_sub(api_f.variables,
                                            api_h.variables))
                   ) / max(1e-30, float(pt.tree_norm(api_h.variables)))

    # fused timing continues on api_f's warmed driver (blocks 1 and 2;
    # a later block can land on a different cohort bucket and recompile,
    # so keep the best of two)
    best = 0.0
    for i in (1, 2):
        t0 = time.perf_counter()
        fused_driver.run_rounds(i * R, R)
        jax.block_until_ready(api_f.variables)
        best = max(best, R / (time.perf_counter() - t0))
    block_rps = best

    # host timing re-runs rounds 1..R-1 on api_h — exactly the rounds the
    # parity pass compiled (round R could land on an unseen bucket and
    # put a compile inside the timed region)
    t0 = time.perf_counter()
    for r in range(1, R):
        api_h.run_round(r)
    jax.block_until_ready(api_h.variables)
    host_cohort = (R - 1) / (time.perf_counter() - t0)

    def host_rps_global():
        api = make_api("global")
        api.run_round(0)  # one static shape — one compile
        jax.block_until_ready(api.variables)
        t0 = time.perf_counter()
        for r in range(1, R):
            api.run_round(r)
        jax.block_until_ready(api.variables)
        return (R - 1) / (time.perf_counter() - t0)

    host_global = host_rps_global()
    return {
        "rounds_per_sec_fused_block": round(block_rps, 3),
        "rounds_per_sec_host_cohort_pack": round(host_cohort, 3),
        "rounds_per_sec_host_global_pack": round(host_global, 3),
        "fused_block_vs_host_cohort_x": round(block_rps / host_cohort, 2),
        "rounds_per_scan": R,
        "block_host_parity_rel_err": parity,
        "note": "fused block = host-presampled cohorts at the block's "
                "cohort bucket under one lax.scan — both throughput "
                "levers composed, same trajectory as the host loop",
    }


def bench_fused_device_sampling() -> dict:
    """The in-scan device-sampling variant (cohort drawn on device each
    round, global-max padding — zero host involvement even for sampling).
    Split from bench_fused_rounds so its global-max compile cannot delay
    the composed-lever contract number."""
    _, make_api = _fused_setup()
    api = make_api()
    return {
        "rounds_per_sec_fused_device_sampling":
            round(_fused_block_rps(api, device_sampling=True), 3),
        "rounds_per_scan": _FUSED_R,
    }


def bench_parallel_axes() -> dict:
    """Perf numbers for the parallelism layer:
    tokens/s of the federated long-context round on a ('clients', 'seq')
    mesh and the Megatron round on ('clients', 'tp'). On the single real
    chip both model axes are size 1 (S=2048 tokens/s of the sharded
    program); on CPU the 8 virtual devices give a real 4x2 layout at smoke
    shapes (the scaling-curve artifact lives in
    runs/parallel_scaling_cpu.json, scripts in tests/perf notes)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.parallel.sequence import make_seq_federated_round
    from fedml_tpu.parallel.tensor import (make_tp_federated_round,
                                           shard_transformer_tp)
    from fedml_tpu.trainer.functional import TrainConfig

    tpu = _is_tpu()
    devs = jax.devices()
    S = 2048 if tpu else 64
    vocab = 512
    width, depth, heads = (256, 4, 4) if tpu else (32, 1, 2)
    n_pad, bsz, steps = (4, 2, 5) if tpu else (2, 2, 2)
    cfg = TrainConfig(epochs=1, batch_size=bsz, lr=0.1)
    rng = np.random.RandomState(0)

    def run(kind, n_model):
        n_cl = max(1, len(devs) // n_model)
        P = n_cl
        mesh = Mesh(np.asarray(devs[:n_cl * n_model]).reshape(
            n_cl, n_model), ("clients", kind))
        lm = TransformerLM(vocab_size=vocab, width=width, depth=depth,
                           num_heads=heads, max_len=S)
        x = rng.randint(0, vocab, (P, n_pad, S)).astype(np.int32)
        y = np.roll(x, -1, axis=-1).astype(np.int32)
        mask = np.ones((P, n_pad), np.float32)
        weights = np.full((P,), float(n_pad), np.float32)
        keys = jax.random.split(jax.random.key(0), P)
        variables = lm.init(jax.random.key(1), jnp.asarray(x[0, :1]),
                            train=False)
        if kind == "seq":
            round_fn = make_seq_federated_round(lm, cfg, mesh)
        else:
            round_fn, shard_params = make_tp_federated_round(
                lm, "nwp", cfg, mesh)
            variables = shard_params(variables)
        args = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask), keys,
                jnp.asarray(weights))
        v, _ = round_fn(variables, *args)  # compile (uncommitted params)
        # second warmup on the COMMITTED output: the jit caches on input
        # sharding, and the seq round's params go in uncommitted but come
        # out mesh-committed — so the next call recompiles. An earlier
        # 577.8 tokens/s seq row (the record predates this installation)
        # was exactly this second compile landing inside the timed
        # region; the tp twin pre-places params via shard_params, which is
        # why only the seq row was 4 orders of magnitude off. Steady state
        # is the committed->committed signature — warm it before timing.
        v, _ = round_fn(v, *args)
        jax.block_until_ready(v)
        t0 = time.perf_counter()
        for _ in range(steps):
            v, _ = round_fn(v, *args)
        jax.block_until_ready(v)
        dt = time.perf_counter() - t0
        return round(steps * P * n_pad * S / dt, 1)

    # single chip (or a 1-device CPU run without the virtual-device flag):
    # model axis of 1 — the sharded program itself, no cross-device split
    n_model = 1 if (tpu or len(devs) < 2) else 2
    return {
        "seq_len": S,
        "mesh_model_axis": n_model,
        "seq_round_tokens_per_sec": run("seq", n_model),
        "tp_round_tokens_per_sec": run("tp", n_model),
        "note": "seq warms BOTH jit signatures (uncommitted-params "
                "compile, then the committed steady state) before "
                "timing; an earlier 577.8 tok/s seq row timed the second "
                "compile (see the make_seq_federated_round docstring; "
                "that record predates this installation). Guarded by the "
                "CPU-shape seq-vs-tp ratio test in "
                "tests/test_seq_federated.py.",
    }


def bench_mesh_scaling() -> dict:
    """Measured multi-chip SPMD federation scaling (parallel/mesh.py):
    fused federated rounds/sec + MFU + collective bytes for the
    transformer and resnet18_gn workloads at named-mesh sizes
    {1, 2, 4, 8}.

    One process per chip: on ``tpu`` every (workload, mesh) point runs
    HERE, in the process that already holds the chips, on the sub-mesh
    ``jax.devices()[:n]`` (a child could not open them). On ``cpu`` each
    leg is its own subprocess forcing
    ``--xla_force_host_platform_device_count=N`` virtual devices — the
    device count is fixed at process start, and a CPU child needs no
    chip. Same worker body either way
    (``parallel.mesh._bench_workload``), so rows are comparable and
    tagged by device_kind.

    Honesty caveats for the CPU form, same contract as
    ci/parallel_scaling_cpu.py: virtual devices share the host's cores,
    so those rows cannot show wall-clock parallel speedup — the measured
    mesh8/mesh1 ratio reflects per-device program efficiency only, and
    the ``scaling_note`` says so. ``mfu`` is None on CPU (the peak table
    never guesses); CPU rows instead carry ``mfu_vs_measured_host_peak``
    against a measured host GEMM peak, explicitly labeled.
    """
    import subprocess

    import jax

    tpu = _is_tpu()
    n_avail = len(jax.devices())
    sizes = [n for n in (1, 2, 4, 8) if (not tpu) or n <= n_avail]
    workloads = ("transformer_flash_s2048", "resnet18_gn")

    def leg(workload: str, n: int, timeout_s: int = 300) -> dict:
        # resnet rounds are ~20x a transformer round on the CPU smoke
        # shapes — fewer timed rounds keep the stage inside its budget
        rounds, disp = ((4, 2) if workload.startswith("transformer")
                        else (2, 1))
        if tpu:
            from fedml_tpu.parallel.mesh import _bench_workload
            try:
                return _bench_workload(workload, {"data": n}, rounds, disp)
            except Exception as exc:  # noqa: BLE001 — one leg, not the stage
                return {"error": f"mesh leg {workload}@{n} failed: "
                                 f"{exc!r}"}
        cmd = [sys.executable, "-m", "fedml_tpu.parallel.mesh",
               "--bench-worker", "--workload", workload,
               "--mesh", f"data={n}",
               "--rounds", str(rounds), "--dispatches", str(disp)]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={n}"
                            ).strip()
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=timeout_s, env=env)
        except subprocess.TimeoutExpired:
            return {"error": f"mesh leg {workload}@{n} hung for "
                             f"{timeout_s}s"}
        if proc.returncode != 0:
            return {"error": f"mesh leg {workload}@{n} failed: "
                             f"{proc.stderr[-500:]}"}
        try:
            return json.loads(proc.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            return {"error": f"mesh leg {workload}@{n} unparseable: "
                             f"{proc.stdout[-300:]}"}

    curves: dict = {w: {} for w in workloads}
    for w in workloads:
        for n in sizes:
            curves[w][str(n)] = leg(w, n)

    def rps(w, n):
        row = curves[w].get(str(n), {})
        return row.get("rounds_per_sec")

    tf, rn = workloads
    top = rps(tf, max(sizes))
    ratio = (round(rps(tf, max(sizes)) / rps(tf, 1), 3)
             if rps(tf, 1) and rps(tf, max(sizes)) else None)
    out = {
        "workloads": list(workloads),
        "mesh_sizes": sizes,
        "curves": curves,
        # the trend-gated headline: the fused transformer stage at the
        # widest mesh — the row the ≥2x scaling criterion reads
        "rounds_per_sec": top,
        "transformer_scaling_ratio": ratio,
        "scaling_ratio_meshes": [1, max(sizes)],
        "resnet_scaling_ratio": (round(rps(rn, max(sizes)) / rps(rn, 1), 3)
                                 if rps(rn, 1) and rps(rn, max(sizes))
                                 else None),
        "scaling_note": (
            "measured on real chips; ratio = ICI strong scaling" if tpu
            else "forced-host XLA:CPU devices share the host's cores, so "
                 "the mesh8/mesh1 ratio reflects per-device program "
                 "efficiency (smaller per-device shapes compile to "
                 "faster total programs), NOT parallel speedup — the "
                 "ci/parallel_scaling_cpu.py contract. The >=2x strong-"
                 "scaling claim is a chip-host claim; real-chip rows "
                 "are tagged by device_kind."),
    }
    failed = sorted(f"{w}@{n}" for w in workloads for n in sizes
                    if "error" in curves[w][str(n)])
    if failed:
        out["error"] = f"mesh legs failed: {', '.join(failed)}"
    _write_artifact("mesh_scaling.json", out)
    return out


def bench_time_to_target_mnist_lr() -> dict:
    """Time-to-target at the REFERENCE ANCHOR shape (BASELINE.md row 1:
    MNIST + LR, 1000 power-law clients, 10/round, B=10, SGD lr=0.03, E=1,
    target >75% — benchmark/README.md:12), on the LEAF-content federation
    the generator builds. The blob TTA below stays as the fast trend
    metric; this row is the north-star-shaped evidence."""
    import jax

    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.data.leaf_gen import build_leaf_mnist_federation
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig

    tpu = _is_tpu()
    N = 1000 if tpu else 100
    max_rounds = 150 if tpu else 80
    # the anchor config is 1000 power-law clients; the CPU fallback
    # subsamples to 100 and MUST label itself smoke, not anchor
    config = (f"B=10 lr=0.03 E=1 10/round, {N} power-law clients, "
              "calibrated 85% ceiling"
              + (" (benchmark/README.md:12 anchor)" if N == 1000
                 else " (CPU SMOKE SUBSAMPLE of the 1000-client anchor)"))
    # calibrated corpus: 85% Bayes ceiling + noise=0.6 so
    # crossing the >75% anchor takes real learning (~15+ rounds), not a
    # saturating round-1 hit
    ds = build_leaf_mnist_federation(client_num=N, seed=0, target_acc=0.85,
                                     noise=0.6)
    api = FedAvgAPI(ds, LogisticRegression(num_classes=10),
                    config=FedAvgConfig(
                        comm_round=max_rounds, client_num_per_round=10,
                        frequency_of_the_test=10**9,
                        eval_train_subsample=2000,
                        train=TrainConfig(epochs=1, batch_size=10,
                                          lr=0.03)))
    # round 0 doubles as the compile warmup: excluded from the TIMER (TTA
    # measures steady state) but counted as a communication round, and its
    # accuracy is checked so an immediate target hit reports 1 round
    api.run_round(0)
    if api.evaluate(0).get("test_acc", 0.0) >= 0.75:
        return {"seconds_to_75pct": 0.0, "rounds_to_75pct": 1,
                "clients_total": N, "config": config}
    jax.block_until_ready(api.variables)
    t0 = time.perf_counter()
    reached = None
    for r in range(1, max_rounds + 1):
        api.run_round(r)
        if api.evaluate(r).get("test_acc", 0.0) >= 0.75:
            reached = r + 1  # rounds COMPLETED, including round 0
            break
    dt = time.perf_counter() - t0
    return {
        "seconds_to_75pct": round(dt, 4) if reached else None,
        "rounds_to_75pct": reached,
        "clients_total": N,
        "config": config,
    }


def bench_time_to_target(target_acc: float = 0.95, max_rounds: int = 60
                         ) -> dict:
    import jax

    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig

    # partial participation + low lr so the target takes tens of rounds —
    # a 1-round hit measures nothing
    ds = make_blob_federated(client_num=32, dim=32, class_num=8,
                             n_samples=8000, seed=3,
                             partition_method="hetero", partition_alpha=0.3)
    api = FedAvgAPI(ds, LogisticRegression(num_classes=ds.class_num),
                    config=FedAvgConfig(
                        comm_round=max_rounds, client_num_per_round=8,
                        frequency_of_the_test=10**9,
                        train=TrainConfig(epochs=1, batch_size=64,
                                          lr=0.003)))
    api.run_round(0)  # compile (excluded: TTA measures the steady state)
    api.evaluate(0)
    jax.block_until_ready(api.variables)

    t0 = time.perf_counter()
    reached = None
    for r in range(1, max_rounds + 1):
        api.run_round(r)
        acc = api.evaluate(r).get("test_acc", 0.0)
        if acc >= target_acc:
            reached = r
            break
    dt = time.perf_counter() - t0
    return {
        "seconds_to_target": round(dt, 4) if reached else None,
        "rounds_to_target": reached,
        "target_acc": target_acc,
    }


def bench_smoke_chip() -> dict:
    """The <=60 s chip-smoke stage: headline rounds/s + MFU, the bf16
    variant, and one flash-attention step at S=2048 — run first and
    persisted immediately, so a failure later in the suite cannot cost
    the run its chip evidence. Shapes are the full flagship shapes; only
    the timed-round counts shrink."""
    import jax
    import jax.numpy as jnp

    out = {}
    tpu = _is_tpu()
    # full flagship shapes on chip; CPU shrinks exactly like
    # bench_fedavg_cnn (the conv backward is ~1000x slower there and the
    # CPU smoke is only a does-it-run check)
    api = _make_api("cnn", 28, 1, CLASSES, 11,
                    samples=SAMPLES_PER_CLIENT if tpu else 2 * BATCH,
                    clients=CLIENTS_PER_ROUND if tpu else 2)
    # a cost-probe failure is reported loudly IN the row, but must not
    # cost the rps capture
    flops, _, cost_err = _round_costs(api)
    rps = _bench_rounds(api, 10)
    peak = _device_peak_tflops() * 1e12
    out["rounds_per_sec"] = round(rps, 3)
    out["achieved_tflops"] = _nn(round(rps * flops / 1e12, 3))
    out["mfu"] = _nn(round(rps * flops / peak, 4)) if peak == peak else None
    if cost_err and tpu:
        out["cost_probe_error"] = cost_err
    if tpu:
        api16 = _make_api("cnn", 28, 1, CLASSES, 11,
                          compute_dtype="bfloat16")
        out["rounds_per_sec_bf16"] = round(_bench_rounds(api16, 10), 3)

    from fedml_tpu.ops.flash_attention import flash_attention
    interpret = not _is_tpu()
    B, S, H, D = (4, 2048, 4, 64) if _is_tpu() else (1, 256, 2, 32)
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
               for _ in range(3))

    @jax.jit
    def step(q, k, v):
        def loss(q):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           interpret=interpret) ** 2)
        return jax.grad(loss)(q)

    g = step(q, k, v)  # compile
    jax.block_until_ready(g)
    t0 = time.perf_counter()
    steps = 3
    for _ in range(steps):
        g = step(q, k, v)
    jax.block_until_ready(g)
    out["flash_attn_fwd_bwd_tokens_per_sec"] = round(
        steps * B * S / (time.perf_counter() - t0), 1)
    out["flash_attn_shape"] = f"B={B} S={S} H={H} D={D}"
    # NB: this is the bare attention op (fwd+bwd), deliberately cheap for
    # the <=60s budget — NOT comparable to transformer_flash_s2048's
    # full 4-layer LM train-step tokens/s
    out["flash_attn_note"] = "bare attention op, not the LM train step"
    return out


def bench_torch_baseline() -> float:
    """Reference-style sequential simulation (torch CPU, this host)."""
    import torch
    import torch.nn as tnn

    class CNN(tnn.Module):
        def __init__(self):
            super().__init__()
            self.c1 = tnn.Conv2d(1, 32, 3)
            self.c2 = tnn.Conv2d(32, 64, 3)
            self.pool = tnn.MaxPool2d(2, 2)
            self.d1 = tnn.Dropout(0.25)
            self.fc1 = tnn.Linear(9216, 128)
            self.d2 = tnn.Dropout(0.5)
            self.fc2 = tnn.Linear(128, CLASSES)

        def forward(self, x):
            x = torch.relu(self.c1(x))
            x = torch.relu(self.c2(x))
            x = self.d1(self.pool(x))
            x = x.flatten(1)
            x = self.d2(torch.relu(self.fc1(x)))
            return self.fc2(x)

    x, y = make_data()
    xt = torch.from_numpy(np.transpose(x, (0, 1, 4, 2, 3)))
    yt = torch.from_numpy(y).long()
    model = CNN()
    global_sd = {k: v.clone() for k, v in model.state_dict().items()}
    crit = tnn.CrossEntropyLoss()

    t0 = time.perf_counter()
    for _ in range(BASELINE_ROUNDS):
        locals_sd = []
        for c in range(CLIENTS_PER_ROUND):
            model.load_state_dict(global_sd)
            opt = torch.optim.SGD(model.parameters(), lr=0.1)
            model.train()
            for b in range(SAMPLES_PER_CLIENT // BATCH):
                xb = xt[c, b * BATCH:(b + 1) * BATCH]
                yb = yt[c, b * BATCH:(b + 1) * BATCH]
                opt.zero_grad()
                crit(model(xb), yb).backward()
                opt.step()
            locals_sd.append(
                {k: v.detach().clone()
                 for k, v in model.state_dict().items()})
        global_sd = {
            k: sum(sd[k] for sd in locals_sd) / len(locals_sd)
            for k in global_sd
        }
    return BASELINE_ROUNDS / (time.perf_counter() - t0)


class _StageTimeout(BaseException):
    # BaseException so broad `except Exception` blocks inside a stage
    # (e.g. _round_flops' cost-model fallback) cannot swallow the timeout
    pass


def _run(name, fn, timeout_s: int = 420):
    """Isolate workloads: a stage that raises or overruns its timeout
    becomes an ``{"error": ...}`` row, so the rows of the stages that did
    run are still printed; main() turns any error row into a non-zero
    exit code."""
    import signal

    timeout_s = int(os.environ.get("FEDML_BENCH_STAGE_TIMEOUT_S", timeout_s))

    def on_alarm(signum, frame):
        raise _StageTimeout(f"{name} exceeded {timeout_s}s")

    _log(f"start {name}")
    prev = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(timeout_s)
    try:
        out = fn()
        _log(f"done  {name}: {out}")
        return out
    except _StageTimeout as exc:
        _log(f"TIMEOUT {name}: {exc}")
        return {"error": f"stage timeout after {timeout_s}s"}
    except Exception as exc:  # noqa: BLE001 — survive and report
        _log(f"FAIL  {name}: {exc!r}")
        return {"error": repr(exc)}
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)


def _no_nan(obj):
    """Recursively nan/inf -> None: persisted artifacts must stay strict
    RFC-8259 JSON (json.dump would happily write bare NaN literals that
    break jq/JSON.parse/Go consumers of the evidence files)."""
    if isinstance(obj, dict):
        return {k: _no_nan(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_no_nan(v) for v in obj]
    if _nonfinite(obj):
        return None
    return obj


#: bumped when the bench artifact layout changes incompatibly. Every
#: artifact bench.py writes carries ``schema_version`` + ``run_id`` and
#: is indexed in runs/MANIFEST.json, so a stale file from an old session
#: is identifiable by inspection instead of by filename archaeology.
BENCH_SCHEMA_VERSION = 1
_RUN_ID: "str | None" = None


def _bench_run_id() -> str:
    """One id per bench invocation (UTC stamp + pid), stamped into every
    artifact this process writes."""
    global _RUN_ID
    if _RUN_ID is None:
        _RUN_ID = (time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
                   + f"-{os.getpid()}")
    return _RUN_ID


def _update_manifest(relpath: str) -> None:
    """Index one artifact write into runs/MANIFEST.json (atomic tmp +
    os.replace — the repo's artifact-write discipline). The manifest is
    the `ls runs/` replacement: which files are live evidence, from
    which run, at which schema."""
    path = os.path.join("runs", "MANIFEST.json")
    manifest: dict = {}
    try:
        with open(path) as f:
            manifest = json.load(f)
    except (OSError, ValueError):
        manifest = {}
    if not isinstance(manifest, dict):
        manifest = {}
    arts = manifest.get("artifacts")
    if not isinstance(arts, dict):
        arts = manifest["artifacts"] = {}
    arts[relpath.replace(os.sep, "/")] = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "run_id": _bench_run_id(),
        "written_at_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                        time.gmtime()),
    }
    manifest["note"] = ("bench.py-maintained index of live evidence "
                        "artifacts; superseded partials live under "
                        "runs/archive/")
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def _write_artifact(name: str, obj: dict) -> None:
    """Write one stamped bench artifact to runs/<name> atomically and
    index it in the manifest — the single write path for every JSON
    evidence file this process produces."""
    os.makedirs("runs", exist_ok=True)
    obj = dict(obj)
    obj["schema_version"] = BENCH_SCHEMA_VERSION
    obj["run_id"] = _bench_run_id()
    rel = os.path.join("runs", name)
    tmp = f"{rel}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(_no_nan(obj), f, indent=2)
    os.replace(tmp, rel)
    _update_manifest(rel)


def _persist_partial(partial: dict) -> None:
    """Write per-stage results as they land (runs/bench_partial.json): if
    the process dies mid-suite, every stage that completed stays on disk
    as evidence."""
    _write_artifact("bench_partial.json", partial)


#: the append-only performance trajectory (fedml_tpu/obs/trend.py):
#: one compact row per measured stage, keyed (stage, host_fingerprint),
#: checked against the trailing median under --check-trend
_TREND_LEDGER = os.path.join("runs", "trends.jsonl")


def _trend_metrics(row: dict) -> "dict | None":
    """The gated figures of one stage row (rounds/sec + bytes/round),
    or None when the stage measured neither — error rows never enter
    the trajectory as evidence."""
    if not isinstance(row, dict) or "error" in row:
        return None
    rps = row.get("rounds_per_sec")
    bpr = row.get("bytes_per_round_total")
    if rps is None:
        # leg-structured stages: gate on the leg whose regression
        # matters (the compressed wire / the chaos-or-kill recovery leg)
        for leg in ("policy_topk_ef_int8", "chaos", "kill", "churn"):
            sub = row.get(leg)
            if isinstance(sub, dict) \
                    and sub.get("rounds_per_sec") is not None:
                rps = sub["rounds_per_sec"]
                if bpr is None:
                    bpr = sub.get("bytes_per_round_total")
                break
    if rps is None and bpr is None:
        return None
    out = {}
    if rps is not None:
        out["rounds_per_sec"] = rps
    if bpr is not None:
        out["bytes_per_round"] = bpr
    return out


def _append_trend_row(stage_key: str, row: dict,
                      host_tag: str) -> "list[str]":
    """Append one stage's trend row and return its regression verdicts
    (vs the ledger BEFORE the append — the new row must not feed its
    own median). No-measurement stages are logged, not silently
    skipped."""
    from fedml_tpu.obs import trend
    metrics = _trend_metrics(row)
    if metrics is None:
        _log(f"trend ledger: no gated metrics for {stage_key} — "
             "no trajectory row")
        return []
    trow = trend.make_row(stage_key, metrics, host_tag=host_tag,
                          run_id=_bench_run_id())
    problems = trend.check_row(trend.load_rows(_TREND_LEDGER), trow)
    trend.append_row(_TREND_LEDGER, trow)
    for p in problems:
        _log("TREND REGRESSION: " + p)
    return problems


#: the REAL stdout, captured before main() re-points sys.stdout at stderr
#: so stray library prints can't corrupt the driver's parse: the contract
#: line is the ONLY thing this process writes to its real stdout.
_CONTRACT_STREAM = None


def _emit(line: dict) -> None:
    """Print the driver contract line AND persist it to
    runs/bench_details.json (also on failure paths, so a stale success
    file can never shadow the latest outcome)."""
    line = _no_nan(dict(line, schema_version=BENCH_SCHEMA_VERSION,
                        run_id=_bench_run_id()))
    _write_artifact("bench_details.json", line)
    print(json.dumps(line), file=_CONTRACT_STREAM or sys.stdout,
          flush=True)


def _arm_global_watchdog(deadline_s: int, partial: dict) -> None:
    """Last line of defense: a daemon thread that force-exits the process
    if the whole suite overruns. SIGALRM cannot interrupt a main thread
    blocked inside native code, but a sibling thread still runs — it
    emits the contract line with whatever stages completed, then
    hard-exits non-zero."""
    import threading

    def fire():
        try:
            _log(f"GLOBAL TIMEOUT after {deadline_s}s — emitting partial "
                 "line")
            # snapshot first: the main thread's staged() may insert keys
            # concurrently and a mid-iteration RuntimeError here would
            # defeat the force-exit
            snap = dict(partial)
            flagship = snap.get("fedavg_femnist_cnn") or {}
            _emit({
                "metric": "fedavg_rounds_per_sec_femnist_cnn",
                "value": flagship.get("rounds_per_sec", 0.0),
                "unit": "rounds/s",
                "vs_baseline": None,
                "extra": {**snap,
                          "error": f"global bench timeout after "
                                   f"{deadline_s}s"},
            })
        finally:
            os._exit(1)

    t = threading.Timer(deadline_s, fire)
    t.daemon = True
    t.start()


def _probe_device(timeout_s: int = 180):
    """Ask a CHILD process what backend JAX finds, with a hard timeout
    (a hang inside native client init is out of reach of Python signal
    handlers). A chip belongs to one process at a time; this child may
    open it only because it has exited before this process first touches
    JAX — and this process must not have touched JAX before calling it.
    The child applies the one backend rule (utils.on_tpu), so an unknown
    backend fails here."""
    import subprocess

    code = ("import json, jax; from fedml_tpu.utils import on_tpu;"
            "print(json.dumps({'tpu': on_tpu(),"
            " 'backend': jax.default_backend(),"
            " 'device': jax.devices()[0].device_kind,"
            " 'count': len(jax.devices())}))")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=timeout_s,
                              cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {"error": f"device probe hung for {timeout_s}s"}
    if proc.returncode != 0:
        return {"error": "device probe failed: " + proc.stderr[-500:]}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception:  # noqa: BLE001
        return {"error": "device probe unparseable: " + proc.stdout[-500:]}


#: ordered suite: (partial key, log name, thunk, aliases for --stages=)
_STAGES = (
    ("fedavg_femnist_cnn", "fedavg_femnist_cnn",
     lambda: bench_fedavg_cnn(), ("headline", "cnn")),
    ("fedavg_femnist_cnn_bf16", "fedavg_femnist_cnn_bf16",
     lambda: bench_fedavg_cnn_bf16(), ("bf16",)),
    ("fedavg_femnist_cnn_fused", "fedavg_femnist_cnn_fused",
     lambda: bench_fedavg_cnn_fused_headline(), ("fused_headline",)),
    ("resnet18_gn_fedcifar100", "resnet18_gn",
     lambda: bench_resnet18_gn(), ("resnet", "resnet18_gn")),
    ("transformer_flash_s2048", "transformer_flash",
     lambda: bench_transformer_flash(), ("flash", "transformer_flash")),
    ("fedavg_powerlaw_1000", "fedavg_powerlaw_1000",
     lambda: bench_powerlaw_1000(), ("powerlaw",)),
    ("population_scale", "population_scale",
     lambda: bench_population_scale(),
     ("million", "population", "virtualization")),
    ("cross_silo_compression", "cross_silo_compression",
     lambda: bench_cross_silo_compression(),
     ("compression", "cross_silo", "wire")),
    ("round_overheads", "round_overheads",
     lambda: bench_round_overheads(),
     ("overheads", "io")),
    ("cross_silo_faults", "cross_silo_faults",
     lambda: bench_cross_silo_faults(),
     ("faults", "chaos", "fault_tolerance")),
    ("fanout_agg", "fanout_agg",
     lambda: bench_fanout_agg(),
     ("fanout", "hotpath", "round_hot_path")),
    ("serving", "serving",
     lambda: bench_serving(), ("serve", "inference")),
    ("server_failover", "server_failover",
     lambda: bench_server_failover(),
     ("failover", "control_plane")),
    ("multi_tenancy", "multi_tenancy",
     lambda: bench_multi_tenancy(),
     ("tenancy", "sched", "scheduler")),
    ("wan_churn", "wan_churn",
     lambda: bench_wan_churn(),
     ("wan", "churn", "diurnal")),
    ("fedavg_fused_rounds", "fedavg_fused_rounds",
     lambda: bench_fused_rounds(), ("fused", "fused_rounds")),
    ("fedavg_fused_device_sampling", "fedavg_fused_device_sampling",
     lambda: bench_fused_device_sampling(), ("fused_device",)),
    ("federated_parallel_axes", "federated_parallel_axes",
     lambda: bench_parallel_axes(), ("parallel_axes", "axes")),
    ("mesh_scaling", "mesh_scaling",
     lambda: bench_mesh_scaling(), ("mesh", "scaling", "multichip")),
    ("time_to_target_mnist_lr", "time_to_target_mnist_lr",
     lambda: bench_time_to_target_mnist_lr(), ("tta_mnist",)),
    ("time_to_target_acc", "time_to_target",
     lambda: bench_time_to_target(), ("tta",)),
)


def _parse_stage_selection(argv) -> "set | None":
    """``--stages=resnet,flash`` -> the matching partial keys (None = all):
    run only the named stages."""
    for arg in argv:
        if arg.startswith("--stages="):
            want = {tok.strip() for tok in arg.split("=", 1)[1].split(",")
                    if tok.strip()}
            keys = set()
            if want & {"smoke", "smoke_chip"}:
                keys.add("smoke_chip")
                want -= {"smoke", "smoke_chip"}
            for key, _, _, aliases in _STAGES:
                if key in want or want & set(aliases):
                    keys.add(key)
                    want -= {key, *aliases}
            if want:
                known = ["smoke", "smoke_chip"] + \
                    [key for key, _, _, al in _STAGES] + \
                    [a for _, _, _, al in _STAGES for a in al]
                raise SystemExit(f"unknown --stages tokens {sorted(want)}; "
                                 f"known: {sorted(known)}")
            return keys
    return None


#: stages whose legs are CHILD processes that open the device themselves
#: (each leg needs its own process for the RSS high-water mark). A chip
#: belongs to one process at a time, so main() runs these before this
#: process first touches JAX.
_CHILD_PROCESS_STAGES = ("population_scale",)


def main():
    from fedml_tpu.utils import enable_persistent_compilation_cache

    # persistent XLA compile cache: recompiling programs a previous run
    # already compiled is the largest avoidable waste of chip time
    # (configures only — no device is opened here)
    enable_persistent_compilation_cache()
    # frame stdout: the driver json-parses it. Everything a stage (or an
    # imported library) prints goes to stderr; the single contract JSON
    # line is written to the real stdout by _emit via _CONTRACT_STREAM.
    global _CONTRACT_STREAM
    _CONTRACT_STREAM = sys.stdout
    sys.stdout = sys.stderr
    try:
        return _main_framed()
    finally:
        sys.stdout, _CONTRACT_STREAM = _CONTRACT_STREAM, None


def _main_framed():
    smoke_only = "--smoke-chip" in sys.argv
    selected = _parse_stage_selection(sys.argv)
    check_trend = "--check-trend" in sys.argv
    trend_problems: list = []
    timeout_s = int(os.environ.get("FEDML_BENCH_PROBE_TIMEOUT_S", 180))
    info = _probe_device(timeout_s)
    if "error" in info:
        # no device, no measurement: say so and fail
        _log(f"device probe failed: {info['error']}")
        _emit({"metric": "fedavg_rounds_per_sec_femnist_cnn",
               "value": 0.0, "unit": "rounds/s", "vs_baseline": None,
               "extra": {"error": info["error"]}})
        return 1
    _log(f"backend={info['backend']} device={info['device']!r} "
         f"count={info['count']}")
    global _ON_TPU
    tpu = _ON_TPU = info["tpu"]
    # every row carries where it ran, so chip numbers can never be
    # conflated with CPU smoke numbers
    host_tag = f"tpu:{info['device']}" if tpu else "cpu-smoke"
    partial: dict = {}
    _arm_global_watchdog(
        int(os.environ.get("FEDML_BENCH_TOTAL_TIMEOUT_S", 2400)), partial)

    def staged(key, name, fn):
        out = _run(name, fn)
        if isinstance(out, dict) and "error" not in out:
            # host/captured_at_utc are evidence stamps; error rows are
            # not evidence
            out.setdefault("host", host_tag)
            out.setdefault("captured_at_utc", time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
        partial[key] = out
        _persist_partial(partial)
        # trend trajectory: every freshly measured stage appends a
        # compact row; regressions vs the trailing median are collected
        # and (under --check-trend) turn the exit code non-zero
        trend_problems.extend(_append_trend_row(key, out, host_tag))
        return out

    def wanted(key):
        if smoke_only:
            return key == "smoke_chip"
        return selected is None or key in selected

    # one process per chip: child-process stages first, while this process
    # has not opened the device (see _CHILD_PROCESS_STAGES); then the
    # <=60s smoke stage, persisted before the long suite
    child = [s for s in _STAGES if s[0] in _CHILD_PROCESS_STAGES]
    smoke_row = ("smoke_chip", "smoke_chip", bench_smoke_chip, ())
    in_process = [s for s in _STAGES if s[0] not in _CHILD_PROCESS_STAGES]
    for key, name, fn, _aliases in (*child, smoke_row, *in_process):
        if wanted(key):
            staged(key, name, fn)
    if smoke_only:
        smoke = partial["smoke_chip"]
        _emit({
            "metric": "fedavg_rounds_per_sec_femnist_cnn",
            "value": smoke.get("rounds_per_sec", 0.0),
            "unit": "rounds/s",
            "vs_baseline": None,
            "extra": {"smoke_chip": smoke, "mode": "--smoke-chip"},
        })
        return _exit_code(partial, check_trend, trend_problems)

    flagship = partial.get("fedavg_femnist_cnn", {})
    flagship_bf16 = partial.get("fedavg_femnist_cnn_bf16", {})
    flagship_fused = partial.get("fedavg_femnist_cnn_fused", {})
    resnet = partial.get("resnet18_gn_fedcifar100", {})
    transformer = partial.get("transformer_flash_s2048", {})
    powerlaw = partial.get("fedavg_powerlaw_1000", {})
    population = partial.get("population_scale", {})
    fused = partial.get("fedavg_fused_rounds", {})
    base_out = _run("torch_baseline",
                    lambda: {"rps": bench_torch_baseline()})
    base = base_out.get("rps", float("nan"))

    extra = {
        # every stage this invocation ran, error rows included
        **partial,
        "baseline_kind": "torch_cpu_this_host (reference-style sequential "
                         "simulation; NOT the published GPU baseline)",
        "baseline_rounds_per_sec": round(base, 3) if base == base else None,
    }
    headline = flagship.get("rounds_per_sec", 0.0)
    # CPU runs shrink the workload (smoke shapes), so the ratio against the
    # full-size torch baseline is only meaningful on the chip
    extra["smoke_shapes"] = not tpu
    extra["host"] = host_tag
    # the competitive metrics, flat, so the driver-recorded artifact
    # captures them even if a consumer drops the nested dicts
    extra["headline_summary"] = {
        "femnist_cnn_rps": flagship.get("rounds_per_sec"),
        "femnist_cnn_mfu": flagship.get("mfu"),
        "femnist_cnn_bf16_rps": flagship_bf16.get("rounds_per_sec"),
        "femnist_cnn_fused_bf16_rps": flagship_fused.get(
            "rounds_per_sec_fused_bf16"),
        "femnist_cnn_fused_mfu": flagship_fused.get("mfu"),
        "resnet18_gn_rps": resnet.get("rounds_per_sec"),
        "resnet18_gn_mfu": resnet.get("mfu"),
        "powerlaw_1000_rps": powerlaw.get("rounds_per_sec"),
        "powerlaw_pipeline_speedup_x": powerlaw.get("pipeline_speedup_x"),
        "powerlaw_prefetch_hidden_ms": powerlaw.get("prefetch_hidden_ms"),
        "population_1m_rss_over_100k_x": population.get(
            "rss_1m_over_100k_x"),
        "population_virtual_vs_resident_1k_x": population.get(
            "virtual_vs_resident_1k_x"),
        "fused_block_rps": fused.get("rounds_per_sec_fused_block"),
        "fused_block_vs_host_cohort_x": fused.get(
            "fused_block_vs_host_cohort_x"),
        "flash_tokens_per_sec": transformer.get("tokens_per_sec"),
    }
    line = {
        "metric": "fedavg_rounds_per_sec_femnist_cnn",
        "value": headline,
        "unit": "rounds/s",
        "vs_baseline": (round(headline / base, 2)
                        if tpu and base == base and base > 0
                        else None),
        # the denominator is the reference-style sequential torch loop ON
        # THIS HOST's CPU, not the published 8xA100 NCCL baseline (which
        # is not measurable here; see BASELINE.md for the projection)
        "vs_baseline_kind": "torch_cpu_this_host",
        "extra": extra,
    }
    if trend_problems:
        extra["trend_regressions"] = trend_problems
    _emit(line)
    return _exit_code(partial, check_trend, trend_problems)


def _exit_code(partial: dict, check_trend: bool,
               trend_problems: "list[str]") -> int:
    """Non-zero when any stage errored or timed out (its row says why),
    else the --check-trend verdict."""
    failed = sorted(k for k, row in partial.items()
                    if isinstance(row, dict) and "error" in row)
    if failed:
        _log(f"{len(failed)} stage(s) failed: {', '.join(failed)}")
        return 1
    return _trend_verdict(check_trend, trend_problems)


def _trend_verdict(check_trend: bool, problems: "list[str]") -> int:
    """--check-trend turns collected regressions into a non-zero exit;
    without the flag they already traveled in the emit's extra (and the
    ledger holds the row either way)."""
    if not check_trend or not problems:
        return 0
    _log(f"--check-trend: {len(problems)} regression(s) vs the trend "
         "ledger — failing")
    return 1


if __name__ == "__main__":
    sys.exit(main())
