#!/usr/bin/env bash
# CI fast lane (the reference's per-PR Travis role, CI-script-fedavg.sh):
# static analysis (analysis CLI: AST lint + jaxpr audit, ~25 s), then a
# 100k-client population-virtualization smoke (seconds — FedAvg rounds
# through the tiered client-state store; the 1M leg lives in the slow
# lane + the population_scale bench stage), then the server-failover
# smoke (~25 s — a real TCP server subprocess SIGKILLed mid-schedule,
# restarted, and required to finish with cp_restores >= 1 and a ledger
# matching the unkilled reference) now recording a flight log that
# `obs merge --ledger` must rebuild cleanly (a real two-epoch SIGKILL
# log, artifact under runs/obs_smoke/), then unit + integration tests
# on 8 virtual CPU devices, ~7 min, followed by the SOFT-FAIL trend
# lane: the session's trend-ledger rows (bench stages + the pytest
# tests/sec row this run just appended) are checked against their
# trailing medians — regressions WARN while the trajectory builds;
# flip to a hard gate once runs/trends.jsonl has history.
set -euo pipefail
cd "$(dirname "$0")/.."
./ci/run_static.sh
JAX_PLATFORMS=cpu python -m fedml_tpu.state.population \
    --population 100000 --rounds 2 --cohort 10
rm -rf runs/obs_smoke && mkdir -p runs/obs_smoke
JAX_PLATFORMS=cpu python -m fedml_tpu.control.failover_harness --smoke \
    --ckpt_dir runs/obs_smoke --obs_dir runs/obs_smoke/flight
# same SIGKILL smoke under the LEGACY inline checkpointer: the default
# leg above exercises the async writer (coalescing slot, writer-thread
# fsync, restore-on-older-boundary + ledger replay); this leg pins
# --checkpoint_sync to the old synchronous semantics so both durability
# modes keep the bit-exact failover contract
rm -rf runs/obs_smoke_sync && mkdir -p runs/obs_smoke_sync
JAX_PLATFORMS=cpu python -m fedml_tpu.control.failover_harness --smoke \
    --checkpoint_sync --ckpt_dir runs/obs_smoke_sync
JAX_PLATFORMS=cpu python -m fedml_tpu.obs merge runs/obs_smoke/flight \
    --ledger runs/obs_smoke/killed/ledger.jsonl \
    --output runs/obs_smoke/merged.json
# multi-job tenancy smoke (fedml_tpu/sched): two federation jobs over
# ONE shared fabric + device, the victim's server SIGKILLed
# mid-schedule and respawned — exits non-zero unless the survivor's
# ledger AND final model are bit-identical to its solo leg, the victim
# recovered via its own job_<id>/ checkpoint (cp_restores >= 1), and
# `obs report` renders one per-tenant summary from the shared obs dir
rm -rf runs/sched_smoke
JAX_PLATFORMS=cpu python -m fedml_tpu.sched smoke --root runs/sched_smoke
# WAN churn smoke (fedml_tpu/wan, ~20 s): a small federation over TCP
# through a diurnal trough + flap burst — exits non-zero unless the
# FULL schedule completed (churn degrades, never stalls), >= 1 silo was
# deadline-evicted AND >= 1 rejoined through the trace-gated JOIN path,
# every sampled cohort member was trace-available, and re-running the
# same trace seed produced a bit-identical round/cohort ledger
JAX_PLATFORMS=cpu python -m fedml_tpu.wan --smoke
# round-hot-path fan-out smoke (fedml_tpu/comm, ~15 s): a real-TCP
# broadcast against a peer that stalls its reads (kernel backpressure)
# plus a 4-silo federation with a chaos-delayed silo — exits non-zero
# unless the round-open broadcast returns in a fraction of the stall,
# fast peers drain while the slow peer is still wedged, the payload
# was encoded exactly once, and the chaos run's ledger + final model
# are bit-identical to the fault-free reference
JAX_PLATFORMS=cpu python -m fedml_tpu.comm.fanout_smoke
# federated-serving smoke (fedml_tpu/serve, ~10 s): train a small
# federation WITH the TCP/JSON inference endpoint attached, drive 50
# closed-loop requests, and exit non-zero unless at least one hot swap
# landed, ZERO requests were shed, and the SLO report carries measured
# latency quantiles + the served round
JAX_PLATFORMS=cpu python -m fedml_tpu.serve --smoke
# named-mesh smoke (fedml_tpu/parallel/mesh, ~5 s, <= 20 s budget): a
# real 2-device data-mesh federation with the flight recorder ON — 3
# host rounds + one fused 2-round block through the named-mesh scan,
# the mesh entry points' collective signatures audited against
# ci/collective_baseline.json, and the flight log rebuilt by
# `obs merge --ledger` at rc 0 (artifact under runs/mesh_smoke/)
JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python -m fedml_tpu.parallel.mesh --smoke \
    --out runs/mesh_smoke
# slowest-20 artifact (tests/conftest.py sessionfinish hook): fast-lane
# time creep becomes a diffable runs/ number instead of a README
# anecdote — AND a trend-ledger row, so creep regresses like a bench
export FEDML_TPU_TEST_DURATIONS="runs/test_durations.json"
export FEDML_TPU_TREND_LEDGER="runs/trends.jsonl"
rc=0
python -m pytest tests/ -q -m "not slow" "$@" || rc=$?
JAX_PLATFORMS=cpu python -m fedml_tpu.obs trend runs/trends.jsonl \
    --check-latest \
    || echo "WARNING: performance trend regression (soft-fail lane;" \
            "see runs/trends.jsonl)" >&2
exit "$rc"
