"""The files the ``kanana_2_30b_a3b_ep8.silo4`` cell adds to the benchmark, as
far as a CPU can hold them to their word: the manifest entries, the
configuration's cut against the catalog's numbers and the program's own
parameter count, the cost function and the reference's FLOP count against
hand counts, and the roofline reader on a small trace, which must return
nothing on a program or a cell without what it reads."""

import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG = "kanana_2_30b_a3b_ep8.silo4", "kanana_2_30b_a3b_ep8"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}
#: name -> (unit, better, source, reader) of the six metrics the cell adds
METRICS = {
    "mla_ms": ("ms", "lower", "device_trace", "scope_ops"),
    "mla_core_roofline": ("%", "higher", "device_trace", "mla_roofline"),
    "shared_expert_ms": ("ms", "lower", "device_trace", "scope_ops"),
    "small_expert_ms": ("ms", "lower", "device_trace", "trace_ops"),
    "small_expert_roofline": ("%", "higher", "device_trace", "moe_roofline"),
    "small_expert_load_peak": ("ratio", "lower", "program_counter",
                               "stat_ratio")}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _module(*parts):
    from benchmark.harness import spec
    return spec.load_module(os.path.join(ROOT, "benchmark", *parts))


@pytest.fixture(scope="module")
def manifest():
    return _load("BENCHMARK.json")


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", CONFIG + ".json")


# -- the manifest -----------------------------------------------------------------

def test_the_cell_and_the_configuration_are_in_the_manifest(manifest):
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL] == {"name": CELL, "config": CONFIG, "traffic": "silo4",
                           "chips": 1, "why": cells[CELL]["why"]}
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == REDUCED
    assert entry["source"] == (
        "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/"
        "blob/main/config.json")
    for text in (cells[CELL]["why"], entry["why"], entry["source"]):
        assert 1 <= len(text) <= 200 and "\t" not in text and "\n" not in text
    why = cells[CELL]["why"]
    assert "latent attention" in why and "shared experts" in why
    assert ("2,048" in why) != ("1,536" in why)  # the row length it runs
    # still one four-chip cell
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(json.dumps(manifest)) < 64 * 1024


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_new_per_layer_metrics_list_the_new_cell_alone(manifest, name):
    unit, better, source, reader = METRICS[name]
    metric = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert metric == {"name": name, "unit": unit, "better": better,
                      "source": source, "layer": "trainer",
                      "moves": "rounds_per_s", "workloads": [CELL]}
    assert NAME.match(name) and re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}",
                                             unit)
    entry = _load("benchmark", "metrics", name + ".json")
    assert entry["reader"] == reader
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers",
                                       reader + ".py"))
    args = entry["args"]
    if "kernel" in args:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "kernels", args["kernel"] + ".py"))
    if source == "device_trace":
        assert args["outside_spans"] == ["bench.evaluate"]
        assert re.search(args["within_modules"], "jit_round_fn(123)")
        assert not re.search(args["within_modules"], "jit_eval(9)")


def test_the_small_expert_metrics_time_the_same_operations():
    ms = _load("benchmark", "metrics", "small_expert_ms.json")["args"]
    share = _load("benchmark", "metrics", "small_expert_roofline.json")["args"]
    assert share["kernel"] == "moe_experts"  # the accepted cost
    assert {k: v for k, v in share.items() if k != "kernel"} == ms
    pattern = re.compile(ms["pattern"])
    # a block's hidden products and gate, one expert's matrices, the stacks
    # of the 16 held, forward and transposed
    for text in ("f32[512,768]{1,0:T(8,128)}", "bf16[512,768]",
                 "f32[2048,768]", "f32[768,2048]{1,0}",
                 "f32[16,2048,768]{2,1,0:T(8,128)}", "bf16[16,768,2048]",
                 "pred[1,2048,768]"):
        assert pattern.search(text), text
    # not the shared experts, the dense feed forward, the latent attention,
    # the router, the head, a block's gathered rows, nor a tile annotation
    for text in ("f32[2048,1536]", "f32[1536,2048]", "f32[2048,6144]",
                 "f32[2048,576]", "f32[512,8192]", "f32[32,2048,192]",
                 "f32[32,1,512,2048]", "f32[2048,128]", "f32[16032,2048]",
                 "bf16[512,2048]{1,0:T(8,128)(2,1)}", "f32[12288]",
                 "f32[7680]", "f32[17680,2048]"):
        assert not pattern.search(text), text
    assert re.search(ms["exclude"],
                     "%fold = f32[16,2048,768] custom-call(...), "
                     "custom_call_target=\"tpu_custom_call\"")


def test_the_new_entries_come_last_and_the_accepted_lists_are_as_they_were(
        manifest):
    assert [m["name"] for m in manifest["per_layer"]][43:49] == [
        "mla_ms", "mla_core_roofline", "shared_expert_ms", "small_expert_ms",
        "small_expert_roofline", "small_expert_load_peak"]
    assert [w["name"] for w in manifest["workloads"]][8:9] == [CELL]
    assert [c["name"] for c in manifest["configs"]][5:6] == [CONFIG]
    lists = {m["name"]: m.get("workloads") for m in manifest["per_layer"]}
    # ISSUE 39: the new cell belongs in several of these; appending it is
    # the next ``benchmark`` issue's (PERF.md section 7 (12))
    for name in ("agg_kernel_ms", "agg_fold_roofline", "tokens_per_round",
                 "aggregate_scope_ms", "unscoped_ms", "attention_ms",
                 "mlp_ms", "lm_head_ms", "moe_scope_ms", "expert_load_peak"):
        assert CELL not in lists[name], name
    # every metric without a list reports in the new cell by its definition
    from benchmark.harness import spec
    cell = spec.load_cell(CELL)
    names = [m["name"] for m in cell.per_layer]
    for name in ("mfu", "train_device_ms", "device_idle_share",
                 "peak_hbm_gib", "loss_at_round_16", *METRICS):
        assert name in names
    for name in ("moe_ms", "ssd_ms", "agg_kernel_ms", "attention_ms"):
        assert name not in names
    assert [m["name"] for m in cell.end_to_end] == ["rounds_per_s", "setup_s"]
    assert cell.chips == 1 and cell.clients == 16


# -- the configuration ---------------------------------------------------------------

def test_every_catalog_number_is_in_the_file_or_listed_as_reduced(config):
    for key, value in CATALOG.items():
        assert key in config, key
        if key in config["reduced"]:
            assert config[key] != value
            assert config["published"][key] == value
            assert key in config["cut"]
        else:
            assert config[key] == value, key
    assert config["reduced"] == REDUCED
    kwargs = config["model"]["kwargs"]
    assert config["num_hidden_layers"] == len(kwargs["layer_ids"]) == 6
    assert config["n_routed_experts"] == kwargs["experts_held"][1] == 16
    assert config["vocab_size"] == config["model"]["output_dim"] \
        == config["data"]["vocab"] == 16032
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]  # the floor
    for text in ("rope_interleave", "mscale", "norm_epsilon", "expert_bias",
                 "initialisation", "content", "local_steps", "lr"):
        assert text in config["assumed"]
    assert "687,502,976" in config["cut"]["arithmetic"]
    assert "eight chips" in config["cut"]["n_routed_experts"]
    assert "vocabulary-parallel" in config["deployment"]
    assert "pipeline stages of six" in config["deployment"]


def test_no_width_is_cut_and_the_floors_hold(config):
    kwargs = config["model"]["kwargs"]
    # the architecture's own arguments and nothing else
    assert set(kwargs) == {
        "hidden_size", "num_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank", "intermediate_size",
        "moe_intermediate_size", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok", "experts_held", "layer_ids",
        "num_dense_layers", "rope_theta", "rms_norm_eps", "norm_topk_prob",
        "routed_scaling_factor"}
    for ours, theirs in (
            ("hidden_size", "hidden_size"), ("num_heads",
                                             "num_attention_heads"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"), ("kv_lora_rank", "kv_lora_rank"),
            ("intermediate_size", "intermediate_size"),
            ("moe_intermediate_size", "moe_intermediate_size"),
            ("n_routed_experts", "n_routed_experts"),  # the router's width
            ("n_shared_experts", "n_shared_experts"),
            ("num_experts_per_tok", "num_experts_per_tok"),
            ("num_dense_layers", "first_k_dense_replace"),
            ("rope_theta", "rope_theta"),
            ("rms_norm_eps", "rms_norm_eps"),
            ("norm_topk_prob", "norm_topk_prob"),
            ("routed_scaling_factor", "routed_scaling_factor")):
        assert kwargs[ours] == CATALOG[theirs], ours
    assert kwargs["qk_nope_head_dim"] + kwargs["qk_rope_head_dim"] \
        == CATALOG["qk_head_dim"]
    assert config["rope_interleave"] is CATALOG["rope_interleave"] is True
    # the floors: the leading dense layer once and at least four after it,
    # at least 8 routed experts, an eighth of the vocabulary
    assert kwargs["layer_ids"] == list(range(6))
    assert kwargs["experts_held"] == [0, 16]
    assert config["vocab_size"] * 8 >= CATALOG["vocab_size"]


def test_the_files_parameter_count_is_the_programs(config):
    from benchmark.harness import cell as cell_mod
    module = cell_mod.make_model(config)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == config["model"]["parameters"] == 687_502_976
    assert config["model"]["task"] == "lm_rows"
    assert config["model"]["create_model"] == "deepseek_v3"


def test_the_check_block_has_a_calibrated_timed_bound_and_no_small(config):
    check = config["check"]
    assert "small" not in check
    assert check["timed"]["param_fraction"] is not None
    assert 0.0 < check["timed"]["param_fraction"] < 0.25
    assert 0.0 < check["timed"]["max_param_change"] <= 0.5
    assert 0.0 < check["loss_rel_tol"] <= 0.1
    assert "my chip run" in check["why"]
    for control in ("no_shared_experts", "rope_off_shared_key",
                    "top_k_less_one", "bf16_fold"):
        assert control in check["why"], control
    assert config["reference"] == "deepseek_v3_local_sgd"
    assert "peak_memory_in_bytes" in config["measured"]["compiled_round"]
    for rows in ("128", "256", "512"):  # the three blocks read on the chip
        assert rows in config["measured"]["grouped_block"]


def test_the_traffic_is_the_issues(config):
    traffic = _load("benchmark", "traffic", "silo4.json")
    data, train = config["data"], config["train"]
    assert (data["generator"], data["clients"]) == ("token_silos", 16)
    assert (data["train_rows"], data["test_rows"]) == (2, 1)
    assert (data["zipf_s"], data["follow_share"]) == (1.1, 0.5)
    assert (train["batch_size"], train["epochs"]) == (1, 1)
    assert train["client_optimizer"] == "sgd"
    assert train["lr"] in (0.3, 0.1, 0.03, 0.01)
    # 2,048-token rows, or the 1,536 ISSUE 39 allows if a round is too slow
    assert data["sequence_length"] in (2048, 1536)
    tokens = (traffic["cohort"] * data["train_rows"] * train["epochs"]
              * data["sequence_length"])
    assert tokens == 8 * data["sequence_length"]
    # a held expert's mean load a step: 6 of 128 a token
    kwargs = config["model"]["kwargs"]
    pairs = data["sequence_length"] * kwargs["num_experts_per_tok"]
    assert pairs / kwargs["n_routed_experts"] in (96.0, 72.0)


# -- the cost function, the FLOP count, the reader -----------------------------------

def test_the_attention_cost_by_hand():
    cost = _module("kernels", "mla_attention.py").cost
    # a round of the cell: 16,384 tokens in rows of 2,048 through six layers
    flops, nbytes = cost(16384, 2048, 32, 192, 128, 6)
    seen = 2048 * 2049 // 2
    assert seen == 2_098_176
    row_layer = seen * (192 + 128) * 2 * 32
    assert row_layer == 42_970_644_480
    assert flops == 3.0 * row_layer * 8 * 6 == 6_187_772_805_120.0
    # q, k, v in and o out; those and do in; three gradients out:
    # 6 x 192 + 6 x 128 floats a token and head
    floats = (2 * 192 + 2 * 128) + (2 * 192 + 3 * 128) + (2 * 192 + 128)
    assert floats == 1920
    assert nbytes == 4.0 * floats * 32 * 16384 * 6 == 24_159_191_040.0
    # 31.4 ms of products at the bf16 peak against 29.5 ms of bytes at 819
    # GB/s: at 2,048 tokens the two bounds meet; the products lead
    assert 0.0314 < flops / 197e12 < 0.0315
    assert 0.0294 < nbytes / 819e9 < 0.0296
    # half the row: the same tokens see half the keys, and move the same
    shorter, same = cost(16384, 1024, 32, 192, 128, 6)
    assert shorter == 3.0 * (1024 * 1025 // 2) * 320 * 2 * 32 * 16 * 6
    assert same == nbytes
    # heads whose keys and values are one size, one layer
    assert cost(2048, 2048, 8, 64, 64, 1)[0] == 3.0 * seen * 128 * 2 * 8


def test_flops_per_row_bills_the_causal_half_and_the_balanced_load():
    """Against a hand count at a tiny size: every projection, the shared
    experts and the head once forward and twice backward; the routed experts
    at ``T x top_k x held / experts`` pairs; the router; attention at the
    causal half of its score matrices."""
    from benchmark.harness import flops
    from fedml_tpu.models import create_model
    reference = _module("references", "deepseek_v3_local_sgd.py")
    d, heads, nope, rope, v, rank = 32, 4, 12, 4, 8, 16
    inter, width, experts, shared, top_k, held = 48, 24, 16, 2, 3, 4
    vocab, length = 40, 16
    module = create_model(
        "deepseek_v3", output_dim=vocab, hidden_size=d, num_heads=heads,
        qk_nope_head_dim=nope, qk_rope_head_dim=rope, v_head_dim=v,
        kv_lora_rank=rank, intermediate_size=inter,
        moe_intermediate_size=width, n_routed_experts=experts,
        n_shared_experts=shared, num_experts_per_tok=top_k,
        experts_held=(4, held), layer_ids=(0, 1, 2))
    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, length), jnp.int32), train=False))
    got = reference.flops_per_row(
        module, "lm_rows", {"batch_size": 1, "lr": 0.1}, variables,
        np.zeros((1, length), np.int32), flops.count)
    projections = 2 * length * (d * heads * (nope + rope) + d * (rank + rope)
                                + rank * heads * (nope + v) + heads * v * d)
    core = length * (length + 1) // 2 * 2 * (nope + rope + v) * heads
    dense = 3 * 2 * length * d * inter
    shared_ff = 3 * 2 * length * d * shared * width
    router = 2 * length * d * experts
    routed = (length * top_k * held / experts) * 3 * 2 * d * width
    head = 2 * length * d * vocab
    want = 3 * (3 * (projections + core) + dense
                + 2 * (shared_ff + router + routed) + head)
    assert got == pytest.approx(want, rel=1e-12)
    # whole [T, T] matrices would bill 2 T / (T + 1) of the core
    assert core * 2 * length / (length + 1) == pytest.approx(
        length * length * 2 * (nope + rope + v) * heads)


def _trace(core_s, other_s, rounds):
    """A traced slice of ``rounds`` rounds: in each, inside ``jit_round_fn``,
    two operations of the attention core (``core_s`` seconds together), a
    projection of the block around it (``other_s``) and a shared expert's
    product; and during an evaluation the core's operation again."""
    names = ["%fusion.1 = f32[32,1,512,2048]{3,2,1,0} fusion(f32[32,1,512,"
             "192] %q)",
             "%convolution.2 = f32[32,1,512,128] convolution(f32[32,1,512,"
             "2048] %p)",
             "%convolution.3 = f32[2048,6144] convolution(f32[2048,2048] %x)",
             "%convolution.4 = f32[2048,1536] convolution(f32[2048,2048] %x)",
             "jit_round_fn(123)", "jit_eval(9)"]
    ops, modules, spans = [], [], [["bench.slice", 0.0, 10.0 * rounds + 5]]
    for r in range(rounds):
        t = 10.0 * r
        modules.append([4, t, 8.0])
        spans.append(["bench.run_round", t, 8.5])
        ops += [[0, t, 0.75 * core_s], [1, t + 2, 0.25 * core_s],
                [2, t + 4, other_s], [3, t + 6, 1.0]]
    t = 10.0 * rounds
    modules.append([5, t, 2.0])
    spans.append(["bench.evaluate", t, 3.0])
    ops.append([0, t + 0.5, 1.0])
    return {"names": names, "spans": spans, "devices": [
        {"name": "/device:TPU:0", "ops": ops, "async": [],
         "modules": modules}]}


def _scope_map(core=("fedml.local_train", "fedml.mla", "fedml.mla_core")):
    """What ``fedml_tpu.utils.tracing.device_scopes`` hands out, for the
    four instructions of ``_trace``."""
    mla = ("fedml.local_train", "fedml.mla")
    return {"jit_round_fn": types.SimpleNamespace(
        chains={"fusion.1": core, "convolution.2": core,
                "convolution.3": mla,
                "convolution.4": ("fedml.local_train",
                                  "fedml.shared_experts")},
        mixed=set(),
        kinds={"fusion.1": "f32[32,1,512,2048] fusion",
               "convolution.2": "f32[32,1,512,128] convolution",
               "convolution.3": "f32[2048,6144] convolution",
               "convolution.4": "f32[2048,1536] convolution"})}


def _ctx(config, trace=None, rounds=4, tokens=16384.0 * 4, traced=2):
    from benchmark.harness import spec
    from benchmark.harness import trace as tr
    cell = types.SimpleNamespace(
        config=config, module=lambda kind, name: spec.load_module(
            os.path.join(ROOT, "benchmark", kind, name + ".py")))
    counters = {} if tokens is None else {"tokens_dispatched": tokens}
    return types.SimpleNamespace(
        cell=cell, trace=trace, trace_rounds=traced if trace else 0,
        trace_window=tr.window_of(trace) if trace else None,
        window=types.SimpleNamespace(rounds=rounds, counters=counters),
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


@pytest.fixture()
def scope_map(monkeypatch):
    from fedml_tpu.utils import tracing
    maps = _scope_map()
    monkeypatch.setattr(tracing, "device_scopes", lambda: maps)
    return maps


def test_the_roofline_reader_against_hand_arithmetic(config, scope_map):
    entry = _load("benchmark", "metrics", "mla_core_roofline.json")
    read = _module("readers", "mla_roofline.py").read
    scope_ms = _module("readers", "scope_ops.py").read
    # 0.5 s of the core a round: not the projection's 0.25 s, not the shared
    # expert's second, not the evaluation's operation
    ctx = _ctx(config, _trace(0.5, 0.25, rounds=2))
    mla = _load("benchmark", "metrics", "mla_ms.json")["args"]
    shared = _load("benchmark", "metrics", "shared_expert_ms.json")["args"]
    assert scope_ms(ctx, **mla) == pytest.approx(750.0)
    assert scope_ms(ctx, **shared) == pytest.approx(1000.0)
    # 16,384 tokens a round x 6 layers: 6.19 TFLOP at 197 TFLOP/s (the
    # 24.16 GB at 819 GB/s are the smaller bound)
    least = 6_187_772_805_120.0 / 197e12
    assert least > 24_159_191_040.0 / 819e9
    got = read(ctx, **entry["args"])
    assert got == pytest.approx(100.0 * least / 0.5) \
        == pytest.approx(6.2820028)
    # the same work whatever implements it: a core twice as fast reads twice
    # the share; one at the bound would read 100
    assert read(_ctx(config, _trace(0.25, 0.25, rounds=2)),
                **entry["args"]) == pytest.approx(2 * got)
    assert read(_ctx(config, _trace(least, 0.25, rounds=2)),
                **entry["args"]) == pytest.approx(100.0)
    # half the tokens a round (the counter over the window's rounds)
    assert read(_ctx(config, _trace(0.5, 0.25, rounds=2),
                     tokens=16384.0 * 2),
                **entry["args"]) == pytest.approx(got / 2)


def test_the_roofline_reader_reads_nothing_where_there_is_nothing(
        config, scope_map, monkeypatch):
    entry = _load("benchmark", "metrics", "mla_core_roofline.json")
    read = _module("readers", "mla_roofline.py").read
    trace = _trace(0.5, 0.25, rounds=2)
    # no trace (an untraced run); a program without the counter; a window
    # without rounds
    assert read(_ctx(config), **entry["args"]) is None
    assert read(_ctx(config, trace, tokens=None), **entry["args"]) is None
    assert read(_ctx(config, _trace(0.5, 0.25, rounds=2), rounds=0),
                **entry["args"]) is None
    # a configuration without the latent-attention keys (every other cell)
    other = _load("benchmark", "configs", "lfm2_8b_a1b_ep4.json")
    assert read(_ctx(other, _trace(0.5, 0.25, rounds=2)),
                **entry["args"]) is None
    # a program that names no such scope (the parent: its map has other
    # names), and one that hands out no map
    scope_map["jit_round_fn"].chains.update({
        "fusion.1": ("fedml.local_train", "fedml.attention"),
        "convolution.2": ("fedml.local_train", "fedml.attention")})
    assert read(_ctx(config, _trace(0.5, 0.25, rounds=2)),
                **entry["args"]) is None
    from fedml_tpu.utils import tracing
    monkeypatch.setattr(tracing, "device_scopes", lambda: {})
    assert read(_ctx(config, _trace(0.5, 0.25, rounds=2)),
                **entry["args"]) is None


def test_no_python_file_of_the_benchmark_knows_the_cell_by_name():
    for kind in ("drivers", "generators", "readers", "kernels",
                 "references", "harness", "tools"):
        folder = os.path.join(ROOT, "benchmark", kind)
        for name in os.listdir(folder):
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                assert CELL not in text and CONFIG not in text, name


def test_the_reference_imports_nothing_of_the_programs_layers():
    with open(os.path.join(ROOT, "benchmark", "references",
                           "deepseek_v3_local_sgd.py")) as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "fedml_tpu.ops" not in code and "fedml_tpu.models" not in code
    assert "fedml_tpu" not in code  # the round loop's two imports are its own
    assert "routed_experts(" not in text and "causal_attention" not in text
