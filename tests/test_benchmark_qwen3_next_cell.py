"""The files the ``qwen3_next_80b_a3b_ep16.silo4`` cell adds to the benchmark,
as far as a CPU can hold them to their word: the manifest entries, the
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
CELL, CONFIG = "qwen3_next_80b_a3b_ep16.silo4", "qwen3_next_80b_a3b_ep16"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
#: the published model configuration (Qwen3-Next-80B-A3B ``config.json``)
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
#: name -> (unit, better, reader, scope) of the four metrics the cell adds
METRICS = {
    "gated_delta_ms": ("ms", "lower", "scope_ops", ["fedml.gated_delta"]),
    "gated_delta_core_ms": ("ms", "lower", "scope_ops",
                            ["fedml.gated_delta_core"]),
    "gated_delta_core_roofline": ("%", "higher", "gated_delta_roofline",
                                  "fedml.gated_delta_core"),
    "gated_attention_ms": ("ms", "lower", "scope_ops",
                           ["fedml.gated_attention"])}


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
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    for text in (cells[CELL]["why"], entry["why"], entry["source"]):
        assert 1 <= len(text) <= 200 and "\t" not in text and "\n" not in text
    why = cells[CELL]["why"]
    assert "linear attention" in why and "full layer" in why
    assert ("2,048" in why) != ("1,536" in why)  # the row length it runs
    # still one four-chip cell
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(json.dumps(manifest)) < 64 * 1024


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_new_per_layer_metrics_list_the_new_cell_alone(manifest, name):
    unit, better, reader, scope = METRICS[name]
    metric = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert metric == {"name": name, "unit": unit, "better": better,
                      "source": "device_trace", "layer": "trainer",
                      "moves": "rounds_per_s", "workloads": [CELL]}
    assert NAME.match(name) and re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}",
                                             unit)
    entry = _load("benchmark", "metrics", name + ".json")
    assert entry["reader"] == reader
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers",
                                       reader + ".py"))
    args = entry["args"]
    assert args["scope"] == scope
    if "kernel" in args:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "kernels", args["kernel"] + ".py"))
    assert args["outside_spans"] == ["bench.evaluate"]
    assert re.search(args["within_modules"], "jit_round_fn(123)")
    assert not re.search(args["within_modules"], "jit_eval(9)")


def test_the_new_entries_come_last_and_the_accepted_lists_are_as_they_were(
        manifest):
    assert [m["name"] for m in manifest["per_layer"]][49:53] == [
        "gated_delta_ms", "gated_delta_core_ms", "gated_delta_core_roofline",
        "gated_attention_ms"]
    assert [m["name"] for m in manifest["per_layer"]][43:49] == [
        "mla_ms", "mla_core_roofline", "shared_expert_ms", "small_expert_ms",
        "small_expert_roofline", "small_expert_load_peak"]
    assert [w["name"] for w in manifest["workloads"]][8:] == [
        "kanana_2_30b_a3b_ep8.silo4", CELL]
    assert [c["name"] for c in manifest["configs"]][5:] == [
        "kanana_2_30b_a3b_ep8", CONFIG]
    lists = {m["name"]: m.get("workloads") for m in manifest["per_layer"]}
    for name in ("agg_kernel_ms", "agg_fold_roofline", "tokens_per_round",
                 "aggregate_scope_ms", "unscoped_ms", "attention_ms",
                 "mlp_ms", "lm_head_ms", "moe_scope_ms", "expert_load_peak",
                 "mla_ms", "shared_expert_ms", "small_expert_ms",
                 "small_expert_load_peak"):
        assert CELL not in lists[name], name
    # every metric without a list reports in the new cell by its definition
    from benchmark.harness import spec
    cell = spec.load_cell(CELL)
    names = [m["name"] for m in cell.per_layer]
    for name in ("mfu", "train_device_ms", "device_idle_share",
                 "peak_hbm_gib", "loss_at_round_16", *METRICS):
        assert name in names
    for name in ("moe_ms", "ssd_ms", "mla_ms", "attention_ms"):
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
    assert config["num_hidden_layers"] == len(kwargs["layer_ids"]) == 4
    assert config["num_experts"] == kwargs["experts_held"][1] == 32
    assert config["vocab_size"] == config["model"]["output_dim"] \
        == config["data"]["vocab"] == 18992
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]  # the floor
    for text in ("initialisation", "A_log", "conv", "l2_norm_eps", "layout",
                 "router_eps", "mtp", "content", "local_steps", "lr"):
        assert text in config["assumed"]
    assert "625,667,136" in config["cut"]["arithmetic"]
    assert "sixteen chips" in config["cut"]["num_experts"]
    assert "vocabulary-parallel" in config["deployment"]
    assert "pipeline stages of four" in config["deployment"]


def test_no_width_is_cut_and_the_floors_hold(config):
    kwargs = config["model"]["kwargs"]
    # the architecture's own arguments and nothing else
    assert set(kwargs) - {"experts_held", "layer_ids"} <= set(CATALOG)
    for key, value in kwargs.items():
        if key in ("experts_held", "layer_ids"):
            continue
        # the router's width is the published count of experts
        assert value == CATALOG[key], key
    for key in ("hidden_size", "head_dim", "linear_key_head_dim",
                "linear_value_head_dim", "moe_intermediate_size",
                "shared_expert_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "linear_num_key_heads",
                "linear_num_value_heads", "linear_conv_kernel_dim",
                "num_experts_per_tok", "partial_rotary_factor"):
        assert key in kwargs, key
    # the floors: a whole period of the 3 : 1 pattern and at least four
    # layers, at least 8 routed experts, an eighth of the vocabulary
    assert kwargs["layer_ids"] == [0, 1, 2, 3]
    interval = kwargs["full_attention_interval"]
    assert [(i + 1) % interval == 0 for i in kwargs["layer_ids"]] == [
        False, False, False, True]
    assert kwargs["experts_held"] == [0, 32]
    assert config["vocab_size"] * 8 >= CATALOG["vocab_size"]


def test_the_files_parameter_count_is_the_programs(config):
    from benchmark.harness import cell as cell_mod
    module = cell_mod.make_model(config)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == config["model"]["parameters"] == 625_667_136
    assert config["model"]["task"] == "lm_rows"
    assert config["model"]["create_model"] == "qwen3_next"


def test_the_check_block_has_a_calibrated_timed_bound_and_no_small(config):
    check = config["check"]
    assert "small" not in check
    assert check["timed"]["param_fraction"] is not None
    assert 0.0 < check["timed"]["param_fraction"] < 0.25
    assert 0.0 < check["timed"]["max_param_change"] <= 0.5
    assert 0.0 < check["loss_rel_tol"] <= 0.1
    assert "seeds 4100000101 to 103" in check["why"]
    for control in ("gate_before_norm", "no_decay", "beta_one",
                    "no_attention_gate", "sigmoid_router", "no_shared_gate",
                    "top_k_less_one", "bf16_fold"):
        assert control in check["why"], control
    assert config["reference"] == "qwen3_next_local_sgd"
    assert "peak_memory_in_bytes" in config["measured"]["compiled_round"]
    assert "64" in config["measured"]["chunk"]


def test_the_traffic_is_silo4_on_rows_of_the_cells_length(config):
    traffic = _load("benchmark", "traffic", "silo4.json")
    data, train = config["data"], config["train"]
    assert (data["generator"], data["clients"]) == ("token_silos", 16)
    assert (data["train_rows"], data["test_rows"]) == (2, 1)
    assert (data["zipf_s"], data["follow_share"]) == (1.1, 0.5)
    assert (train["batch_size"], train["epochs"]) == (1, 1)
    assert train["client_optimizer"] == "sgd"
    assert train["lr"] in (0.3, 0.1, 0.03, 0.01)
    assert data["sequence_length"] in (2048, 1536)
    tokens = (traffic["cohort"] * data["train_rows"] * train["epochs"]
              * data["sequence_length"])
    assert tokens == 8 * data["sequence_length"]
    # a held expert's expected load a step: 10 of 512 a token, 1/16 of the
    # sixteen chips' 640
    kwargs = config["model"]["kwargs"]
    pairs = data["sequence_length"] * kwargs["num_experts_per_tok"]
    if data["sequence_length"] == 2048:
        assert pairs * kwargs["experts_held"][1] / kwargs["num_experts"] \
            == 1280
        assert pairs / kwargs["num_experts"] == 40 == 640 / 16


# -- the cost function, the FLOP count, the reader -----------------------------------

def test_the_delta_rule_cost_by_hand():
    cost = _module("kernels", "gated_delta.py").cost
    # a round of the cell: 16,384 tokens through three linear layers
    flops, nbytes = cost(16384, 16, 32, 128, 128, 3)
    c = 64
    key_head = 2 * c * 128 + 2 * c * 128           # K K', Q K' a token
    value_head = (c * c / 3 + 2 * c * 128 + 2 * c * 128
                  + 3 * 2 * 128 * 128 + 2 * c * 128)
    forward = 16 * key_head + 32 * value_head
    assert forward == pytest.approx(5_286_570.6667)
    assert flops == pytest.approx(3 * forward * 16384 * 3)
    # q, k a key head, v, g, beta in and o out; those and do in; q, k, v,
    # g, beta gradients out
    inputs = 2 * 16 * 128 + 32 * 128 + 2 * 32
    assert inputs == 8256
    floats = (inputs + 4096) + (inputs + 4096) + inputs
    assert nbytes == 4.0 * floats * 16384 * 3 == 6_480_199_680.0
    # 7.9 ms of bytes at 819 GB/s against 4.0 ms of products at the bf16
    # peak: an ideal kernel is bound by the bytes
    assert 0.0079 < nbytes / 819e9 < 0.0080
    assert 0.0039 < flops / 197e12 < 0.0040
    # the chunk is the file's, whatever the program runs
    assert _module("kernels", "gated_delta.py").CHUNK == 64
    # one key head for each value head, one layer
    assert cost(64, 2, 2, 8, 8, 1)[0] == pytest.approx(3 * 64 * (
        2 * (4 * 64 * 8) + 2 * (64 * 64 / 3 + 6 * 64 * 8 + 6 * 8 * 8)))


def test_flops_per_row_bills_the_causal_half_the_held_load_and_the_cost():
    """Against a hand count at a tiny size: every projection, the shared
    expert, its gate and the head once forward and twice backward; the
    routed experts at ``T x top_k x held / experts`` pairs; the router;
    attention at the causal half of its score matrices; the delta rule as
    ``kernels/gated_delta.py`` bills it."""
    from benchmark.harness import flops
    from fedml_tpu.models import create_model
    reference = _module("references", "qwen3_next_local_sgd.py")
    cost = _module("kernels", "gated_delta.py").cost
    d, hq, hkv, dim, kh, vh, dk, dv = 32, 4, 2, 16, 2, 4, 8, 8
    width, shared, experts, top_k, held = 16, 24, 16, 3, 4
    vocab, length = 40, 16
    module = create_model(
        "qwen3_next", output_dim=vocab, hidden_size=d,
        num_attention_heads=hq, num_key_value_heads=hkv, head_dim=dim,
        linear_num_key_heads=kh, linear_num_value_heads=vh,
        linear_key_head_dim=dk, linear_value_head_dim=dv,
        moe_intermediate_size=width, shared_expert_intermediate_size=shared,
        num_experts=experts, num_experts_per_tok=top_k,
        experts_held=(4, held), layer_ids=(0, 1, 2, 3))
    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, length), jnp.int32), train=False))
    got = reference.flops_per_row(
        module, "lm_rows", {"batch_size": 1, "lr": 0.1}, variables,
        np.zeros((1, length), np.int32), flops.count)
    linear = 2 * length * (d * (2 * kh * dk + 2 * vh * dv) + d * 2 * vh
                           + vh * dv * d)
    full = 2 * length * (d * 2 * hq * dim + 2 * d * hkv * dim + hq * dim * d)
    shared_ff = 3 * 2 * length * d * shared + 2 * length * d
    head = 2 * length * d * vocab
    core = length * (length + 1) // 2 * 2 * hq * 2 * dim
    router = 2 * length * d * experts
    routed = (length * top_k * held / experts) * 3 * 2 * d * width
    want = (3 * (3 * linear + full + 4 * shared_ff + head)
            + 3 * (4 * (router + routed) + core)
            + cost(length, kh, vh, dk, dv, 3)[0])
    assert got == pytest.approx(want, rel=1e-12)


def _trace(core_s, other_s, rounds):
    """A traced slice of ``rounds`` rounds: in each, inside ``jit_round_fn``,
    two operations of the delta rule (``core_s`` seconds together), a
    projection of the block around it (``other_s``) and an operation of the
    full-attention block; and during an evaluation the core's operation
    again."""
    names = ["%fusion.1 = f32[2,32,64,64]{3,2,1,0} fusion(f32[64,2048] %k)",
             "%convolution.2 = f32[2,32,64,128] convolution(f32[2,32,64,64] "
             "%t)",
             "%convolution.3 = f32[2048,12288] convolution(f32[2048,2048] %x)",
             "%convolution.4 = f32[2048,8192] convolution(f32[2048,2048] %x)",
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


def _scope_map(core=("fedml.local_train", "fedml.gated_delta",
                     "fedml.gated_delta_core")):
    """What ``fedml_tpu.utils.tracing.device_scopes`` hands out, for the
    four instructions of ``_trace``."""
    return {"jit_round_fn": types.SimpleNamespace(
        chains={"fusion.1": core, "convolution.2": core,
                "convolution.3": ("fedml.local_train", "fedml.gated_delta"),
                "convolution.4": ("fedml.local_train",
                                  "fedml.gated_attention")},
        mixed=set(),
        kinds={"fusion.1": "f32[2,32,64,64] fusion",
               "convolution.2": "f32[2,32,64,128] convolution",
               "convolution.3": "f32[2048,12288] convolution",
               "convolution.4": "f32[2048,8192] convolution"})}


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
    entry = _load("benchmark", "metrics", "gated_delta_core_roofline.json")
    read = _module("readers", "gated_delta_roofline.py").read
    scope_ms = _module("readers", "scope_ops.py").read
    # 0.5 s of the core a round; the block adds the projection's 0.25 s; the
    # full-attention block's second is its own; the evaluation's is no
    # round's
    ctx = _ctx(config, _trace(0.5, 0.25, rounds=2))
    args = {name: _load("benchmark", "metrics", name + ".json")["args"]
            for name in ("gated_delta_ms", "gated_delta_core_ms",
                         "gated_attention_ms")}
    assert scope_ms(ctx, **args["gated_delta_core_ms"]) == pytest.approx(
        500.0)
    assert scope_ms(ctx, **args["gated_delta_ms"]) == pytest.approx(750.0)
    assert scope_ms(ctx, **args["gated_attention_ms"]) == pytest.approx(
        1000.0)
    # 16,384 tokens a round x 3 linear layers of the 4 held: 6.48 GB at
    # 819 GB/s (the 0.78 TFLOP at 197 TFLOP/s are the smaller bound)
    least = 6_480_199_680.0 / 819e9
    assert least > 3 * 5_286_570.6667 * 16384 * 3 / 197e12
    got = read(ctx, **entry["args"])
    assert got == pytest.approx(100.0 * least / 0.5) \
        == pytest.approx(1.5824664)
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
    # one linear layer held of the four: a third of the work
    one = {**config, "model": {**config["model"], "kwargs": {
        **config["model"]["kwargs"], "layer_ids": [2, 3]}}}
    assert read(_ctx(one, _trace(0.5, 0.25, rounds=2)),
                **entry["args"]) == pytest.approx(got / 3)


def test_the_roofline_reader_reads_nothing_where_there_is_nothing(
        config, scope_map, monkeypatch):
    entry = _load("benchmark", "metrics", "gated_delta_core_roofline.json")
    read = _module("readers", "gated_delta_roofline.py").read
    trace = _trace(0.5, 0.25, rounds=2)
    # no trace (an untraced run); a program without the counter; a window
    # without rounds
    assert read(_ctx(config), **entry["args"]) is None
    assert read(_ctx(config, trace, tokens=None), **entry["args"]) is None
    assert read(_ctx(config, _trace(0.5, 0.25, rounds=2), rounds=0),
                **entry["args"]) is None
    # a configuration without the linear-attention keys (every other cell)
    other = _load("benchmark", "configs", "kanana_2_30b_a3b_ep8.json")
    assert read(_ctx(other, _trace(0.5, 0.25, rounds=2)),
                **entry["args"]) is None
    # a cut that holds the full layer alone
    full = {**config, "model": {**config["model"], "kwargs": {
        **config["model"]["kwargs"], "layer_ids": [3]}}}
    assert read(_ctx(full, _trace(0.5, 0.25, rounds=2)),
                **entry["args"]) is None
    # a program that names no such scope (the parent: it has no such
    # model), and one that hands out no map
    scope_map["jit_round_fn"].chains.update({
        "fusion.1": ("fedml.local_train", "fedml.ssd"),
        "convolution.2": ("fedml.local_train", "fedml.ssd")})
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
                           "qwen3_next_local_sgd.py")) as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "fedml_tpu" not in code  # the round loop's two imports are its own
    assert "routed_experts(" not in text and "causal_attention" not in text
    assert "gated_delta_rule" not in text
