"""The files the ``lfm2_8b_a1b_ep4.silo4`` and ``fedcifar100_resnet18gn
.mesh1`` cells add to the benchmark, as far as a CPU can hold them to their
word: the manifest entries, the configuration's cut against the catalog's
numbers and the program's own parameter count, the cost function, the
reference's FLOP count against a hand count, and the readers, which must
return nothing on a program without the new stats."""

import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG = "lfm2_8b_a1b_ep4.silo4", "lfm2_8b_a1b_ep4"
MESH1 = "fedcifar100_resnet18gn.mesh1"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["full_attention" if i in (2, 6, 10, 14, 18, 21)
                    else "conv" for i in range(24)],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}


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

def test_the_cells_and_the_configuration_are_in_the_manifest(manifest):
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL] == {"name": CELL, "config": CONFIG, "traffic": "silo4",
                           "chips": 1, "why": cells[CELL]["why"]}
    assert cells[MESH1] == {
        "name": MESH1, "config": "fedcifar100_resnet18gn",
        "traffic": "mesh1", "chips": 1, "why": cells[MESH1]["why"]}
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/LiquidAI/LFM2-8B-A1B/"
                               "blob/main/config.json")
    for text in (cells[CELL]["why"], cells[MESH1]["why"], entry["why"],
                 entry["source"]):
        assert 1 <= len(text) <= 200 and "\t" not in text and "\n" not in text
    assert "4,096" in cells[CELL]["why"] and "4x" in cells[CELL]["why"]
    # one four-chip cell of seven (eight since PR 35): no second fits under
    # a quarter
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(manifest["workloads"][:7]) == 7
    assert len(json.dumps(manifest)) < 64 * 1024


@pytest.mark.parametrize("name, unit, better, source", [
    ("moe_ms", "ms", "lower", "device_trace"),
    ("moe_expert_roofline", "%", "higher", "device_trace"),
    ("expert_load_peak", "ratio", "lower", "program_counter")])
def test_the_new_per_layer_metrics_list_the_new_cell_alone(
        manifest, name, unit, better, source):
    metric = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert metric == {"name": name, "unit": unit, "better": better,
                      "source": source, "layer": "trainer",
                      "moves": "rounds_per_s", "workloads": [CELL]}
    assert NAME.match(name) and re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}",
                                             unit)
    entry = _load("benchmark", "metrics", name + ".json")
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers",
                                       entry["reader"] + ".py"))
    if name.endswith("_roofline"):
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "kernels", entry["args"]["kernel"] + ".py"))
    if source == "device_trace":
        # only the experts are 1792 wide: the pattern takes a dimension of
        # exactly that, anywhere in a shape
        pattern = re.compile(entry["args"]["pattern"])
        assert pattern.search("f32[8,2048,1792]{2,1,0}")
        assert pattern.search("f32[512,1792]") and pattern.search("[1792,8]")
        assert not pattern.search("f32[4096,17920]")
        assert not pattern.search("f32[11792,64]")
        assert entry["args"]["within_modules"] == "^jit_round_fn\\("
        assert entry["args"]["outside_spans"] == ["bench.evaluate"]
        # the Pallas fold of the w1 / w3 leaves is agg_kernel_ms's
        assert re.search(entry["args"]["exclude"],
                         "%fold = f32[16384,1792] custom-call(...), "
                         "custom_call_target=\"tpu_custom_call\"")


def lists_of(manifest):
    return {m["name"]: m.get("workloads") for m in manifest["per_layer"]}


def test_the_new_entries_come_after_the_accepted_ones(manifest):
    assert [m["name"] for m in manifest["per_layer"]][25:28] == [
        "moe_ms", "moe_expert_roofline", "expert_load_peak"]
    assert [w["name"] for w in manifest["workloads"]][5:7] == [CELL, MESH1]
    assert [c["name"] for c in manifest["configs"]][3:4] == [CONFIG]
    # mesh1's trace has nothing for agg_kernel_ms to read (a psum over one
    # chip is no all-reduce, and the mesh driver has no Pallas mean), so the
    # metric gets a list: every cell but mesh1 - the new cell's fold is
    # aggregation like silo4's
    # (the cell PR 35 added is not on it: ISSUE 35 left the accepted lists
    # to the next ``benchmark`` issue, PERF.md section 7 (12))
    assert lists_of(manifest)["agg_kernel_ms"] == [
        w["name"] for w in manifest["workloads"][:7] if w["name"] != MESH1]
    # the lists the accepted metrics carry are not this PR's to extend
    lists = lists_of(manifest)
    assert lists["tokens_per_round"] == lists["agg_fold_roofline"] == [
        "phi4_mini_flash_6l.silo4"]


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
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    kwargs = config["model"]["kwargs"]
    assert config["num_hidden_layers"] == len(kwargs["layer_ids"])
    assert config["num_experts"] == kwargs["experts_held"][1] == 8
    assert config["vocab_size"] == config["model"]["output_dim"] \
        == config["data"]["vocab"] == 16384
    for text in ("head_dim", "tied_embedding_and_qk_norm", "expert_bias",
                 "initialisation", "content", "local_steps", "lr"):
        assert text in config["assumed"]
    # the architecture's own arguments and nothing else: no knob of the cell's
    assert set(kwargs) == {
        "hidden_size", "num_heads", "num_kv_heads", "intermediate_size",
        "moe_intermediate_size", "num_experts", "num_experts_per_tok",
        "experts_held", "layer_ids", "layer_types", "num_dense_layers",
        "conv_L_cache", "rope_theta", "norm_eps", "norm_topk_prob",
        "routed_scaling_factor", "use_expert_bias"}
    assert "expert-parallel" in config["deployment"]
    assert "vocabulary-parallel" in config["deployment"]
    assert "four chips share every layer" in config["deployment"]


def test_no_width_is_cut_and_the_floors_hold(config):
    kwargs = config["model"]["kwargs"]
    assert kwargs["hidden_size"] == CATALOG["hidden_size"] == 2048
    assert kwargs["num_heads"] == CATALOG["num_attention_heads"] == 32
    assert kwargs["num_kv_heads"] == CATALOG["num_key_value_heads"] == 8
    assert kwargs["intermediate_size"] == CATALOG["intermediate_size"]
    assert kwargs["moe_intermediate_size"] == 1792
    # the router's width and the experts a token are the published ones
    assert kwargs["num_experts"] == CATALOG["num_experts"] == 32
    assert kwargs["num_experts_per_tok"] == 4
    assert kwargs["conv_L_cache"] == 3
    assert kwargs["layer_types"] == CATALOG["layer_types"]
    assert kwargs["num_dense_layers"] == CATALOG["num_dense_layers"]
    assert kwargs["rope_theta"] == CATALOG["rope_theta"]
    assert kwargs["norm_eps"] == CATALOG["norm_eps"]
    assert kwargs["norm_topk_prob"] and kwargs["use_expert_bias"]
    assert kwargs["routed_scaling_factor"] == 1
    # the floors: a whole period, at least four sparse layers after the
    # dense one, 8 experts, an eighth of the vocabulary; never below 1-5
    ids = kwargs["layer_ids"]
    assert ids == list(range(1, 1 + len(ids))) and len(ids) >= 5
    kinds = [CATALOG["layer_types"][i] for i in ids]
    assert kinds[1:5] == ["full_attention", "conv", "conv", "conv"]
    assert sum(i >= CATALOG["num_dense_layers"] for i in ids) >= 4
    assert kwargs["experts_held"] == [0, 8]
    assert config["vocab_size"] * 8 >= CATALOG["vocab_size"]


def test_the_files_parameter_count_is_the_programs(config):
    from benchmark.harness import cell as cell_mod
    module = cell_mod.make_model(config)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == config["model"]["parameters"]
    assert config["model"]["task"] == "lm_rows"
    assert config["model"]["create_model"] == "lfm2_moe"


def test_the_check_block_has_a_calibrated_timed_bound_and_no_small(config):
    check = config["check"]
    assert "small" not in check
    assert check["timed"]["param_fraction"] is not None
    assert 0.0 < check["timed"]["param_fraction"] < 0.25
    assert 0.0 < check["timed"]["max_param_change"] <= 0.5
    assert 0.0 < check["loss_rel_tol"] <= 0.1
    assert "my chip run" in check["why"]
    assert config["reference"] == "lfm2_moe_local_sgd"


def test_the_traffic_is_the_issues(config):
    traffic = _load("benchmark", "traffic", "silo4.json")
    data, train = config["data"], config["train"]
    assert (data["generator"], data["clients"]) == ("token_silos", 16)
    assert (data["train_rows"], data["test_rows"]) == (2, 1)
    assert (data["zipf_s"], data["follow_share"]) == (1.1, 0.5)
    assert (train["batch_size"], train["epochs"]) == (1, 1)
    assert train["client_optimizer"] == "sgd"
    tokens = (traffic["cohort"] * data["train_rows"] * train["epochs"]
              * data["sequence_length"])
    assert data["sequence_length"] == 4096 and tokens == 32768
    # balanced, a held expert sees 512 tokens a step
    kwargs = config["model"]["kwargs"]
    assert (data["sequence_length"] * kwargs["num_experts_per_tok"]
            // kwargs["num_experts"]) == 512


def test_mesh1_is_the_dense_cohort_through_the_mesh_driver():
    mesh1 = _load("benchmark", "traffic", "mesh1.json")
    dense = _load("benchmark", "traffic", "dense.json")
    assert {k: mesh1[k] for k in ("driver", "clients", "cohort",
                                  "eval_every", "round_bound")} == {
        "driver": "spmd", "clients": "reference", "cohort": 104,
        "eval_every": 5, "round_bound": 1024}
    assert all(mesh1[k] == dense[k] for k in (
        "clients", "cohort", "eval_every", "round_bound"))
    from benchmark.harness import spec
    cell = spec.load_cell(MESH1)
    assert cell.chips == 1 and cell.clients == 500
    assert [m["name"] for m in cell.end_to_end] == ["rounds_per_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert "moe_ms" not in names and "train_device_ms" in names
    assert "agg_kernel_ms" not in names


# -- the cost function, the FLOP count, the readers -----------------------------------

def test_expert_cost_by_hand():
    cost = _module("kernels", "moe_experts.py").cost
    # a round of the cell, balanced: 6 sparse layers x 8 steps x 4,096 pairs
    pairs, passes = 6 * 8 * 4096, 6 * 8
    flops, nbytes = cost(pairs, 8, 2048, 1792, passes)
    assert flops == 18.0 * pairs * 2048 * 1792 == 12_987_981_103_104.0
    weights = 8 * 3 * 2048 * 1792 * 4   # the held experts' matrices, bytes
    assert weights == 352_321_536
    assert nbytes == 3 * weights * passes + 5 * pairs * 2048 * 4
    # 65.9 ms of products at the bf16 peak against 71.8 ms of bytes at 819
    # GB/s: at 512 tokens an expert the weights' traffic bounds it
    assert 0.065 < flops / 197e12 < 0.067
    assert 0.071 < nbytes / 819e9 < 0.073


def test_flops_per_row_counts_the_experts_at_the_balanced_load():
    """Against a hand count at a tiny size: every product once forward and
    twice backward; attention as whole [T, T] matrices (the reference's);
    the experts at T x top_k x held / num_experts pairs, not the 4 x T rows
    of the masked-dense loop."""
    from benchmark.harness import flops
    from fedml_tpu.models import create_model
    reference = _module("references", "lfm2_moe_local_sgd.py")
    d, heads, kv, inter, width, vocab, length = 32, 4, 2, 48, 24, 40, 16
    module = create_model(
        "lfm2_moe", output_dim=vocab, hidden_size=d, num_heads=heads,
        num_kv_heads=kv, intermediate_size=inter,
        moe_intermediate_size=width, num_experts=8, num_experts_per_tok=2,
        experts_held=(0, 4), layer_ids=(1, 2, 3))
    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, length), jnp.int32), train=False))
    got = reference.flops_per_row(
        module, "lm_rows", {"batch_size": 1, "lr": 0.1}, variables,
        np.zeros((1, length), np.int32), flops.count)
    dim = d // heads
    conv = 2 * length * d * 3 * d + 2 * length * d * d
    attention = (2 * 2 * length * d * d + 2 * 2 * length * d * kv * dim
                 + heads * 2 * 2 * length * length * dim)
    dense = 3 * 2 * length * d * inter
    pairs = length * 2 * 4 / 8
    sparse = pairs * 3 * 2 * d * width + length * 2 * d * 8
    head = 2 * length * d * vocab
    want = 3 * ((conv + dense) + (attention + sparse) + (conv + sparse)
                + head)
    assert got == want
    # the masked-dense loop would have billed 4 experts on all 16 tokens
    assert 4 * length * 3 * 2 * d * width == 4 * pairs * 3 * 2 * d * width


def _ctx(stats, kwargs=None, trace=None, rounds=None):
    window = types.SimpleNamespace(
        stats=stats, rounds=len(stats) if rounds is None else rounds)
    config = {"model": {"kwargs": kwargs or {}},
              "train": {"batch_size": 1, "epochs": 1}}
    return types.SimpleNamespace(
        window=window, trace=trace, trace_rounds=0, trace_window=None,
        cell=types.SimpleNamespace(config=config))


def test_expert_load_peak_is_held_times_top_over_assignments():
    read = _module("readers", "stat_ratio.py").read
    args = dict(numerator="moe_top_expert_assignments",
                denominator="moe_assignments", scale_by="experts_held")
    stats = [{"loss_sum": 1.0, "moe_assignments": 4000.0,
              "moe_top_expert_assignments": 900.0},
             {"loss_sum": 1.0, "moe_assignments": 4192.0,
              "moe_top_expert_assignments": 636.0}]
    assert read(_ctx(stats, {"experts_held": [0, 8]}), **args) \
        == 8 * 1536.0 / 8192.0 == 1.5
    balanced = [{"moe_assignments": 8.0 * 512,
                 "moe_top_expert_assignments": 512.0}]
    assert read(_ctx(balanced, {"experts_held": [0, 8]}), **args) == 1.0
    # a program whose rounds carry no routing stats (the parent, every
    # other cell): nothing, and no error
    assert read(_ctx([{"loss_sum": 1.0, "count": 8.0}],
                     {"experts_held": [0, 8]}), **args) is None
    assert read(_ctx([], {}), **args) is None
    assert read(_ctx(stats, {}), **args) is None


@pytest.mark.parametrize("stats, fill", [
    # qwen3's shape: 1,280 held pairs a layer pass in 35 blocks of 64
    ([{"moe_assignments": 1280.0, "moe_block_rows": 35 * 64.0},
      {"moe_assignments": 1260.0, "moe_block_rows": 34 * 64.0}],
     2540.0 / (69 * 64.0)),
    # every block full
    ([{"moe_assignments": 512.0, "moe_block_rows": 512.0}], 1.0),
    # the parent's rounds carry no block rows: nothing, and no error
    ([{"loss_sum": 1.0, "moe_assignments": 1280.0}], None),
    ([], None)])
def test_moe_block_fill_is_assignments_over_the_rows_the_blocks_ran(stats,
                                                                    fill):
    entry = _load("benchmark", "metrics", "moe_block_fill.json")
    assert entry["reader"] == "stat_ratio"
    assert entry["args"] == {"numerator": "moe_assignments",
                             "denominator": "moe_block_rows"}
    read = _module("readers", "stat_ratio.py").read
    assert read(_ctx(stats, {"experts_held": [0, 32]}),
                **entry["args"]) == fill
    manifest = _load("BENCHMARK.json")
    metric = {m["name"]: m for m in manifest["per_layer"]}["moe_block_fill"]
    assert metric == {
        "name": "moe_block_fill", "unit": "ratio", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "rounds_per_s", "workloads": [
            "qwen3_next_80b_a3b_ep16.silo4", "kanana_2_30b_a3b_ep8.silo4",
            CELL]}
    assert manifest["per_layer"][-1] == metric


def test_the_roofline_reader_reads_nothing_without_a_trace_or_the_stats():
    read = _module("readers", "moe_roofline.py").read
    assert read(_ctx([{"moe_assignments": 1.0}], {"experts_held": [0, 8]}),
                "moe_experts", "x") is None
    ctx = _ctx([{"loss_sum": 1.0}], {"hidden_size": 8}, trace={}, rounds=1)
    ctx.trace_rounds = 1
    assert read(ctx, "moe_experts", "x") is None


def test_a_control_that_holds_less_is_compared_on_the_whole_model():
    """``tools/timed_check_controls.py``: a share without its last held
    expert hands back smaller leaves; the part it lacks counts as left at
    its initial value, and the reading over what both hold cuts the
    reference to the smaller shapes."""
    tool = _module("tools", "timed_check_controls.py")
    init = {"experts_w1": np.zeros((8, 2, 3), np.float32),
            "router": np.zeros((2, 32), np.float32)}
    got = {"experts_w1": np.ones((7, 2, 3), np.float32),
           "router": np.ones((2, 32), np.float32)}
    whole = tool._left_as_initialised(init, got)
    assert whole["experts_w1"].shape == (8, 2, 3)
    assert whole["experts_w1"][:7].all() and not whole["experts_w1"][7].any()
    assert whole["router"].all() and not init["experts_w1"].any()
    want = {"experts_w1": np.full((8, 2, 3), 2.0, np.float32),
            "router": np.full((2, 32), 2.0, np.float32)}
    shared = tool._held_by_both(want, got)
    assert shared["experts_w1"].shape == (7, 2, 3)
    assert shared["router"].shape == (2, 32)


def test_no_python_file_of_the_benchmark_knows_the_cells_by_name():
    for kind in ("drivers", "generators", "readers", "kernels",
                 "references", "harness"):
        folder = os.path.join(ROOT, "benchmark", kind)
        for name in os.listdir(folder):
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                for word in (CELL, CONFIG, MESH1, "mesh1"):
                    assert word not in text, (name, word)
