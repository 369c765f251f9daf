"""The files the ``phi4_mini_flash_6l.silo4`` cell adds to the benchmark, as
far as a CPU can hold them to their word: the manifest entries, the
configuration's cut against the catalog's numbers and the program's own
parameter count, the token generator, the cost functions and the readers
that must return nothing on a program without the new counters."""

import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG = "phi4_mini_flash_6l.silo4", "phi4_mini_flash_6l"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "max_position_embeddings": 262144, "mb_per_layer": 2,
           "model_type": "phi4flash", "num_attention_heads": 40,
           "num_hidden_layers": 32, "num_key_value_heads": 20,
           "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False,
           "lm_head_bias": False, "vocab_size": 200064}


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

def test_the_cell_and_its_configuration_are_in_the_manifest(manifest):
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "silo4",
                    "chips": 1, "why": cell["why"]}
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["source"].startswith("https://huggingface.co/microsoft/"
                                      "Phi-4-mini-flash-reasoning/")
    for text in (cell["why"], entry["why"], entry["source"]):
        assert 1 <= len(text) <= 200 and "\t" not in text and "\n" not in text


@pytest.mark.parametrize("name, layer", [
    ("tokens_per_round", "driver"), ("ssm_scan_ms", "trainer"),
    ("ssm_scan_roofline", "trainer"), ("agg_fold_roofline", "aggregation")])
def test_the_new_per_layer_metrics_list_the_new_cell_alone(manifest, name,
                                                            layer):
    metric = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert metric["workloads"] == [CELL]
    assert metric["moves"] == "rounds_per_s" and metric["layer"] == layer
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert NAME.match(name) and re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}",
                                             metric["unit"])
    entry = _load("benchmark", "metrics", name + ".json")
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers",
                                       entry["reader"] + ".py"))
    if name.endswith("_roofline"):
        assert metric["unit"] == "%"
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "kernels", entry["args"]["kernel"] + ".py"))


#: the manifest as PR 29 left it, by name and position: later PRs add behind
ACCEPTED_CELLS = ["fedcifar100_resnet18gn.dense",
                  "fedcifar100_resnet18gn.mesh4", "femnist_cnn.powerlaw",
                  "femnist_cnn.resident", CELL]
ACCEPTED_CONFIGS = ["femnist_cnn", "fedcifar100_resnet18gn", CONFIG]
ACCEPTED_PER_LAYER = [
    "dispatch_ms", "recompiles", "pack_ms", "prefetch_wait_ms",
    "padded_row_share", "train_device_ms", "mfu", "loss_at_round_16",
    "agg_kernel_ms", "agg_kernel_roofline", "allreduce_exposed_share",
    "device_idle_share", "peak_hbm_gib", "host_rss_gib", "starved_ms",
    "starved_max_ms", "produce_ms", "idle_prefetch_wait_ms",
    "idle_dispatch_ms", "idle_round_other_ms", "dispatched_padding_share",
    "tokens_per_round", "ssm_scan_ms", "ssm_scan_roofline",
    "agg_fold_roofline"]


def test_the_accepted_entries_are_still_first_and_unchanged(manifest):
    """The accepted prefix, so that an addition behind it needs no edit
    here."""
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells[:len(ACCEPTED_CELLS)] == ACCEPTED_CELLS
    configs = [c["name"] for c in manifest["configs"]]
    assert configs[:len(ACCEPTED_CONFIGS)] == ACCEPTED_CONFIGS
    assert manifest["run_seconds"] == 30
    per_layer = manifest["per_layer"][:len(ACCEPTED_PER_LAYER)]
    assert [m["name"] for m in per_layer] == ACCEPTED_PER_LAYER
    accepted = {m["name"]: m for m in per_layer}
    assert CELL not in accepted["agg_kernel_roofline"]["workloads"]


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
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["num_hidden_layers"] == len(
        config["model"]["kwargs"]["layer_ids"]) == 6
    assert config["vocab_size"] == config["model"]["output_dim"] \
        == config["data"]["vocab"]
    assert config["vocab_size"] * 4 == CATALOG["vocab_size"]
    assert config["vocab_size"] * 8 >= CATALOG["vocab_size"]  # the floor
    for text in ("mamba", "attention", "local_steps", "lr"):
        assert text in config["assumed"]
    assert "vocabulary-parallel" in config["deployment"]


def test_no_width_is_cut(config):
    kwargs = config["model"]["kwargs"]
    assert kwargs["hidden_size"] == CATALOG["hidden_size"]
    assert kwargs["num_heads"] == CATALOG["num_attention_heads"]
    assert kwargs["num_kv_heads"] == CATALOG["num_key_value_heads"]
    assert kwargs["intermediate_size"] == CATALOG["intermediate_size"]
    assert kwargs["sliding_window"] == CATALOG["sliding_window"]
    assert kwargs["published_layers"] == CATALOG["num_hidden_layers"]
    assert kwargs["layer_ids"] == [0, 1, 16, 17, 18, 19]
    assert kwargs["layer_norm_eps"] == CATALOG["layer_norm_eps"]


def test_the_files_parameter_count_is_the_programs(config):
    from benchmark.harness import cell as cell_mod
    module = cell_mod.make_model(config)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == config["model"]["parameters"] == 761_114_752
    assert config["model"]["task"] == "lm_rows"


def test_the_check_block_has_a_calibrated_timed_bound_and_no_small(config):
    check = config["check"]
    assert "small" not in check
    assert check["timed"]["param_fraction"] is not None
    assert 0.0 < check["timed"]["param_fraction"] < 0.25
    assert 0.0 < check["loss_rel_tol"] <= 0.1
    assert "my chip run" in check["why"]
    assert config["reference"] == "hybrid_lm_local_sgd"


def test_the_traffic_is_the_issues(config):
    traffic = _load("benchmark", "traffic", "silo4.json")
    assert {k: traffic[k] for k in ("driver", "clients", "cohort",
                                    "eval_every", "round_bound")} == {
        "driver": "silo", "clients": 16, "cohort": 4, "eval_every": 5,
        "round_bound": 1024}
    data, train = config["data"], config["train"]
    assert (data["train_rows"], data["test_rows"]) == (2, 1)
    assert (train["batch_size"], train["epochs"]) == (1, 1)
    tokens = (traffic["cohort"] * data["train_rows"] * train["epochs"]
              * data["sequence_length"])
    assert tokens == 16384


# -- the generator ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_data():
    return {"sequence_length": 48, "vocab": 1000, "train_rows": 2,
            "test_rows": 1, "zipf_s": 1.1, "follow_share": 0.5}


def test_token_silos_rows_are_shifted_packed_sequences(small_data):
    build = _module("generators", "token_silos.py").build
    dataset, n_train = build(small_data, 5, 2 ** 31 + 12345)
    assert dataset.client_num == 5 and n_train.tolist() == [2] * 5
    x, y = dataset.train_data_global
    assert x.shape == y.shape == (10, 48) and x.dtype == np.int32
    assert dataset.test_data_global[0].shape == (5, 48)
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    assert 0 <= x.min() and max(x.max(), y.max()) < 1000
    follows = np.mean((x.astype(np.int64) * 48271 + 11) % 1000 == y)
    assert 0.4 < follows < 0.65
    assert dataset.cohort_padded_len([0, 3], 1) == 2
    assert dataset.class_num == 1000


def test_token_silos_shapes_do_not_depend_on_the_seed(small_data):
    build = _module("generators", "token_silos.py").build
    a, _ = build(small_data, 4, 7)
    b, _ = build(small_data, 4, 8)
    again, _ = build(small_data, 4, 7)
    assert a.train_data_global[0].shape == b.train_data_global[0].shape
    assert not np.array_equal(a.train_data_global[0],
                              b.train_data_global[0])
    np.testing.assert_array_equal(a.train_data_global[0],
                                  again.train_data_global[0])
    # silos draw through permutations of their own: their commonest ids
    # differ
    tops = {np.bincount(a.train_data_local_dict[c][0].ravel(),
                        minlength=1000).argmax() for c in range(4)}
    assert len(tops) > 1


# -- the cost functions and the readers -------------------------------------------------

def test_fold_cost_is_three_passes_a_client():
    flops, nbytes = _module("kernels", "wfold.py").cost(4, 761_114_752)
    assert flops == 2.0 * 4 * 761_114_752
    assert nbytes == 12.0 * 4 * 761_114_752  # 36.5 GB: 44.6 ms at 819 GB/s


def test_scan_cost_counts_the_per_token_tensors_only():
    flops, nbytes = _module("kernels", "selective_scan.py").cost(
        16384, 5120, 16, 2)
    per_token = 4 * ((3 * 5120 + 2 * 16) + (5 * 5120 + 4 * 16))
    assert nbytes == per_token * 16384 * 2 == 5_381_292_032
    # the state itself (5120 x 16 floats a token) is not among the bytes
    assert nbytes < 4 * 5120 * 16 * 16384 * 2
    assert flops / 197e12 < nbytes / 819e9  # bytes bound it


def _ctx(counters, rounds=21):
    window = types.SimpleNamespace(counters=counters, rounds=rounds)
    return types.SimpleNamespace(window=window, trace=None, trace_rounds=0)


def test_tokens_per_round_divides_the_counter_by_the_rounds():
    read = _module("readers", "counter_per_round.py").read
    assert read(_ctx({"tokens_dispatched": 21 * 16384}),
                "tokens_dispatched") == 16384.0
    # a program without the counter (the parent): nothing, and no error
    assert read(_ctx({"rows_dispatched": 168}), "tokens_dispatched") is None
    assert read(_ctx({}, rounds=0), "tokens_dispatched") is None


def test_scan_roofline_reads_nothing_without_a_trace():
    read = _module("readers", "scan_roofline.py").read
    assert read(_ctx({}), "selective_scan", "x") is None


def test_no_python_file_of_the_benchmark_knows_the_cell_by_name():
    for kind in ("drivers", "generators", "readers", "kernels",
                 "references", "harness"):
        folder = os.path.join(ROOT, "benchmark", kind)
        for name in os.listdir(folder):
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                assert CELL not in text and CONFIG not in text, name
