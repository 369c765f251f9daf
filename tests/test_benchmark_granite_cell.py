"""The files the ``granite_4_0_h_micro_10l.silo4`` cell adds to the
benchmark, as far as a CPU can hold them to their word: the manifest
entries, the configuration's cut against the catalog's numbers and the
program's own parameter count, the cost function and the reference's FLOP
count against hand counts, and the roofline reader on a small trace, which
must return nothing on a program or a cell without what it reads."""

import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG = "granite_4_0_h_micro_10l.silo4", "granite_4_0_h_micro_10l"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": ["attention" if i % 10 == 5 else "mamba"
                    for i in range(40)],
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


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
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/ibm-granite/"
                               "granite-4.0-h-micro/blob/main/config.json")
    for text in (cells[CELL]["why"], entry["why"], entry["source"]):
        assert 1 <= len(text) <= 200 and "\t" not in text and "\n" not in text
    assert "2,048" in cells[CELL]["why"] and "Mamba-2" in cells[CELL]["why"]
    # still one four-chip cell
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(json.dumps(manifest)) < 64 * 1024


@pytest.mark.parametrize("name, unit, better", [
    ("ssd_ms", "ms", "lower"), ("ssd_roofline", "%", "higher")])
def test_the_new_per_layer_metrics_list_the_new_cell_alone(manifest, name,
                                                            unit, better):
    metric = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert metric == {"name": name, "unit": unit, "better": better,
                      "source": "device_trace", "layer": "trainer",
                      "moves": "rounds_per_s", "workloads": [CELL]}
    assert NAME.match(name) and re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}",
                                             unit)
    entry = _load("benchmark", "metrics", name + ".json")
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers",
                                       entry["reader"] + ".py"))
    if name.endswith("_roofline"):
        assert entry["reader"] == "ssd_roofline"
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "kernels", entry["args"]["kernel"] + ".py"))
    else:
        assert entry["reader"] == "trace_ops"  # the accepted reader
    args = entry["args"]
    # the two metrics time the same operations
    assert args["pattern"] == _load("benchmark", "metrics",
                                    "ssd_ms.json")["args"]["pattern"]
    pattern = re.compile(args["pattern"])
    # a chunk's decay matrices, the C B' block, the chunk states, a chunk's
    # inputs and outputs (as heads x channels and flat), its running sums
    for text in ("f32[64,256,256]{2,1,0:T(8,128)}", "pred[256,256]",
                 "bf16[1,256,256]", "f32[64,64,128]", "f32[8,1,64,64,128]",
                 "bf16[1,64,64,128]{3,2,1,0}", "f32[256,64,64]",
                 "f32[8,1,256,4096]{3,2,1,0}", "bf16[1,256,4096]",
                 "f32[1,256,64]", "f32[64,256]"):
        assert pattern.search(text), text
    # not the projections, the convolution, the gate (2,048 positions),
    # attention's blocks, the feed forward or the head
    for text in ("f32[2048,8512]", "f32[1,2048,4352]", "f32[2048,4096]",
                 "f32[1,2048,64,64]", "f32[2048,64]", "f32[8,4,512,2048]",
                 "f32[8,4,512,64]", "f32[2048,16384]", "f32[12544,2048]",
                 "f32[4096,2048]", "f32[1256,256]", "f32[164,64,128]",
                 "f32[64,64,1280]", "f32[1256,4096]", "f32[256,640]"):
        assert not pattern.search(text), text
    assert args["within_modules"] == "^jit_round_fn\\("
    assert args["outside_spans"] == ["bench.evaluate"]
    assert re.search(args["exclude"],
                     "%fold = f32[64,64,128] custom-call(...), "
                     "custom_call_target=\"tpu_custom_call\"")


def test_the_new_entries_come_last_and_the_accepted_lists_are_as_they_were(
        manifest):
    assert [m["name"] for m in manifest["per_layer"]][28:30] == [
        "ssd_ms", "ssd_roofline"]
    assert [w["name"] for w in manifest["workloads"]][7:8] == [CELL]
    assert [c["name"] for c in manifest["configs"]][4:5] == [CONFIG]
    lists = {m["name"]: m.get("workloads") for m in manifest["per_layer"]}
    # ISSUE 35: the new cell belongs in these three; appending it is the
    # next ``benchmark`` issue's (PERF.md section 7 (12))
    for name in ("agg_kernel_ms", "agg_fold_roofline", "tokens_per_round"):
        assert CELL not in lists[name]
    # every metric without a list reports in the new cell by its definition
    from benchmark.harness import spec
    cell = spec.load_cell(CELL)
    names = [m["name"] for m in cell.per_layer]
    for name in ("mfu", "train_device_ms", "device_idle_share",
                 "peak_hbm_gib", "loss_at_round_16", "ssd_ms",
                 "ssd_roofline"):
        assert name in names
    for name in ("moe_ms", "ssm_scan_ms", "agg_kernel_ms"):
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
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    kwargs = config["model"]["kwargs"]
    assert config["num_hidden_layers"] == len(kwargs["layer_ids"]) == 10
    assert config["vocab_size"] == config["model"]["output_dim"] \
        == config["data"]["vocab"] == 12544
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]  # the floor
    for text in ("head_dim", "mamba2_layout", "tied_embedding",
                 "initialisation", "content", "local_steps", "lr"):
        assert text in config["assumed"]
    assert "772,160,448" in config["cut"]["arithmetic"]
    assert "vocabulary-parallel" in config["deployment"]
    assert "four pipeline stages of ten" in config["deployment"]


def test_no_width_is_cut_and_the_floors_hold(config):
    kwargs = config["model"]["kwargs"]
    # the architecture's own arguments and nothing else: no knob of the cell's
    assert set(kwargs) == {
        "hidden_size", "num_heads", "num_kv_heads",
        "shared_intermediate_size", "layer_ids", "layer_types",
        "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
        "mamba_d_conv", "mamba_chunk_size", "embedding_multiplier",
        "residual_multiplier", "attention_multiplier", "logits_scaling",
        "rms_norm_eps"}
    assert kwargs["hidden_size"] == CATALOG["hidden_size"] == 2048
    assert kwargs["num_heads"] == CATALOG["num_attention_heads"] == 32
    assert kwargs["num_kv_heads"] == CATALOG["num_key_value_heads"] == 8
    assert kwargs["shared_intermediate_size"] \
        == CATALOG["shared_intermediate_size"] == 8192
    for ours, theirs in (("mamba_n_heads", 64), ("mamba_d_head", 64),
                         ("mamba_d_state", 128), ("mamba_n_groups", 1),
                         ("mamba_d_conv", 4), ("mamba_chunk_size", 256)):
        assert kwargs[ours] == CATALOG[ours] == theirs
    assert kwargs["mamba_n_heads"] * kwargs["mamba_d_head"] \
        == CATALOG["mamba_expand"] * CATALOG["hidden_size"]
    for name in ("embedding_multiplier", "residual_multiplier",
                 "attention_multiplier", "logits_scaling"):
        assert kwargs[name] == CATALOG[name]
    assert kwargs["rms_norm_eps"] == CATALOG["rms_norm_eps"]
    assert kwargs["layer_types"] == CATALOG["layer_types"]
    # the floors: a whole period of ten with its one attention layer, an
    # eighth of the vocabulary
    ids = kwargs["layer_ids"]
    assert ids == list(range(10))
    kinds = [CATALOG["layer_types"][i] for i in ids]
    assert kinds == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert config["vocab_size"] * 8 >= CATALOG["vocab_size"]


def test_the_files_parameter_count_is_the_programs(config):
    from benchmark.harness import cell as cell_mod
    module = cell_mod.make_model(config)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == config["model"]["parameters"] == 772_160_448
    assert config["model"]["task"] == "lm_rows"
    assert config["model"]["create_model"] == "granite_hybrid"


def test_the_check_block_has_a_calibrated_timed_bound_and_no_small(config):
    check = config["check"]
    assert "small" not in check
    assert check["timed"]["param_fraction"] is not None
    assert 0.0 < check["timed"]["param_fraction"] < 0.25
    assert 0.0 < check["timed"]["max_param_change"] <= 0.5
    assert 0.0 < check["loss_rel_tol"] <= 0.1
    assert "my chip run" in check["why"]
    assert config["reference"] == "granite_hybrid_local_sgd"


def test_the_traffic_is_the_issues(config):
    traffic = _load("benchmark", "traffic", "silo4.json")
    data, train = config["data"], config["train"]
    assert (data["generator"], data["clients"]) == ("token_silos", 16)
    assert (data["train_rows"], data["test_rows"]) == (2, 1)
    assert (data["zipf_s"], data["follow_share"]) == (1.1, 0.5)
    assert (train["batch_size"], train["epochs"]) == (1, 1)
    assert train["client_optimizer"] == "sgd"
    assert train["lr"] in (0.3, 0.1, 0.03, 0.01)
    tokens = (traffic["cohort"] * data["train_rows"] * train["epochs"]
              * data["sequence_length"])
    # 2,048-token rows, or the 1,536 ISSUE 35 allows if a round is too slow
    assert data["sequence_length"] in (2048, 1536)
    assert tokens == 8 * data["sequence_length"]
    assert data["sequence_length"] % config["mamba_chunk_size"] == 0
    # nine layers in ten are the new mixer
    kwargs = config["model"]["kwargs"]
    mamba = sum(kwargs["layer_types"][i] == "mamba"
                for i in kwargs["layer_ids"])
    assert mamba == 9 and tokens * mamba == 72 * data["sequence_length"]


# -- the cost function, the FLOP count, the reader -----------------------------------

def test_the_ssd_cost_by_hand():
    cost = _module("kernels", "ssd.py").cost
    # a round of the cell: 16,384 tokens through nine Mamba-2 layers
    flops, nbytes = cost(16384, 64, 64, 128, 1, 256, 9)
    token_layer = 2 * 256 * 128 + 2 * 256 * 64 * 64 + 4 * 128 * 64 * 64
    assert token_layer == 65_536 + 2_097_152 + 2_097_152 == 4_259_840
    assert flops == 3.0 * token_layer * 16384 * 9 == 1_884_416_901_120.0
    # forward xs, dt, B, C in and y out; backward those and dy in, four
    # gradients out: (5 x 4096 + 3 x 64 + 6 x 128) floats a token and layer
    floats = 5 * 4096 + 3 * 64 + 6 * 128
    assert floats == 21_440
    assert nbytes == 4.0 * floats * 16384 * 9 == 12_645_826_560.0
    # 9.6 ms of products at the bf16 peak against 15.4 ms of bytes at 819
    # GB/s: an ideal kernel is bound by what it moves
    assert 0.0095 < flops / 197e12 < 0.0097
    assert 0.0154 < nbytes / 819e9 < 0.0155
    # two groups share B and C between 32 heads each; a smaller chunk has
    # smaller score blocks
    more, _ = cost(16384, 64, 64, 128, 2, 256, 9)
    assert more - flops == 3.0 * 2 * 256 * 128 * 16384 * 9
    less, same = cost(16384, 64, 64, 128, 1, 128, 9)
    assert less < flops and same == nbytes


def test_flops_per_row_bills_the_dual_forms_products():
    """Against a hand count at a tiny size: every product once forward and
    twice backward; attention as whole [T, T] matrices (the reference's);
    the recurrence, which the reference runs step by step and which traces
    to nothing, at the dual form's products."""
    from benchmark.harness import flops
    from fedml_tpu.models import create_model
    reference = _module("references", "granite_hybrid_local_sgd.py")
    d, heads, kv, inter, vocab, length = 32, 4, 2, 48, 40, 16
    mh, mp, n, g, q = 4, 16, 8, 2, 8
    module = create_model(
        "granite_hybrid", output_dim=vocab, hidden_size=d, num_heads=heads,
        num_kv_heads=kv, shared_intermediate_size=inter, mamba_n_heads=mh,
        mamba_d_head=mp, mamba_d_state=n, mamba_n_groups=g,
        mamba_chunk_size=q, layer_ids=(4, 5, 6))
    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, length), jnp.int32), train=False))
    got = reference.flops_per_row(
        module, "lm_rows", {"batch_size": 1, "lr": 0.1}, variables,
        np.zeros((1, length), np.int32), flops.count)
    dim, inner = d // heads, mh * mp
    mamba = (2 * length * d * (2 * inner + 2 * g * n + mh)
             + 2 * length * inner * d)
    ssd = length * (2 * q * n * g + 2 * q * mp * mh + 4 * n * mp * mh)
    attention = (2 * 2 * length * d * d + 2 * 2 * length * d * kv * dim
                 + heads * 2 * 2 * length * length * dim)
    feed_forward = 3 * 2 * length * d * inter
    head = 2 * length * d * vocab
    want = 3 * (2 * (mamba + ssd) + attention + 3 * feed_forward + head)
    assert got == want
    assert reference.ssd_flops_per_token(
        reference.hyperparameters(module)) == ssd / length


def _trace(ssd_s, other_s, rounds):
    """A traced slice of ``rounds`` rounds: in each, inside ``jit_round_fn``,
    one operation on a chunk's decay matrices and one on a chunk state
    (``ssd_s`` seconds together), a projection (``other_s``) and a Pallas
    fold of a ``[64, 64, 128]``-shaped leaf; and during an evaluation an
    operation on the same shapes."""
    names = ["%f.1 = f32[64,256,256]{2,1,0} fusion(f32[256,64] %p)",
             "%c.2 = f32[256,64,64] convolution(f32[64,64,128]{2,1,0} %s, "
             "bf16[256,1,128] %c)",
             "%c.3 = f32[2048,8512] convolution(f32[2048,2048] %x)",
             "%fold = f32[64,64,128] custom-call(f32[64,64,128] %a), "
             "custom_call_target=\"tpu_custom_call\"",
             "jit_round_fn(123)", "jit_eval(9)"]
    ops, modules, spans = [], [], [["bench.slice", 0.0, 10.0 * rounds + 5]]
    for r in range(rounds):
        t = 10.0 * r
        modules.append([4, t, 8.0])
        spans.append(["bench.run_round", t, 8.5])
        ops += [[0, t, 0.75 * ssd_s], [1, t + 2, 0.25 * ssd_s],
                [2, t + 4, other_s], [3, t + 6, 1.0]]
    t = 10.0 * rounds
    modules.append([5, t, 2.0])
    spans.append(["bench.evaluate", t, 3.0])
    ops.append([0, t + 0.5, 1.0])
    return {"names": names, "spans": spans, "devices": [
        {"name": "/device:TPU:0", "ops": ops, "async": [],
         "modules": modules}]}


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


def test_the_roofline_reader_against_hand_arithmetic(config):
    entry = _load("benchmark", "metrics", "ssd_roofline.json")
    read = _module("readers", "ssd_roofline.py").read
    ops_ms = _module("readers", "trace_ops.py").read
    ms_args = _load("benchmark", "metrics", "ssd_ms.json")["args"]
    # 0.2 s of the recurrence a round: not the projection's 0.5 s, not the
    # fold's custom call, not the evaluation's operation
    ctx = _ctx(config, _trace(0.2, 0.5, rounds=2))
    assert ops_ms(ctx, **ms_args) == pytest.approx(200.0)
    # 16,384 tokens a round x 9 layers: 12,645,826,560 bytes at 819 GB/s
    # (the products' 1.88 TFLOP at 197 TFLOP/s are the smaller bound)
    least = 12_645_826_560.0 / 819e9
    assert least > 1_884_416_901_120.0 / 197e12
    got = read(ctx, **entry["args"])
    assert got == pytest.approx(100.0 * least / 0.2) \
        == pytest.approx(7.720284835)
    # the same work whatever implements it: a scan twice as fast reads
    # twice the share; one at the bound would read 100
    assert read(_ctx(config, _trace(0.1, 0.5, rounds=2)),
                **entry["args"]) == pytest.approx(2 * got)
    assert read(_ctx(config, _trace(least, 0.5, rounds=2)),
                **entry["args"]) == pytest.approx(100.0)
    # half the tokens a round (the counter over the window's rounds)
    assert read(_ctx(config, _trace(0.2, 0.5, rounds=2), tokens=16384.0 * 2),
                **entry["args"]) == pytest.approx(got / 2)


def test_the_roofline_reader_reads_nothing_where_there_is_nothing(config):
    entry = _load("benchmark", "metrics", "ssd_roofline.json")
    read = _module("readers", "ssd_roofline.py").read
    trace = _trace(0.2, 0.5, rounds=2)
    # no trace (an untraced run); a program without the counter (the
    # parent); a window without rounds
    assert read(_ctx(config), **entry["args"]) is None
    assert read(_ctx(config, trace, tokens=None), **entry["args"]) is None
    assert read(_ctx(config, trace, rounds=0), **entry["args"]) is None
    # a configuration without the state-space keys (every other cell)
    other = _load("benchmark", "configs", "lfm2_8b_a1b_ep4.json")
    assert read(_ctx(other, trace), **entry["args"]) is None
    # a trace without such operations
    bare = _trace(0.2, 0.5, rounds=2)
    bare["names"][0] = bare["names"][1] = "%c = f32[2048,4096] add(...)"
    assert read(_ctx(config, bare), **entry["args"]) is None
    # no state-space layer among the layers held
    attention_only = json.loads(json.dumps(config))
    attention_only["model"]["kwargs"]["layer_ids"] = [5]
    assert read(_ctx(attention_only, trace), **entry["args"]) is None


def test_no_python_file_of_the_benchmark_knows_the_cell_by_name():
    for kind in ("drivers", "generators", "readers", "kernels",
                 "references", "harness"):
        folder = os.path.join(ROOT, "benchmark", kind)
        for name in os.listdir(folder):
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                assert CELL not in text and CONFIG not in text, name


def test_the_reference_imports_nothing_of_the_programs_layers():
    with open(os.path.join(ROOT, "benchmark", "references",
                           "granite_hybrid_local_sgd.py")) as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "fedml_tpu.ops" not in code and "fedml_tpu.models" not in code
    assert "fedml_tpu" not in code  # the round loop's two imports are its own
    assert "ssd_scan" not in text
