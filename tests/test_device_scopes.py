"""Device time by the program's own ``fedml.*`` scopes (PR 37): the scope
parser on a recorded HLO snippet, the map ``device_scopes`` hands out for
the two drivers' round programs, what ``run_round`` registers and what it
never does unasked, the three language models' new scopes over unchanged
programs, the benchmark's two new readers on the recorded trace with a
hand-made map and on hand-made round records, and the new manifest
entries."""

import hashlib
import json
import os
import re
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.data.synthetic import make_blob_federated
from fedml_tpu.models import create_model
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.ops import tree_weighted_mean_pallas
from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                     DistributedFedAvgConfig)
from fedml_tpu.trainer.functional import TrainConfig, make_local_train
from fedml_tpu.utils import tracing
from fedml_tpu.utils.tracing import (RoundTimer, ScopeMap, device_scopes,
                                     instruction_kind, parse_hlo_scopes,
                                     scope_chain)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LT = "jit(round_fn)/while/body/closed_call/fedml.local_train/while/body"


# -- the parser -------------------------------------------------------------------

@pytest.mark.parametrize("op_name, chain", [
    (f"{LT}/closed_call/jvp(SambaYLM)/vmap(fedml.ssm_scan)/closed_call/mul",
     ("fedml.local_train", "fedml.ssm_scan")),
    (f"{LT}/closed_call/transpose(jvp(fedml.mamba2))/fedml.ssd/dot_general",
     ("fedml.local_train", "fedml.mamba2", "fedml.ssd")),
    (f"{LT}/transpose(jvp(GraniteHybridLM))/checkpoint/rematted_computation"
     "/fedml.mamba2/vmap(jvp(fedml.ssd))/transpose(jvp(fedml.ssd))/exp",
     ("fedml.local_train", "fedml.mamba2", "fedml.ssd")),
    ("jit(round_fn)/while/body/closed_call/fedml.fold/pallas_call",
     ("fedml.fold",)),
    ("jit(round_fn)/while/body/closed_call/convert_element_type", ()),
    ("", ()),
])
def test_a_chain_is_the_op_names_fedml_tokens_in_order_each_once(op_name,
                                                                 chain):
    assert scope_chain(op_name) == chain


#: a round program as the TPU compiler prints it, cut to what the rules
#: read: a weight gradient with the SGD update fused in (the root carries
#: fedml.local_train alone), a forward fusion of one chain, a copy the
#: layout pass put into the loop (no metadata), the fold's custom call and
#: an unnamed copy at the program's edge
SNIPPET = f'''HloModule jit_round_fn, is_scheduled=true, entry_computation_layout={{(f32[128,512]{{1,0}})->f32[128,512]{{1,0}}}}

FileNames
1 "/root/repo/fedml_tpu/algorithms/fedavg.py"

%fused_computation.7 (param_0.1: f32[128,512], param_1.2: f32[8,128], param_2.3: f32[8,512]) -> f32[128,512] {{
  %param_0.1 = f32[128,512]{{1,0:T(8,128)}} parameter(0)
  %param_1.2 = f32[8,128]{{1,0:T(8,128)}} parameter(1)
  %param_2.3 = f32[8,512]{{1,0:T(8,128)}} parameter(2)
  %convolution.9 = f32[128,512]{{1,0:T(8,128)}} convolution(%param_1.2, %param_2.3), dim_labels=fb_io->bf, metadata={{op_name="{LT}/closed_call/transpose(jvp(SambaYLM))/vmap()/checkpoint/fedml.mlp/dot_general" stack_frame_id=12}}
  %constant.3 = f32[] constant(0.1), metadata={{op_name="{LT}/closed_call/mul"}}
  %broadcast.5 = f32[128,512]{{1,0:T(8,128)}} broadcast(%constant.3), dimensions={{}}
  %multiply.4 = f32[128,512]{{1,0:T(8,128)}} multiply(%convolution.9, %broadcast.5), metadata={{op_name="{LT}/closed_call/mul"}}
  ROOT %subtract.3 = f32[128,512]{{1,0:T(8,128)}} subtract(%param_0.1, %multiply.4), metadata={{op_name="{LT}/closed_call/sub"}}
}}

%fused_computation.8 (param_0.4: f32[8,128]) -> f32[8,128] {{
  %param_0.4 = f32[8,128]{{1,0:T(8,128)}} parameter(0)
  ROOT %exponential.2 = f32[8,128]{{1,0:T(8,128)}} exponential(%param_0.4), metadata={{op_name="{LT}/closed_call/jvp(GraniteHybridLM)/fedml.mamba2/vmap(fedml.ssd)/exp"}}
}}

%region_1.5 (arg_tuple.1: (s32[], f32[128,512], f32[8,128], /*index=3*/f32[8,512])) -> (s32[], f32[128,512], f32[8,128], /*index=3*/f32[8,512]) {{
  %arg_tuple.1 = (s32[], f32[128,512]{{1,0:T(8,128)}}, f32[8,128]{{1,0:T(8,128)}}, /*index=3*/f32[8,512]{{1,0:T(8,128)}}) parameter(0)
  %get-tuple-element.2 = f32[128,512]{{1,0:T(8,128)}} get-tuple-element(%arg_tuple.1), index=1
  %copy.1403 = f32[128,512]{{0,1:T(8,128)}} copy(%get-tuple-element.2)
  %get-tuple-element.3 = f32[8,128]{{1,0:T(8,128)}} get-tuple-element(%arg_tuple.1), index=2
  %get-tuple-element.4 = f32[8,512]{{1,0:T(8,128)}} get-tuple-element(%arg_tuple.1), index=3
  %fusion.8 = f32[8,128]{{1,0:T(8,128)}} fusion(%get-tuple-element.3), kind=kLoop, calls=%fused_computation.8, metadata={{op_name="{LT}/closed_call/jvp(GraniteHybridLM)/fedml.mamba2/vmap(fedml.ssd)/exp"}}
  %fusion.7 = f32[128,512]{{1,0:T(8,128)}} fusion(%copy.1403, %fusion.8, %get-tuple-element.4), kind=kOutput, calls=%fused_computation.7, metadata={{op_name="{LT}/closed_call/sub"}}
  %get-tuple-element.1 = s32[] get-tuple-element(%arg_tuple.1), index=0
  ROOT %tuple.3 = (s32[], f32[128,512]{{1,0:T(8,128)}}, f32[8,128]{{1,0:T(8,128)}}, /*index=3*/f32[8,512]{{1,0:T(8,128)}}) tuple(%get-tuple-element.1, %fusion.7, %fusion.8, %get-tuple-element.4)
}}

%region_2.6 (arg_tuple.2: (s32[], f32[128,512], f32[8,128], /*index=3*/f32[8,512])) -> pred[] {{
  %arg_tuple.2 = (s32[], f32[128,512]{{1,0:T(8,128)}}, f32[8,128]{{1,0:T(8,128)}}, /*index=3*/f32[8,512]{{1,0:T(8,128)}}) parameter(0)
  %get-tuple-element.5 = s32[] get-tuple-element(%arg_tuple.2), index=0
  %constant.8 = s32[] constant(2)
  ROOT %compare.1 = pred[] compare(%get-tuple-element.5, %constant.8), direction=LT
}}

ENTRY %main.10 (Arg_0.1: f32[128,512]) -> f32[128,512] {{
  %Arg_0.1 = f32[128,512]{{1,0:T(8,128)}} parameter(0)
  %copy.9 = f32[128,512]{{1,0:T(8,128)}} copy(%Arg_0.1)
  %tuple.1 = (s32[], f32[128,512]{{1,0:T(8,128)}}, f32[8,128]{{1,0:T(8,128)}}, /*index=3*/f32[8,512]{{1,0:T(8,128)}}) tuple(%constant.1, %copy.9, %constant.2, %constant.4)
  %while.4 = (s32[], f32[128,512]{{1,0:T(8,128)}}, f32[8,128]{{1,0:T(8,128)}}, /*index=3*/f32[8,512]{{1,0:T(8,128)}}) while(%tuple.1), condition=%region_2.6, body=%region_1.5, metadata={{op_name="jit(round_fn)/while/body/closed_call/fedml.local_train/while" stack_frame_id=3}}, backend_config={{"known_trip_count":{{"n":"2"}}}}
  %get-tuple-element.9 = f32[128,512]{{1,0:T(8,128)}} get-tuple-element(%while.4), index=1
  ROOT %custom-call.2 = f32[128,512]{{1,0:T(8,128)}} custom-call(%copy.9, %get-tuple-element.9), custom_call_target="tpu_custom_call", metadata={{op_name="jit(round_fn)/while/body/closed_call/fedml.fold/pallas_call"}}
}}
'''


def test_the_parser_on_a_recorded_snippet():
    module, (chains, mixed, kinds) = parse_hlo_scopes(SNIPPET)
    assert module == "jit_round_fn"
    train = ("fedml.local_train",)
    # forward, and nested scopes under a jvp
    assert chains["fusion.8"] == train + ("fedml.mamba2", "fedml.ssd")
    # the weight gradient with the update fused in goes with its product,
    # not with the update at its root, and the map says the rule decided
    assert chains["fusion.7"] == train + ("fedml.mlp",)
    assert mixed == {"fusion.7"}
    # no metadata inside the loop: the ``while``'s chain, in the body and
    # in the condition; the ``while`` itself has its own
    assert chains["while.4"] == train
    for inherited in ("copy.1403", "get-tuple-element.2", "arg_tuple.1",
                      "tuple.3", "compare.1", "constant.8"):
        assert chains[inherited] == train, inherited
    # the fold's kernel whatever implements the trainer; nobody named the
    # entry's copy, and nothing calls the entry
    assert chains["custom-call.2"] == ("fedml.fold",)
    assert chains["copy.9"] == chains["Arg_0.1"] == ()
    # the fused computations' own instructions are no trace events
    assert not {"convolution.9", "subtract.3", "exponential.2",
                "param_0.1"} & set(chains)
    assert len(chains) == 19 and set(kinds) == set(chains)
    # what a reader holds an event of that name to: result type and
    # operation, layouts dropped, a tuple type's parentheses skipped
    assert kinds["copy.1403"] == "f32[128,512] copy"
    assert kinds["fusion.7"] == "f32[128,512] fusion"
    assert kinds["while.4"] == ("(s32[], f32[128,512], f32[8,128], "
                                "/*index=3*/f32[8,512]) while")


# -- the map of the two drivers' round programs -----------------------------------

def _blobs():
    return make_blob_federated(client_num=8, n_samples=8 * 25, seed=0,
                               partition_method="homo")


def _sim(dataset, **config):
    return FedAvgAPI(
        dataset, LogisticRegression(num_classes=dataset.class_num),
        task="classification",
        config=FedAvgConfig(comm_round=4, client_num_per_round=4,
                            prefetch_depth=0, **config,
                            train=TrainConfig(epochs=1, batch_size=8,
                                              lr=0.1)),
        aggregate_hook=lambda variables, stacked, weights, key:
        tree_weighted_mean_pallas(stacked, weights, interpret=True))


def _mesh(dataset):
    return DistributedFedAvgAPI(
        dataset, LogisticRegression(num_classes=dataset.class_num),
        task="classification",
        config=DistributedFedAvgConfig(
            comm_round=4, client_num_per_round=4, prefetch_depth=0,
            train=TrainConfig(epochs=1, batch_size=8, lr=0.1)))


def _loop_instructions(text):
    """The instructions of the entry and of every ``while`` body and
    condition: what a device trace shows."""
    loops = set(re.findall(r"(?:body|condition)=%?([^\s,)]+)", text))
    names, inside = set(), False
    for line in text.splitlines():
        head = re.match(r"^(ENTRY\s+)?%?([^\s(]+)\s+\(.*->.*\{\s*$", line)
        if head:
            inside = bool(head.group(1)) or head.group(2) in loops
        elif line.startswith("}"):
            inside = False
        elif inside:
            found = re.match(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s", line)
            if found:
                names.add(found.group(1))
    return names


@pytest.mark.parametrize("build, module", [(_sim, "jit_round_fn"),
                                           (_mesh, "jit_body")])
def test_the_map_knows_the_round_program_of_either_driver(
        build, module, monkeypatch, capsys):
    api = build(_blobs())
    # this driver's programs alone: other tests' drivers may still live
    monkeypatch.setattr(tracing, "_live_timers", lambda: [api.timer])
    for r in range(2):
        api.run_round(r)
    jax.block_until_ready(api.variables)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_, **__: compiles.append(name)
        if name.endswith("backend_compile_duration") else None)
    maps = device_scopes()
    # the lowering the call made, and the executable that ran: no second
    # compilation, whose instructions would be numbered a little otherwise
    assert compiles == []
    assert list(maps) == [module]
    chains, mixed, kinds = maps[module]
    program, = api.timer.programs()
    text = program.fn.lower(*program.avals).compile().as_text()
    seen = _loop_instructions(text)
    assert len(seen) > 50 and seen <= set(chains)
    tokens = {t for chain in chains.values() for t in chain}
    assert {"fedml.local_train", "fedml.aggregate"} <= tokens
    assert mixed <= set(chains) and set(kinds) == set(chains)
    assert f"device_scopes: {module} lowered" in capsys.readouterr().err
    # read once and kept
    assert device_scopes()[module].chains == chains
    assert "device_scopes" not in capsys.readouterr().err


class _Counting:
    """A jitted function that counts what is asked of it."""

    def __init__(self, fn):
        self.fn, self.calls, self.lowered = fn, 0, 0

    def __call__(self, *args):
        self.calls += 1
        return self.fn(*args)

    def lower(self, *avals):
        self.lowered += 1
        return self.fn.lower(*avals)


def test_run_round_registers_a_shape_once_and_lowers_nothing_unasked(
        monkeypatch):
    made = []
    abstract = tracing._abstract
    monkeypatch.setattr(tracing, "_abstract",
                        lambda tree: made.append(1) or abstract(tree))
    for stage in (jax.stages.Lowered, jax.stages.Compiled):
        for method in ("compile", "as_text"):
            if hasattr(stage, method):
                monkeypatch.setattr(
                    stage, method, lambda *a, **k: pytest.fail(
                        "tracing off: nothing may be compiled or printed"))
    api = _sim(_blobs())
    api._round_fn = counting = _Counting(api._round_fn)
    for r in range(4):
        api.run_round(r)
    jax.block_until_ready(api.variables)
    assert counting.calls == 4 and counting.lowered == 0
    assert len(made) == 1 and len(api.timer.programs()) == 1
    program, = api.timer.programs()
    # abstract values only: the model is donated, the arrays are gone
    leaves = jax.tree.leaves(program.avals)
    assert all(isinstance(a, jax.ShapeDtypeStruct) for a in leaves)
    assert program.scopes is tracing._Program._UNREAD
    # another operand shape is another program
    api.timer.register_program(counting, api.variables,
                               (jnp.zeros((2, 3)),))
    assert len(made) == 2 and len(api.timer.programs()) == 2


def test_a_program_that_cannot_be_read_costs_the_map_and_nothing_else(
        monkeypatch, capsys):
    class Broken:
        def lower(self, *avals):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    timer = RoundTimer()
    timer.register_program(Broken(), {"w": jnp.zeros(3)}, (jnp.zeros(2),))
    monkeypatch.setattr(tracing, "_live_timers", lambda: [timer])
    assert device_scopes() == {}
    err = capsys.readouterr().err
    assert "no map of a round program" in err and "RESOURCE_EXHAUSTED" in err
    assert device_scopes() == {}  # not tried again
    assert capsys.readouterr().err == ""


def test_two_programs_of_one_name_keep_what_they_agree_on(monkeypatch):
    texts = iter([
        SNIPPET,
        SNIPPET.replace("fedml.fold/pallas_call", "fedml.aggregate/dot")])
    compiled = types.SimpleNamespace(as_text=lambda: next(texts))

    class Fn:
        def lower(self, *avals):
            return types.SimpleNamespace(compile=lambda: compiled)

    fn = Fn()
    timer = RoundTimer()
    timer.register_program(fn, {}, (jnp.zeros(2),))
    timer.register_program(fn, {}, (jnp.zeros(3),))
    monkeypatch.setattr(tracing, "_live_timers", lambda: [timer])
    chains, mixed, kinds = device_scopes()["jit_round_fn"]
    assert "custom-call.2" not in chains and len(chains) == 18
    assert set(kinds) == set(chains)
    assert chains["fusion.7"] == ("fedml.local_train", "fedml.mlp")
    assert mixed == {"fusion.7"}


# -- the language models' new scopes over unchanged programs ----------------------

#: sha256(str(jaxpr))[:16] of one client's local training as the parent
#: commit (01b1198, before the scopes) traces it at these sizes under the
#: tests' settings (matmul precision ``highest``, tests/conftest.py);
#: ``lfm2_moe``'s as traced since the routed experts' combine moved inside
#: their block loop (``ops/moe.py``) and the routing stats gained
#: ``moe_block_rows``, the rows those loops ran (the block at these sizes is
#: the call's 48 pairs, as it was)
LANGUAGE_MODELS = {
    "sambay": (dict(hidden_size=128, num_heads=4, num_kv_heads=2,
                    intermediate_size=128, sliding_window=8,
                    layer_ids=(0, 1, 16, 17, 18, 19), scan_chunk=8,
                    scan_lanes=2, attn_block=8), "4261a6359dad3c63"),
    "lfm2_moe": (dict(hidden_size=64, num_heads=4, num_kv_heads=2,
                      intermediate_size=128, moe_intermediate_size=32,
                      num_experts=4, num_experts_per_tok=2,
                      experts_held=(0, 2), layer_ids=(1, 2, 3),
                      attn_block=8), "0b7f1e03f36b7646"),
    "granite_hybrid": (dict(hidden_size=64, num_heads=4, num_kv_heads=2,
                            shared_intermediate_size=128, layer_ids=(4, 5),
                            mamba_n_heads=4, mamba_d_head=32,
                            mamba_d_state=16, mamba_chunk_size=8,
                            attn_block=8), "3d20db3db090a616"),
}


@pytest.mark.parametrize("name", sorted(LANGUAGE_MODELS))
def test_the_language_models_name_their_feed_forward_head_and_embedding(
        name):
    kwargs, parent = LANGUAGE_MODELS[name]
    module = create_model(name, output_dim=64, **kwargs)
    x = jnp.zeros((2, 24), jnp.int32)
    variables = module.init(jax.random.key(0), x[:1])
    local_train = make_local_train(
        module, "lm_rows", TrainConfig(epochs=1, batch_size=1, lr=0.05))
    operands = (variables, x, x, jnp.ones(2), jax.random.key(1))
    # names only: no equation of the program changed
    jaxpr = jax.make_jaxpr(local_train)(*operands)
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == parent
    lowered = jax.jit(local_train).lower(*operands).as_text(debug_info=True)
    for scope in ("fedml.mlp", "fedml.lm_head", "fedml.embed"):
        names = re.findall(rf'"([^"]*{re.escape(scope)}[^"]*)"', lowered)
        # forward, and the backward pass under the same name
        assert any("jvp(" in n and "transpose(" not in n for n in names)
        assert any("transpose(" in n for n in names), scope


# -- the benchmark's readers ------------------------------------------------------

def _reader(name):
    from benchmark.harness import spec
    return spec.load_module(os.path.join(ROOT, "benchmark", "readers",
                                         name + ".py"))


WITHIN = dict(within_modules=r"^jit_(round_fn|body)\(",
              outside_spans=["bench.evaluate"])


@pytest.fixture()
def traced():
    """The trace recorded on the chip (``benchmark/tests/fixture``) as a
    reader's context, and a hand-made map of its round program: fusions
    under the trainer, the stacking's dynamic-update-slices under the
    aggregation (one of them a mixed fusion), the copies nobody's."""
    from benchmark.harness import trace as tr
    trace = tr.load(os.path.join(ROOT, "benchmark", "tests", "fixture",
                                 "trace_v5e.json.gz"))
    window = tr.window_of(trace)
    ops = tr.events(trace, 0, "ops", window)
    programs = tr.events(trace, 0, "modules", window)
    rounds = [trace["names"][i].startswith("jit_round_fn(")
              for i in programs.ids]
    ran = ops.inside(tr.merge(programs.intervals(np.array(rounds))))
    chains, kinds = {}, {}
    for name in {trace["names"][i] for i in ops.ids[ran]}:
        instruction, _, rest = name.partition(" = ")
        instruction = instruction.lstrip("%")
        kinds[instruction] = instruction_kind(rest)
        chains[instruction] = (
            ("fedml.local_train",) if instruction.startswith("fusion")
            else ("fedml.aggregate",) if instruction.startswith("dynamic")
            else ("fedml.local_train", "fedml.ssd")
            if instruction.startswith("while") else ())
    ctx = types.SimpleNamespace(trace=trace, trace_window=window,
                                trace_rounds=2)
    whole = tr.op_seconds(trace, window, **WITHIN)
    return ctx, {"jit_round_fn": ScopeMap(
        chains, frozenset({"dynamic-update-slice.56"}), kinds)}, whole


def test_scope_ops_adds_up_to_the_round_programs_self_time(
        traced, monkeypatch, capsys):
    ctx, maps, whole = traced
    monkeypatch.setattr(tracing, "device_scopes", lambda: maps)
    reader = _reader("scope_ops")
    read = {name: reader.read(ctx, **args, **WITHIN) for name, args in {
        "train": dict(scope=["fedml.local_train"]),
        "agg": dict(scope=["fedml.aggregate", "fedml.fold"]),
        "ssd": dict(scope=["fedml.ssd"]),
        "train_less_ssd": dict(scope=["fedml.local_train"],
                               exclude=["fedml.ssd"]),
        "nobody": dict(unscoped=True)}.items()}
    assert all(value is not None and value > 0 for value in read.values())
    assert read["train"] == pytest.approx(
        read["train_less_ssd"] + read["ssd"])
    # unscoped + the time under any scope = what the accepted trace_ops
    # metrics call train_device_ms + agg_kernel_ms
    assert read["train"] + read["agg"] + read["nobody"] == pytest.approx(
        1e3 * whole / ctx.trace_rounds, rel=1e-9)
    err = capsys.readouterr().err
    # the table once a run, longest first, with the mixed fusions' part
    assert err.count("by scope chain") == 1
    rows = [float(line.split()[1]) for line in err.splitlines()
            if line.startswith("[bench]   ")]
    assert len(rows) == 4 and rows == sorted(rows, reverse=True)
    assert "fedml.local_train > fedml.ssd" in err and "(unscoped)" in err
    assert "the map knows 100.000 %" in err


def test_scope_ops_reads_nothing_rather_than_a_wrong_number(
        traced, monkeypatch, capsys):
    ctx, maps, _ = traced
    reader = _reader("scope_ops")
    args = dict(scope=["fedml.local_train"], **WITHIN)
    # a scope the map lacks: mistyped, or an older tree's executable
    monkeypatch.setattr(tracing, "device_scopes", lambda: maps)
    assert reader.read(ctx, scope=["fedml.mlp"], **WITHIN) is None
    assert "no instruction of the map is under" in capsys.readouterr().err
    # the text is not of the executable that ran
    chains, mixed, kinds = maps["jit_round_fn"]
    holes = {k: v for k, v in chains.items() if not k.startswith("copy")}
    monkeypatch.setattr(
        tracing, "device_scopes",
        lambda: {"jit_round_fn": ScopeMap(holes, mixed, kinds)})
    ctx.trace.pop("_scope_ops")
    assert reader.read(ctx, **args) is None
    assert "% of the round programs' self time" in capsys.readouterr().err
    # every name is there, but another compilation numbered its copies
    # otherwise: a name whose result type is another's is not known
    numbers = sorted((k for k in kinds if k.startswith("copy.")),
                     key=lambda k: int(k.split(".")[1]))
    shifted = {**kinds, **dict(zip(numbers[1:], (kinds[k] for k in
                                                 numbers[:-1])))}
    monkeypatch.setattr(
        tracing, "device_scopes",
        lambda: {"jit_round_fn": ScopeMap(chains, mixed, shifted)})
    ctx.trace.pop("_scope_ops")
    assert reader.read(ctx, **args) is None
    assert "% of the round programs' self time" in capsys.readouterr().err
    # no map: a lowering that failed; an older tree without the function
    monkeypatch.setattr(tracing, "device_scopes", lambda: {})
    ctx.trace.pop("_scope_ops")
    assert reader.read(ctx, **args) is None
    monkeypatch.delattr(tracing, "device_scopes")
    assert reader.read(ctx, **args) is None
    assert "no scope map" in capsys.readouterr().err
    # an untraced run, a slice without rounds
    assert reader.read(types.SimpleNamespace(trace=None, trace_rounds=0),
                       **args) is None


def _spans(rounds, at_s, pack_s):
    """A round every 100 ms from ``at_s``: the round thread holds it open
    for 10 ms, 1 ms of them starved; the worker's ``produce`` (``pack`` and
    2 ms of ``upload`` inside) closes 30 ms after the round has, during
    the drain."""
    ns, out = 1_000_000, []
    for i, r in enumerate(rounds):
        t0 = int(at_s * 1e9) + i * 100 * ns
        done = t0 + 40 * ns
        pack = int(pack_s * 1e9)
        out += [("device_starved", "main", r, t0 + ns, t0 + 2 * ns),
                ("round", "main", r, t0, t0 + 10 * ns),
                ("pack", "worker", None, done - 2 * ns - pack, done - 2 * ns),
                ("upload", "worker", None, done - 2 * ns, done),
                ("produce", "worker", None, done - 3 * ns - pack, done)]
    return out


def test_the_quiet_phases_are_those_of_the_rounds_after_the_slice(
        monkeypatch, capsys):
    from benchmark.harness import spec
    reader = _reader("timer_phase_quiet")
    cell = spec.load_cell("fedcifar100_resnet18gn.mesh4")  # eval every 5
    # the warm-up's round 16, then the window's: rounds 6..15 ran under
    # the profiler at 300 ms of pack, 16..20 after it at 10
    spans = sorted(_spans([0, 16], 1.0, 0.9) + _spans(range(0, 6), 5.0, 0.05)
                   + _spans(range(6, 16), 6.0, 0.3)
                   + _spans(range(16, 21), 9.0, 0.01), key=lambda s: s[3])
    monkeypatch.setattr(tracing, "recent_spans", lambda: spans)
    ctx = types.SimpleNamespace(
        cell=cell, window=types.SimpleNamespace(traced=True, rounds=21))
    # the last round's cohort is done after the round has closed: four of
    # the five count. Over the rounds that reads four fifths of a cohort's
    # cost; the mean span of each phase is free of the stretch's edges
    assert reader.read(ctx, ["pack", "upload"]) == pytest.approx(12 * 4 / 5)
    assert reader.read(ctx, ["pack", "upload"], per_span=True
                       ) == pytest.approx(12.0)
    assert reader.read(ctx, ["produce"], per_span=True) == pytest.approx(13.0)
    # a phase of the round thread, per round: one round in five starved
    # would read a fifth of its span
    assert reader.read(ctx, ["device_starved"]) == pytest.approx(1.0)
    assert reader.read(ctx, ["no_such_phase"]) == 0.0
    assert reader.read(ctx, ["no_such_phase"], per_span=True) == 0.0
    assert ("{'produce': 4} spans in the 5 rounds 16..20 after the slice"
            in capsys.readouterr().err)
    # under three rounds after the slice: nothing
    ctx.window.rounds = 18
    assert reader.read(ctx, ["pack"]) is None
    assert "2 rounds followed the slice" in capsys.readouterr().err
    # the rounds have left the ring
    ctx.window.rounds = 24
    assert reader.read(ctx, ["pack"]) is None
    assert "not in the span ring" in capsys.readouterr().err
    # an untraced window has no slice; a program without spans
    ctx.window = types.SimpleNamespace(traced=False, rounds=21)
    assert reader.read(ctx, ["pack"]) is None
    ctx.window.traced = True
    monkeypatch.delattr(tracing, "recent_spans")
    assert reader.read(ctx, ["pack"]) is None


def test_a_workers_span_between_two_rounds_is_in_the_ring_not_in_a_record():
    """Why the quiet phases are read from the spans: a round's record holds
    what closed while it was open."""
    timer = RoundTimer()
    timer.begin_round(11)
    timer.end_round(11)
    with timer.phase("pack"):  # during the drain: no round is open
        pass
    timer.begin_round(12)
    timer.end_round(12)
    assert all("pack" not in rec["phases"] for rec in timer.round_records())
    assert [s[0] for s in timer.spans()] == ["round", "pack", "round"]


def test_a_traced_run_reads_the_new_metrics_through_the_harness(tmp_path,
                                                                capsys):
    """A fixture cell, traced, on the CPU: the run lowers its round program
    after the window, finds no device plane to join it to, leaves the
    by-scope metrics out and prints every other one."""
    from benchmark.harness import cell as cell_mod
    from benchmark.harness import spec
    manifest = spec.load_json(os.path.join(
        ROOT, "benchmark", "tests", "fixture", "BENCHMARK.json"))
    new = [{k: v for k, v in m.items() if k != "workloads"}
           for m in spec.load_json(spec.MANIFEST)["per_layer"][31:]]
    manifest["per_layer"] += new
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    cell = spec.load_cell("tiny_lr.tiny_fast", str(path))
    result = cell_mod.run(cell, seed=2 ** 31 + 7, seconds=1.0, trace=True,
                          t_start=time.time(), out_dir=str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    assert not [m["name"] for m in new if m["source"] == "device_trace"
                and m["name"] in metrics]
    assert metrics["recompiles"]["value"] == 0.0  # the lowering came after
    assert metrics["produce_quiet_ms"]["value"] > 0.0
    assert metrics["starved_quiet_ms"]["value"] >= 0.0
    assert metrics["pack_quiet_ms"]["value"] >= 0.0
    err = capsys.readouterr().err
    assert "device_scopes: jit_round_fn lowered" in err
    assert "{'produce': " in err and "after the slice" in err


# -- the manifest -------------------------------------------------------------------

ALL = ["fedcifar100_resnet18gn.dense", "fedcifar100_resnet18gn.mesh4",
       "femnist_cnn.powerlaw", "femnist_cnn.resident",
       "phi4_mini_flash_6l.silo4", "lfm2_8b_a1b_ep4.silo4",
       "fedcifar100_resnet18gn.mesh1", "granite_4_0_h_micro_10l.silo4"]
LANGUAGE = [ALL[4], ALL[5], ALL[7]]
PACKING = [ALL[0], ALL[6], ALL[1], ALL[2]]
NEW = [
    ("aggregate_scope_ms", "aggregation", ALL,
     dict(scope=["fedml.aggregate", "fedml.fold"])),
    ("unscoped_ms", "driver", ALL, dict(unscoped=True)),
    ("attention_ms", "trainer", LANGUAGE,
     dict(scope=["fedml.attention", "fedml.diff_attention"])),
    ("mlp_ms", "trainer", LANGUAGE, dict(scope=["fedml.mlp"])),
    ("lm_head_ms", "trainer", LANGUAGE,
     dict(scope=["fedml.lm_head", "fedml.embed"])),
    ("mamba2_mixer_ms", "trainer", LANGUAGE[2:],
     dict(scope=["fedml.mamba2"], exclude=["fedml.ssd"])),
    ("ssd_scope_ms", "trainer", LANGUAGE[2:], dict(scope=["fedml.ssd"])),
    ("ssm_scan_scope_ms", "trainer", LANGUAGE[:1],
     dict(scope=["fedml.ssm_scan"])),
    ("moe_scope_ms", "trainer", LANGUAGE[1:2], dict(scope=["fedml.moe"])),
    ("pack_quiet_ms", "packer", PACKING,
     dict(phases=["pack", "upload"], per_span=True)),
    ("produce_quiet_ms", "packer", PACKING,
     dict(phases=["produce"], per_span=True)),
    ("starved_quiet_ms", "driver", PACKING,
     dict(phases=["device_starved"])),
]


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_twelve_entries_are_appended_and_nothing_else_moved(manifest):
    assert [m["name"] for m in manifest["per_layer"]][30:43] == [
        "pack_recycled_per_round"] + [name for name, *_ in NEW]
    # later PRs append cells and configurations (PR 39 a ninth and a sixth)
    assert [w["name"] for w in manifest["workloads"]][:8] == ALL
    assert len(manifest["configs"]) >= 5
    assert len(json.dumps(manifest, indent=2)) < 64 * 1024
    layers = {m["layer"] for m in manifest["per_layer"][:31]}
    assert {m["layer"] for m in manifest["per_layer"][31:]} <= layers


@pytest.mark.parametrize("name, layer, cells, args", NEW,
                         ids=[name for name, *_ in NEW])
def test_a_new_metric_is_an_entry_a_file_and_a_reader(manifest, name, layer,
                                                      cells, args):
    by_scope = "phases" not in args
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert entry == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "device_trace" if by_scope else "program_span",
        "layer": layer, "moves": "rounds_per_s", "workloads": cells}
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", name)
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        metric = json.load(f)
    assert metric["reader"] == ("scope_ops" if by_scope
                                else "timer_phase_quiet")
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers",
                                       metric["reader"] + ".py"))
    assert metric["args"] == ({**args, **WITHIN} if by_scope else args)
    # every scope a metric names is one the program opens
    source = "".join(
        open(os.path.join(base, f)).read()
        for base, _, files in os.walk(os.path.join(ROOT, "fedml_tpu"))
        for f in files if f.endswith(".py"))
    for scope in args.get("scope", []) + args.get("exclude", []):
        assert f'named_scope("{scope}")' in source, scope
    from benchmark.harness import spec
    for cell in ALL:
        names = [m["name"] for m in spec.load_cell(cell).per_layer]
        assert (name in names) == (cell in cells)
