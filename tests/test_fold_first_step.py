"""A silo's first local step out of place (``local_train``'s ``shared_init``,
which ``make_folded_body`` passes): the folded round's arithmetic is the
in-place round's, no leaf the step loop starts from is the global model's,
and the counter that says it engaged."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import make_folded_body
from fedml_tpu.models import create_model
from fedml_tpu.trainer.functional import TrainConfig
from tests.test_fold_round import SMALL, VOCAB, _api, _in_place, _token_silos


@pytest.fixture(scope="module")
def module():
    # a Mamba layer and a windowed attention layer: the fold's leaves at
    # test_fold_round's widths, a third of its layers to compile
    return create_model("sambay", output_dim=VOCAB,
                        **{**SMALL, "layer_ids": (0, 1)})


def _folded_inputs(module, epochs, rows):
    dataset = _token_silos(rows=(rows,) * 6, seed=epochs + rows)
    api = _api(dataset, module, "lm_rows",
               train=TrainConfig(epochs=epochs, batch_size=1, lr=0.05))
    _, (x, y, mask, keys, weights, _) = api._pack_round(0)[1:]
    return api, (x, y, mask, keys, weights)


@pytest.mark.parametrize("lr_scale", [None, 0.5])
@pytest.mark.parametrize("epochs, rows", [(1, 1), (1, 2), (2, 2)])
def test_the_out_of_place_first_step_leaves_the_folded_round_as_it_was(
        module, epochs, rows, lr_scale):
    """1, 2 and 4 local steps a silo: the same steps on the same batches
    with the same keys, so the new model and the stat totals are the
    in-place round's (to the bit on this CPU; the bound leaves room for a
    backend that fuses the first update otherwise)."""
    api, args = _folded_inputs(module, epochs, rows)
    scale = None if lr_scale is None else jnp.float32(lr_scale)
    got, got_stats = jax.jit(make_folded_body(
        api._local_train, interpret=True))(api.variables, *args, scale)
    want, want_stats = jax.jit(make_folded_body(
        _in_place(api._local_train), interpret=True))(api.variables, *args,
                                                      scale)
    assert float(got_stats["count"]) == 4 * rows * epochs
    for key in want_stats:
        np.testing.assert_allclose(got_stats[key], want_stats[key],
                                   rtol=1e-6)
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), got,
                         want)
    assert max(jax.tree.leaves(moved)) <= 1e-6
    changed = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), got,
                           api.variables)
    assert max(jax.tree.leaves(changed)) > 1e-4


def _step_scans(body, variables, args):
    """The silo ``scan`` of a folded body's jaxpr and, of the ``scan``s its
    body runs, those that carry the model: the step loops."""
    leaves = sorted(leaf.shape for leaf in jax.tree.leaves(
        variables["params"]))
    outer = [e for e in jax.make_jaxpr(body)(variables, *args).jaxpr.eqns
             if e.primitive.name == "scan"]
    assert len(outer) == 1 and outer[0].params["length"] == 4
    silo = outer[0].params["jaxpr"].jaxpr
    constants = set(silo.invars[:outer[0].params["num_consts"]])

    def carried(eqn):
        first = eqn.params["num_consts"]
        return eqn.invars[first:first + eqn.params["num_carry"]]

    # plain SGD: the parameters are all a step carries
    steps = [e for e in silo.eqns if e.primitive.name == "scan" and sorted(
        v.aval.shape for v in carried(e)) == leaves]
    shared = [sum(v in constants for v in carried(e)) for e in steps]
    return steps, shared, len(leaves)


@pytest.mark.parametrize("epochs, rows, loops", [(1, 1, 0), (1, 2, 1),
                                                 (2, 2, 1)])
def test_no_leaf_a_silos_step_loop_starts_from_is_the_global_models(
        module, epochs, rows, loops):
    api, args = _folded_inputs(module, epochs, rows)
    steps, shared, _ = _step_scans(
        make_folded_body(api._local_train, interpret=True), api.variables,
        args)
    # one step: no loop; else a loop over the steps after the first, whose
    # every carried leaf the silo's own body made
    assert [e.params["length"] for e in steps] == [epochs * rows - 1] * loops
    assert shared == [0] * loops


def test_the_in_place_step_loop_starts_from_the_global_models_leaves(module):
    """The control: the same reading of the round as it was finds every
    parameter leaf of the loop's start among the silo scan's constants."""
    api, args = _folded_inputs(module, 1, 2)
    steps, shared, leaves = _step_scans(
        make_folded_body(_in_place(api._local_train), interpret=True),
        api.variables, args)
    assert [e.params["length"] for e in steps] == [2]
    assert shared == [leaves]


def test_a_shared_start_cannot_be_combined_with_a_bounded_loop(module):
    api, (x, y, mask, keys, _) = _folded_inputs(module, 1, 2)
    with pytest.raises(ValueError, match="shared_init"):
        jax.eval_shape(lambda v: api._local_train(
            v, x[0], y[0], mask[0], keys[0], n_steps=jnp.int32(1),
            shared_init=True), api.variables)


@pytest.mark.parametrize("fold", [True, False])
def test_the_counter_of_out_of_place_first_steps(module, fold):
    api = _api(_token_silos(), module, "lm_rows", fold_clients=fold)
    api.run_round(0)
    counters = api.timer.counters
    if fold:
        assert counters["clients_first_step_out_of_place"] == 4
        assert counters["clients_folded"] == 4
    else:
        assert "clients_first_step_out_of_place" not in counters
