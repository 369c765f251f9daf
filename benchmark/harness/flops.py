"""Operations a traced function needs, from its jaxpr.

Counts what the MXU does — ``dot_general`` (2·M·N·K) and
``conv_general_dilated`` (2 · output elements · C_in/groups · kernel
window) — exactly, with ``scan`` bodies multiplied by their length. Nothing
compiles and no device is touched. Elementwise work is left out on purpose
(0.4-0.7 % of these models' totals): the figure divides a rate into the
chip's matrix peak.

XLA's own ``cost_analysis()`` is not used: it bills a ``scan`` body once
(``bench.py`` says so itself), which under-counts a local-training scan by
its trip count.
"""

from __future__ import annotations

import math

import jax


def _sub_jaxprs(eqn):
    """(jaxpr, multiplier) for every jaxpr an equation carries."""
    name = eqn.primitive.name
    if name == "scan":
        yield eqn.params["jaxpr"].jaxpr, int(eqn.params["length"])
        return
    if name == "while":
        raise ValueError(
            "a while loop has no static trip count; the FLOP count of a "
            "program that contains one is not defined here")
    for value in eqn.params.values():
        inner = getattr(value, "jaxpr", value)
        if hasattr(inner, "eqns"):
            yield inner, 1


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    if name == "dot_general":
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        contract = math.prod(lhs[d] for d in lhs_contract)
        return 2.0 * math.prod(eqn.outvars[0].aval.shape) * contract
    if name == "conv_general_dilated":
        rhs = eqn.invars[1].aval.shape
        spec = eqn.params["dimension_numbers"].rhs_spec
        # rhs_spec = (out features, in features per group, *window)
        window = math.prod(rhs[d] for d in spec[2:])
        return (2.0 * math.prod(eqn.outvars[0].aval.shape)
                * rhs[spec[1]] * window)
    return 0.0


def _jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        total += _eqn_flops(eqn)
        if eqn.primitive.name == "cond":  # one branch runs: the dearest
            total += max(_jaxpr_flops(b.jaxpr)
                         for b in eqn.params["branches"])
            continue
        for inner, times in _sub_jaxprs(eqn):
            total += times * _jaxpr_flops(inner)
    return total


def count(fn, *args) -> float:
    """dot + conv FLOPs of ``fn(*args)``, scan lengths multiplied."""
    return _jaxpr_flops(jax.make_jaxpr(fn)(*args).jaxpr)
