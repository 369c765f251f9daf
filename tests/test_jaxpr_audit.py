"""fedml_tpu.analysis layer 2 — jaxpr audit: planted violations, the
shipped entry-point registry, the lowering-key sweep contract, and the
collective-signature baseline (FT105/FT106)."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.analysis.jaxpr_audit import (audit_spec,
                                            check_collective_baseline,
                                            run_audit, signature_key,
                                            write_collective_baseline)
from fedml_tpu.analysis.registry import (AuditSpec, _REGISTRY,
                                         hot_entry_point,
                                         load_entry_points)

REPO = Path(__file__).resolve().parent.parent
REQUIRED_ENTRIES = {"fedavg.round_fn", "fedopt.round_fn",
                    "spmd.block_multiround", "spmd.sharded_eval",
                    "ops.flash_attention_fwd_bwd"}


def _host_sin(x):
    return np.sin(x, dtype=np.float32)


class TestPlantedViolations:
    def test_pure_callback_in_scan_is_flagged(self):
        def fused_rounds(xs):
            def body(c, x):
                y = jax.pure_callback(
                    _host_sin, jax.ShapeDtypeStruct((), jnp.float32), x)
                return c + y, y
            return jax.lax.scan(body, jnp.float32(0.0), xs)

        spec = AuditSpec(fn=fused_rounds, sweep=[(jnp.ones(4),)])
        findings, report = audit_spec("planted.callback", spec)
        assert "FT102" in {f.rule for f in findings}
        assert report["n_lowering_keys"] == 1

    def test_callback_outside_loop_is_not_flagged(self):
        def fn(x):
            return jax.pure_callback(
                _host_sin, jax.ShapeDtypeStruct((), jnp.float32), x[0])

        findings, _ = audit_spec("planted.hoisted",
                                 AuditSpec(fn=fn, sweep=[(jnp.ones(4),)]))
        assert "FT102" not in {f.rule for f in findings}

    def test_weak_type_recompile_is_flagged(self):
        # the r5 class: one caller passes a Python float (weak-typed
        # scalar), another a jnp.float32 — two jit cache entries for one
        # logical program
        fn = lambda x: x * 2  # noqa: E731
        spec = AuditSpec(fn=fn, sweep=[(2.0,), (jnp.float32(2.0),)],
                         max_lowerings=1)
        findings, report = audit_spec("planted.weak", spec)
        assert [f.rule for f in findings] == ["FT104"]
        assert report["n_lowering_keys"] == 2

    def test_identical_signatures_are_one_key(self):
        fn = lambda x: x * 2  # noqa: E731
        spec = AuditSpec(fn=fn, sweep=[(jnp.float32(2.0),),
                                       (jnp.float32(7.0),)])
        findings, report = audit_spec("planted.stable", spec)
        assert findings == [] and report["n_lowering_keys"] == 1

    def test_f64_result_is_flagged(self):
        with jax.enable_x64(True):
            spec = AuditSpec(
                fn=lambda x: x.astype("float64") * 2,
                sweep=[(jnp.ones(3, jnp.float32),)])
            findings, _ = audit_spec("planted.f64", spec)
        assert "FT101" in {f.rule for f in findings}

    def test_grad_path_upcast_is_flagged(self):
        def loss(x):
            return jnp.sum(x.astype(jnp.float32) ** 2)

        spec = AuditSpec(fn=jax.grad(loss),
                         sweep=[(jnp.ones(4, jnp.bfloat16),)],
                         grad_path=True)
        findings, _ = audit_spec("planted.upcast", spec)
        assert "FT103" in {f.rule for f in findings}

    def test_forward_only_tolerates_sub_f64_upcasts(self):
        spec = AuditSpec(fn=lambda x: x.astype(jnp.float32) * 2,
                         sweep=[(jnp.ones(4, jnp.bfloat16),)],
                         grad_path=False)
        findings, _ = audit_spec("planted.fwd_upcast", spec)
        assert "FT103" not in {f.rule for f in findings}

    def test_hazard_in_second_lowering_is_still_walked(self):
        # with max_lowerings > 1, a hazard living only in the program a
        # LATER sweep point traces must not be masked by the first trace
        def fn(x):
            if hasattr(x, "dtype") and x.ndim == 2:  # 2nd sweep point only
                def body(c, row):
                    y = jax.pure_callback(
                        _host_sin, jax.ShapeDtypeStruct((), jnp.float32),
                        row[0])
                    return c + y, y
                return jax.lax.scan(body, jnp.float32(0.0), x)[0]
            return x.sum()

        spec = AuditSpec(fn=fn, sweep=[(jnp.ones(4),), (jnp.ones((3, 2)),)],
                         max_lowerings=2)
        findings, report = audit_spec("planted.second_lowering", spec)
        assert report["n_lowering_keys"] == 2
        assert "FT104" not in {f.rule for f in findings}  # within contract
        assert "FT102" in {f.rule for f in findings}

    def test_crashing_builder_is_a_loud_ft100(self):
        @hot_entry_point("_test.crash")
        def _crash():
            raise RuntimeError("builder exploded")

        try:
            findings, reports = run_audit(only=["_test.crash"])
            assert [f.rule for f in findings] == ["FT100"]
            assert reports == []
        finally:
            _REGISTRY.pop("_test.crash", None)


class TestSignatureKey:
    def test_weak_type_is_part_of_the_key(self):
        k1 = signature_key(jax.make_jaxpr(lambda x: x + 1)(2.0))
        k2 = signature_key(jax.make_jaxpr(lambda x: x + 1)(jnp.float32(2.0)))
        assert k1 != k2

    def test_shape_and_dtype_are_part_of_the_key(self):
        f = lambda x: x + 1  # noqa: E731
        k = lambda a: signature_key(jax.make_jaxpr(f)(a))  # noqa: E731
        assert k(jnp.ones(3)) != k(jnp.ones(4))
        assert k(jnp.ones(3)) != k(jnp.ones(3, jnp.int32))
        assert k(jnp.ones(3)) == k(jnp.zeros(3))


class TestShippedRegistry:
    def test_registers_at_least_four_hot_entry_points(self):
        entries = load_entry_points()
        assert REQUIRED_ENTRIES <= set(entries), sorted(entries)

    @pytest.mark.parametrize("entry,sweep_len", [
        ("fedavg.round_fn", 3),
        ("fedopt.round_fn", 3),
        ("spmd.block_multiround", 2),
        ("spmd.sharded_eval", 2),
        ("ops.flash_attention_fwd_bwd", 2),
    ])
    def test_shape_sweep_is_one_lowering_key(self, entry, sweep_len):
        """The acceptance assertion: every shipped hot entry point's
        declared shape sweep lowers to exactly ONE signature — round-
        index, cohort and window changes may not fork the jit cache."""
        spec = load_entry_points()[entry]()
        findings, report = audit_spec(entry, spec)
        assert findings == [], [f.format_text() for f in findings]
        assert report["sweep_len"] == sweep_len
        assert report["n_lowering_keys"] == 1
        assert report["n_lowering_keys"] <= report["max_lowerings"]


def _mesh_psum_spec(scale=1.0):
    """A tiny shard_map'd program with one real psum — the planted
    substrate for the collective-signature tests."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), ("clients",))

    def body(x):
        return jax.lax.psum(x * scale, ("clients",))

    n = 8 * len(jax.devices())
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("clients"),
                               out_specs=P()))
    return AuditSpec(fn=fn, sweep=[(jnp.ones(n, jnp.float32),)])


class TestCollectiveSignature:
    def test_psum_is_recorded_with_axes_and_bytes(self):
        findings, report = audit_spec("planted.psum", _mesh_psum_spec())
        assert findings == []
        colls = report["collectives"]
        assert len(colls) == 1
        assert colls[0]["op"] == "psum"
        assert colls[0]["axes"] == ["clients"]
        assert colls[0]["count"] == 1
        assert colls[0]["bytes"] > 0

    def test_collective_free_entry_has_empty_signature(self):
        spec = AuditSpec(fn=lambda x: x * 2,
                         sweep=[(jnp.ones(4, jnp.float32),)])
        _, report = audit_spec("planted.none", spec)
        assert report["collectives"] == []

    def test_missing_baseline_file_is_loud_ft105(self, tmp_path):
        _, report = audit_spec("planted.psum", _mesh_psum_spec())
        findings, stale = check_collective_baseline(
            [report], tmp_path / "absent.json")
        assert [f.rule for f in findings] == ["FT105"]
        assert "MISSING" in findings[0].message

    def test_round_trip_matches_then_rogue_collective_is_ft105(
            self, tmp_path):
        _, clean = audit_spec("planted.entry", _mesh_psum_spec())
        bl = tmp_path / "coll.json"
        write_collective_baseline(bl, [clean])
        findings, stale = check_collective_baseline([clean], bl)
        assert findings == [] and stale == []
        # the rogue: the same entry grows an all_gather the baseline
        # never sanctioned
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()), ("clients",))

        def rogue_body(x):
            g = jax.lax.all_gather(x, "clients")
            return jax.lax.psum(x, ("clients",)) + g.sum()

        n = 8 * len(jax.devices())
        # check_vma off: a gathered sum is typed device-varying, which
        # out_specs=P() would reject; off, psum also traces under its
        # plain name, so both spellings must land on one baseline key
        rogue = AuditSpec(fn=jax.jit(jax.shard_map(
            rogue_body, mesh=mesh, in_specs=P("clients"), out_specs=P(),
            check_vma=False)),
            sweep=[(jnp.ones(n, jnp.float32),)])
        _, rep = audit_spec("planted.entry", rogue)
        findings, _ = check_collective_baseline([rep], bl)
        assert [f.rule for f in findings] == ["FT105"]
        assert "all_gather" in findings[0].message
        assert "NEW collective" in findings[0].message

    def test_bytes_drift_within_tolerance_is_clean(self, tmp_path):
        # the tolerance must actually tolerate: same op/axes/count with
        # a small bytes delta (fingerprint mismatch) is NOT a finding
        _, clean = audit_spec("planted.entry", _mesh_psum_spec())
        bl = tmp_path / "coll.json"
        tweaked = json.loads(json.dumps(clean))  # deep copy
        tweaked["collectives"][0]["bytes"] = int(
            tweaked["collectives"][0]["bytes"] * 1.2)
        write_collective_baseline(bl, [tweaked])
        findings, stale = check_collective_baseline([clean], bl)
        assert findings == [], [f.format_text() for f in findings]
        assert stale == []

    def test_bytes_drift_beyond_tolerance_is_ft106(self, tmp_path):
        _, clean = audit_spec("planted.entry", _mesh_psum_spec())
        bl = tmp_path / "coll.json"
        write_collective_baseline(bl, [clean])
        # same op/axes/count, 4x the bytes (psum over a 4x-wider array)
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()), ("clients",))

        def body(x):
            return jax.lax.psum(
                jnp.tile(x, 4).reshape(4, -1), ("clients",))

        n = 8 * len(jax.devices())
        fat = AuditSpec(fn=jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("clients"), out_specs=P())),
            sweep=[(jnp.ones(n, jnp.float32),)])
        _, rep = audit_spec("planted.entry", fat)
        findings, _ = check_collective_baseline([rep], bl)
        assert [f.rule for f in findings] == ["FT106"]
        assert "bytes estimate drifted" in findings[0].message

    def test_uncovered_entry_is_ft105_and_dead_entry_is_stale(
            self, tmp_path):
        _, rep = audit_spec("planted.entry", _mesh_psum_spec())
        bl = tmp_path / "coll.json"
        other = dict(rep, entry="planted.retired")
        write_collective_baseline(bl, [other])
        findings, stale = check_collective_baseline([rep], bl)
        assert [f.rule for f in findings] == ["FT105"]
        assert "no collective-baseline entry" in findings[0].message
        assert stale == ["planted.retired"]


class TestShippedCollectiveBaseline:
    def test_covers_every_registered_entry_and_matches(self):
        # the acceptance bar: the checked-in baseline covers EVERY
        # registered hot entry point and the current tree matches it
        findings, reports = run_audit()
        assert findings == [], [f.format_text() for f in findings]
        coll_findings, stale = check_collective_baseline(
            reports, REPO / "ci" / "collective_baseline.json")
        assert coll_findings == [], [f.format_text()
                                     for f in coll_findings]
        assert stale == []
        baseline = json.loads(
            (REPO / "ci" / "collective_baseline.json").read_text())
        assert set(baseline["entries"]) == {r["entry"] for r in reports}

    def test_spmd_entries_pin_their_psums(self):
        baseline = json.loads(
            (REPO / "ci" / "collective_baseline.json").read_text())
        block = baseline["entries"]["spmd.block_multiround"]
        assert any(c["op"] == "psum" and c["axes"] == ["clients"]
                   for c in block["collectives"])
        ev = baseline["entries"]["spmd.sharded_eval"]
        assert any(c["op"] == "psum" for c in ev["collectives"])
