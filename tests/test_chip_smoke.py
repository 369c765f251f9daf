"""chip_smoke.py on the CPU: the script itself must refuse to pass here,
its legs must run tiny with the kernels interpreted, and the two helpers it
leans on — the one backend rule and the compile-cache placement — must hold
their contracts."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

sys.path.remove(REPO)


def test_script_fails_without_a_tpu_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout
    assert "'cpu'" in proc.stderr
    # no result line: nothing on stdout parses as the summary object
    for line in proc.stdout.splitlines():
        assert not line.startswith("{"), line


def test_last_stdout_line_is_the_result_and_nothing_else(
        monkeypatch, tmp_path, capsys):
    """Whoever checks the smoke parses the last line of stdout alone: one
    JSON object with exactly ``ok`` and ``device`` {platform, kind, count}.
    ``main()`` with the device faked and the legs stubbed (they run for
    real, tiny, in the tests below) must end on that line, with the full
    report on the line before it and in ``summary.json``."""
    import fedml_tpu.utils as utils

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    monkeypatch.setattr(chip_smoke, "device_report", lambda: dict(device))
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    monkeypatch.setenv("FEDML_GEN_CACHE", "")  # main() sets it; undone here
    monkeypatch.setattr(utils, "enable_persistent_compilation_cache",
                        lambda: str(tmp_path / "cache"))
    monkeypatch.setattr(chip_smoke, "load_federation",
                        lambda *a, **k: (None, "m", "t"))
    monkeypatch.setattr(chip_smoke, "model_shapes",
                        lambda *a, **k: {"w": jax.ShapeDtypeStruct(
                            (8, 128), jax.numpy.float32)})
    monkeypatch.setattr(chip_smoke, "peak_memory", dict)
    leg_failures = []
    monkeypatch.setattr(
        chip_smoke, "train_leg", lambda kind, *a, **k: (
            {"first_round_s": 1.0, "later_round_s": [], "devices": {},
             "failures": list(leg_failures)}, {"w": jax.numpy.ones(3)}))
    monkeypatch.setattr(chip_smoke, "parity_leg",
                        lambda *a, **k: {"failures": []})
    monkeypatch.setattr(chip_smoke, "kernel_leg",
                        lambda **k: {"checks": [], "failures": []})

    for failures, code in (([], 0), (["boom"], 1)):
        leg_failures[:] = failures
        assert chip_smoke.main() == code
        lines = capsys.readouterr().out.splitlines()
        assert json.loads(lines[-1]) == {"ok": code == 0, "device": device}
        assert lines[-2].startswith("chip_smoke: report {")
        report = json.loads(lines[-2].split("report ", 1)[1])
        assert report == json.load(open(tmp_path / "summary.json"))
        assert report["ok"] is (code == 0) and "legs" in report


@pytest.fixture(scope="module")
def tiny_federation():
    return chip_smoke.load_federation("mnist_gen", clients=12)


def test_train_legs_tiny_on_the_forced_host_mesh(tiny_federation, tmp_path):
    from fedml_tpu.experiments.flagship_scale import param_rel_err

    ds, model_name, task = tiny_federation
    finals = {}
    for kind in ("sim", "spmd"):
        report, finals[kind] = chip_smoke.train_leg(
            kind, ds, model_name, task, rounds=3, per_round=8,
            batch_size=10, out_dir=str(tmp_path))
        assert report["failures"] == [], report
        assert report["first_round_s"] > 0
        assert len(report["later_round_s"]) == 2
        assert report["param_change_rel"] > 0
        rows = [json.loads(l) for l in
                open(tmp_path / f"{kind}_history.jsonl")]
        assert [r["round"] for r in rows] == [0, 1, 2]
        if kind == "spmd":
            everyone = sorted(d.id for d in jax.devices())
            assert len(everyone) == 8
            assert report["devices"] == {"cohort": everyone,
                                         "model": everyone}
        else:
            # the one backend rule: no compiled kernel on cpu
            assert report["aggregation"] == "jnp"
    assert param_rel_err(finals["sim"], finals["spmd"]) < 1e-5


def test_parity_leg_tiny_and_its_tolerance(tiny_federation, tmp_path):
    ds, model_name, task = tiny_federation
    out = chip_smoke.parity_leg(ds, model_name, task, per_round=8,
                                batch_size=10, out_dir=str(tmp_path))
    assert out["failures"] == [], out
    assert out["sim_spmd_param_rel_err"] < out["sim_spmd_param_rel_err_tol"]
    assert out["sim_spmd_param_rel_err_tol"] == pytest.approx(
        chip_smoke.PARITY_FRACTION * out["param_change_rel"])
    # a driver that did not train is as far away as training moved; an
    # aggregation rounded to bf16 is off by ~2^-9 of the parameters,
    # 0.3 of the 6.4e-3 one ResNet-18-GN round moved them on the v5e
    assert chip_smoke.parity_check(6.4e-3, 6.4e-3)["failures"]
    assert chip_smoke.parity_check(2.0 ** -9, 6.4e-3)["failures"]
    assert chip_smoke.parity_check(float("nan"), 6.4e-3)["failures"]
    # a change measured from an init that is not the API's (~1.4) must not
    # turn into a tolerance everything passes
    assert chip_smoke.parity_check(1e-5, 1.4)["failures"]
    assert not chip_smoke.parity_check(1e-5, 6.4e-3)["failures"]


def test_kernel_leg_tiny_interpreted():
    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jax.numpy.float32)

    out = chip_smoke.kernel_leg(
        cohorts=((3, {"conv": leaf(3, 3, 8, 128), "bias": leaf(128)}),
                 (5, {"fc": leaf(72, 128), "head": leaf(128, 62)})),
        dims=(5000, 4096), topk_frac=0.05,
        attn_shape=(1, 256, 2, 32), block_grid=((128, 128), (256, 128)))
    assert out["interpreted"] is True
    assert out["failures"] == [], out["failures"]
    kernels = {c["kernel"] for c in out["checks"]}
    assert [(c["shape"], c["kernel_params"]) for c in out["checks"][:2]] == [
        ([3, 9344], 9216), ([5, 17152], 9216)]
    assert kernels == {"aggregate.tree_weighted_mean_pallas",
                       "quantize.int8_round_trip",
                       "sparsify.topk_int8_round_trip",
                       "sparsify.topk_rebuild", "sparsify.topk_support",
                       "flash_attention.fwd", "flash_attention.bwd",
                       "flash_attention.fwd_rows",
                       "flash_attention.bwd_rows"}
    # 2 dims x 5 checks + 2 block pairs x (fwd, bwd) x (element, row norm)
    assert len(out["checks"]) == 18
    # interpreted, the default precision is f32: the XLA attention sits on
    # the oracle and the kernel is held to the f32 tolerances
    for c in out["checks"]:
        if c["kernel"].startswith("flash_attention.fwd"):
            assert c["xla_default_err"] < 1e-6
            assert c["tol"] == chip_smoke.FLASH_F32_TOL[0]
        if c["kernel"].startswith("flash_attention.bwd"):
            assert max(c["xla_default_dq_dk_dv_err"]) < 1e-6
            assert c["tol"] == chip_smoke.FLASH_F32_TOL[1]


def test_attention_row_norm_sees_what_the_element_norm_cannot():
    # a tile edge mis-masked by one key in the late rows: a small absolute
    # error (late rows average many keys), far under what one bf16 pass
    # costs the first rows on a TPU (3e-3 of max|ref| on the v5e) — the
    # row norm past the first positions reads it several times larger
    import jax.numpy as jnp
    from fedml_tpu.parallel.sequence import reference_attention

    b, s, h, d = 1, 1024, 2, 32
    q, k, v = (jax.random.normal(key, (b, s, h, d), jnp.float32)
               for key in jax.random.split(jax.random.key(0), 3))
    want = reference_attention(q, k, v, causal=True)
    pos = jnp.arange(s)
    edge = (pos % 128 == 0) & (pos >= s // 2)
    mask = pos[None, :] <= pos[:, None] + edge[:, None]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(1.0 * d)
    p = jax.nn.softmax(jnp.where(mask[None, None], scores, -1e30), axis=-1)
    bad = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    element, rows = chip_smoke.attention_errors(bad, want)
    assert rows > 4 * element
    assert rows > 0.02
    # the first positions are the element norm's: an error there does not
    # reach the row norm
    first = want.at[:, 0].add(1.0)
    element, rows = chip_smoke.attention_errors(first, want)
    assert element > 0.1 and rows == 0.0


class TestBackendRule:
    def test_cpu_is_not_tpu(self):
        from fedml_tpu.utils import on_tpu
        assert on_tpu() is False

    def test_tpu_is_tpu(self, monkeypatch):
        from fedml_tpu.utils import on_tpu
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert on_tpu() is True

    @pytest.mark.parametrize("asked", [None, "", "tpu,cpu"])
    def test_a_cpu_nobody_asked_for_is_an_error(self, monkeypatch, asked):
        # a TPU runtime that fails to initialise leaves JAX on CpuDevice:
        # only JAX_PLATFORMS=cpu makes the cpu backend a choice
        from fedml_tpu.trainer.functional import TrainConfig, make_local_train
        from fedml_tpu.utils import on_tpu
        monkeypatch.setattr(type(jax.config), "jax_platforms",
                            property(lambda self: asked))
        with pytest.raises(RuntimeError, match="without being asked"):
            on_tpu()
        # ...and no trainer is built on it, whichever driver asks
        with pytest.raises(RuntimeError, match="without being asked"):
            make_local_train(None, "classification", TrainConfig())

    def test_any_other_backend_is_an_error(self, monkeypatch):
        from fedml_tpu.comm.compression import _resolve_interpret
        from fedml_tpu.ops.autotune import device_kind
        from fedml_tpu.utils import on_tpu
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="gpu"):
            on_tpu()
        # ...everywhere it used to be a silent choice
        with pytest.raises(RuntimeError, match="gpu"):
            _resolve_interpret(None)
        with pytest.raises(RuntimeError, match="gpu"):
            device_kind()


class TestCompileCachePlacement:
    @pytest.fixture
    def recorded(self, monkeypatch):
        """jax.config.update calls made by the helper (none applied)."""
        calls = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.__setitem__(k, v))
        return calls

    def test_env_var_places_it_and_the_helper_keeps_off(
            self, monkeypatch, recorded, tmp_path):
        from fedml_tpu.utils import enable_persistent_compilation_cache
        target = str(tmp_path / "never_created")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
        assert enable_persistent_compilation_cache() == target
        assert "jax_compilation_cache_dir" not in recorded
        assert not os.path.exists(target)  # the directory is JAX's business
        # only the persist-everything thresholds
        assert recorded == {
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1}

    def test_unset_means_the_checkout(self, monkeypatch, recorded):
        from fedml_tpu.utils import enable_persistent_compilation_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert enable_persistent_compilation_cache() == want
        assert recorded["jax_compilation_cache_dir"] == want

    def test_every_launcher_calls_the_helper(self):
        """Source-level wiring guard: every launcher routes through the
        ONE helper, with no argument to hand it another directory."""
        launchers = ["bench.py", "chip_smoke.py"] + [
            os.path.join("fedml_tpu", *p) for p in (
                ("experiments", "fed_launch.py"),
                ("experiments", "main_fedavg.py"),
                ("experiments", "flagship_scale.py"),
                ("experiments", "virtualization_stress.py"),
                ("parallel", "mesh.py"), ("sched", "__main__.py"),
                ("serve", "__main__.py"), ("state", "population.py"))]
        for rel in launchers:
            with open(os.path.join(REPO, rel)) as f:
                assert "enable_persistent_compilation_cache()" in f.read(), rel
