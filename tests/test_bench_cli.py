"""bench.py CLI: stage selection, the suite table it indexes, and the
exit code — a failed device probe or a failing stage must fail the run
while the rows of the stages that did run are still printed. Pure
host-side logic, no device."""

import json

import pytest

import bench


def test_stage_table_keys_unique_and_ordered():
    keys = [key for key, _, _, _ in bench._STAGES]
    assert len(keys) == len(set(keys))
    # the suite order is heaviest-evidence-first contract: headline
    # before the long tail
    assert keys[0] == "fedavg_femnist_cnn"


def test_selection_none_without_flag():
    assert bench._parse_stage_selection(["bench.py"]) is None


def test_selection_by_key_and_alias():
    got = bench._parse_stage_selection(["--stages=resnet,flash"])
    assert got == {"resnet18_gn_fedcifar100", "transformer_flash_s2048"}
    got = bench._parse_stage_selection(
        ["--stages=fedavg_powerlaw_1000,tta_mnist"])
    assert got == {"fedavg_powerlaw_1000", "time_to_target_mnist_lr"}


def test_selection_smoke_alias():
    assert bench._parse_stage_selection(["--stages=smoke"]) == {"smoke_chip"}


def test_selection_rejects_unknown_token():
    with pytest.raises(SystemExit):
        bench._parse_stage_selection(["--stages=resnet,nope"])


def test_every_alias_resolves():
    for key, _, _, aliases in bench._STAGES:
        for alias in aliases:
            assert bench._parse_stage_selection([f"--stages={alias}"]) == \
                {key}, alias


def _drive_main(tmp_path, monkeypatch, capsys, probe, stages, argv):
    """bench.main() end to end with a faked probe + stage table; returns
    (exit code, the contract line)."""
    import sys

    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(bench, "_ON_TPU", None)  # main() stores the probe's
    monkeypatch.setattr(bench, "_probe_device", lambda timeout_s=0: probe)
    monkeypatch.setattr(bench, "_STAGES", stages)
    monkeypatch.setattr(bench, "bench_torch_baseline", lambda: 1.0)
    monkeypatch.setattr(sys, "argv", ["bench.py", *argv])
    rc = bench.main()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    with open("runs/bench_details.json") as f:
        assert json.load(f)["run_id"] == line["run_id"]
    return rc, line


_CPU_PROBE = {"tpu": False, "backend": "cpu", "device": "cpu", "count": 1}


def test_stages_selection_runs_only_selected(tmp_path, monkeypatch, capsys):
    ran = []
    stages = (
        ("resnet18_gn_fedcifar100", "resnet",
         lambda: ran.append("resnet") or {"rounds_per_sec": 2.0},
         ("resnet",)),
        ("fedavg_powerlaw_1000", "powerlaw",
         lambda: ran.append("powerlaw") or {"rounds_per_sec": 3.0},
         ("powerlaw",)),
    )
    rc, line = _drive_main(tmp_path, monkeypatch, capsys, _CPU_PROBE,
                           stages, ["--stages=resnet"])
    assert rc == 0
    assert ran == ["resnet"]  # powerlaw not selected, smoke not run
    row = line["extra"]["resnet18_gn_fedcifar100"]
    assert row["rounds_per_sec"] == 2.0 and row["host"] == "cpu-smoke"
    assert "fedavg_powerlaw_1000" not in line["extra"]
    with open("runs/bench_partial.json") as f:
        assert set(json.load(f)) >= {"resnet18_gn_fedcifar100"}


def test_failed_probe_exits_nonzero_and_carries_nothing(
        tmp_path, monkeypatch, capsys):
    # no device, no measurement: rows an earlier session left on disk
    # must not come back as this run's headline
    (tmp_path / "runs").mkdir()
    (tmp_path / "runs" / "bench_partial.json").write_text(json.dumps({
        "fedavg_femnist_cnn": {"rounds_per_sec": 7.0, "host": "tpu:x"}}))
    ran = []
    stages = (("fedavg_femnist_cnn", "cnn",
               lambda: ran.append("cnn") or {"rounds_per_sec": 1.0}, ()),)
    rc, line = _drive_main(tmp_path, monkeypatch, capsys,
                           {"error": "probe hung"}, stages, [])
    assert rc != 0
    assert ran == []
    assert line["value"] == 0.0
    assert line["extra"] == {"error": "probe hung"}


def test_cpu_nobody_asked_for_fails_the_probe(tmp_path, monkeypatch, capsys):
    # when the TPU runtime fails to initialise JAX hands back CpuDevice;
    # with JAX_PLATFORMS unset that is no choice, so the REAL probe child
    # (the one backend rule, utils.on_tpu) must fail and bench.py with it
    monkeypatch.delenv("JAX_PLATFORMS")
    info = bench._probe_device(120)
    assert info.get("backend") != "cpu", info
    if "error" not in info:  # a machine with a chip: nothing fell back
        return
    assert "without being asked" in info["error"]
    ran = []
    stages = (("fedavg_femnist_cnn", "cnn",
               lambda: ran.append("cnn") or {"rounds_per_sec": 1.0}, ()),)
    rc, line = _drive_main(tmp_path, monkeypatch, capsys, info, stages, [])
    assert rc != 0 and ran == []
    assert not (tmp_path / "runs" / "bench_partial.json").exists()


def test_raising_stage_exits_nonzero_with_other_rows_printed(
        tmp_path, monkeypatch, capsys):
    def boom():
        raise RuntimeError("kernel refused")

    stages = (
        ("fedavg_femnist_cnn", "cnn", lambda: {"rounds_per_sec": 5.0},
         ("cnn",)),
        ("resnet18_gn_fedcifar100", "resnet", boom, ("resnet",)),
        ("fedavg_powerlaw_1000", "powerlaw",
         lambda: {"rounds_per_sec": 3.0}, ("powerlaw",)),
    )
    rc, line = _drive_main(tmp_path, monkeypatch, capsys, _CPU_PROBE,
                           stages, ["--stages=cnn,resnet,powerlaw"])
    assert rc != 0
    extra = line["extra"]
    assert "kernel refused" in extra["resnet18_gn_fedcifar100"]["error"]
    # the stages before AND after the failure still ran and printed
    assert line["value"] == 5.0
    assert extra["fedavg_powerlaw_1000"]["rounds_per_sec"] == 3.0
    assert "host" not in extra["resnet18_gn_fedcifar100"]


def test_child_process_stage_runs_first_with_the_probes_answer(
        tmp_path, monkeypatch, capsys):
    # one process per chip: a stage whose legs are children that open
    # the device runs before any in-process stage, sized by the probe
    order = []
    stages = (
        ("fedavg_femnist_cnn", "cnn",
         lambda: order.append("cnn") or {"rounds_per_sec": 5.0}, ("cnn",)),
        ("population_scale", "population_scale",
         lambda: order.append(("population", bench._is_tpu()))
         or {"legs": {}},
         ("population",)),
    )
    rc, _ = _drive_main(tmp_path, monkeypatch, capsys, _CPU_PROBE,
                        stages, ["--stages=cnn,population"])
    assert rc == 0
    assert order == [("population", False), "cnn"]


def test_roofline_math():
    # FEMNIST-CNN-like figures: 16 GFLOP round, 8 GB touched, v5e chip
    r = bench._roofline(flops=16e9, bytes_acc=8e9,
                        peak=197e12, bw=819e9)
    assert r["memory_bound"] is True  # AI=2 << ridge=240.5
    assert r["arithmetic_intensity_flop_per_byte"] == 2.0
    assert abs(r["ridge_flop_per_byte"] - 240.54) < 0.01
    # ceiling = AI*BW/peak = 2*819e9/197e12 ~ 0.83%
    assert abs(r["mfu_ceiling_at_measured_ai"] - 0.0083) < 5e-4
    # compute-bound case caps at 1.0
    r2 = bench._roofline(flops=1e12, bytes_acc=1e9,
                         peak=197e12, bw=819e9)
    assert r2["memory_bound"] is False
    assert r2["mfu_ceiling_at_measured_ai"] == 1.0
    # unavailable inputs -> None
    assert bench._roofline(float("nan"), 1.0, 1.0, 1.0) is None
    assert bench._roofline(1.0, 0.0, 1.0, 1.0) is None
