"""Kill-and-resume parity for the spmd and cross-silo backends: a run checkpointed at round k and restarted must
produce bit-identical final weights to an uninterrupted run, because client
sampling and all client RNG derive from (seed, round_idx)."""

import jax
import numpy as np
import pytest

from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.trainer.functional import TrainConfig
from fedml_tpu.utils.checkpoint import CheckpointManager


@pytest.fixture(scope="module")
def federation(small_dataset):
    return small_dataset


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestSpmdResume:
    def _api(self, ds, comm_round):
        from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                             DistributedFedAvgConfig)
        return DistributedFedAvgAPI(
            ds, LogisticRegression(num_classes=ds.class_num),
            config=DistributedFedAvgConfig(
                comm_round=comm_round, client_num_per_round=4,
                frequency_of_the_test=10,
                train=TrainConfig(epochs=1, batch_size=8, lr=0.1)))

    def test_resume_is_bit_identical(self, federation, tmp_path):
        ds = federation
        # uninterrupted 4-round run
        full = self._api(ds, 4)
        full.train()

        # "killed" after round 2: checkpoints exist for rounds 1 and 2
        mgr = CheckpointManager(str(tmp_path / "ck"))
        first = self._api(ds, 2)
        first.train(checkpoint_mgr=mgr)
        assert mgr.latest_round() == 2

        # fresh process: new API, resume from the latest checkpoint
        resumed = self._api(ds, 4)
        resumed.train(checkpoint_mgr=mgr, resume=True)
        _tree_equal(resumed.variables, full.variables)
        assert mgr.latest_round() == 4

    def test_resume_without_checkpoint_starts_fresh(self, federation,
                                                    tmp_path):
        mgr = CheckpointManager(str(tmp_path / "empty"))
        api = self._api(federation, 1)
        api.train(checkpoint_mgr=mgr, resume=True)  # no checkpoint yet: ok
        assert mgr.latest_round() == 1


class TestKillMidRun:
    def test_sigkill_then_resume_completes(self, tmp_path):
        """Hard-kill a checkpointing cross-silo run mid-flight (SIGKILL, no
        cleanup), then rerun with --resume: the federation finishes from the
        last complete checkpoint (atomic tmp+rename writes guarantee no torn
        state)."""
        import os
        import signal
        import subprocess
        import sys
        import time

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ckdir = str(tmp_path / "ck")
        flags = ["--dataset", "blob", "--model", "lr", "--backend", "inproc",
                 "--client_num_in_total", "4", "--client_num_per_round", "2",
                 "--comm_round", "40", "--epochs", "1", "--batch_size", "8",
                 "--checkpoint_dir", ckdir,
                 "--run_dir", str(tmp_path / "runs")]
        code = ("import sys;"
                "from fedml_tpu.experiments.main_fedavg import main;"
                "main(sys.argv[1:])")
        args = [sys.executable, "-c", code] + flags
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

        proc = subprocess.Popen(args, cwd=repo, env=env,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        # wait until at least one round checkpointed, then SIGKILL
        deadline = time.time() + 120
        mgr = CheckpointManager(ckdir)
        while time.time() < deadline:
            if proc.poll() is not None:
                pytest.fail("run finished before it could be killed; "
                            "raise comm_round")
            if (mgr.latest_round() or 0) >= 1:
                break
            time.sleep(0.2)
        else:
            proc.kill()
            pytest.fail("no checkpoint appeared within 120s")
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        killed_at = mgr.latest_round()
        assert killed_at is not None and killed_at < 40

        out = subprocess.run(args + ["--resume"], cwd=repo, env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert mgr.latest_round() == 40


class TestCrossSiloResume:
    def _run(self, ds, comm_round, checkpoint_dir=None, resume=False):
        from fedml_tpu.algorithms.fedavg_cross_silo import (
            run_fedavg_cross_silo)
        return run_fedavg_cross_silo(
            ds, LogisticRegression(num_classes=ds.class_num),
            worker_num=2, comm_round=comm_round,
            train_cfg=TrainConfig(epochs=1, batch_size=8, lr=0.1),
            backend="INPROC", checkpoint_dir=checkpoint_dir, resume=resume)

    def test_resume_is_bit_identical(self, federation, tmp_path):
        ds = federation
        full_model, _ = self._run(ds, 4)

        ckdir = str(tmp_path / "silo_ck")
        self._run(ds, 2, checkpoint_dir=ckdir)
        assert CheckpointManager(ckdir).latest_round() == 2

        resumed_model, history = self._run(ds, 4, checkpoint_dir=ckdir,
                                           resume=True)
        _tree_equal(resumed_model, full_model)
        # the resumed protocol ran only rounds 2..3
        assert [h["round"] for h in history] == [2, 3]

    def test_resume_of_finished_run_is_noop(self, federation, tmp_path):
        ds = federation
        ckdir = str(tmp_path / "done_ck")
        model_a, _ = self._run(ds, 2, checkpoint_dir=ckdir)
        model_b, history = self._run(ds, 2, checkpoint_dir=ckdir,
                                     resume=True)
        _tree_equal(model_a, model_b)
        assert history == []


class TestModelParallelResume:
    def test_fsdp_spmd_resume_is_bit_identical(self, tmp_path):
        """Resume with --model_parallel fsdp: checkpoint restore hands back
        host arrays; the jit's in_shardings must re-place them into the
        ZeRO layout and continue bit-identically."""
        from fedml_tpu.data.synthetic import make_blob_federated
        from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                             DistributedFedAvgConfig)

        ds = make_blob_federated(client_num=4, dim=128, class_num=16,
                                 n_samples=1024, seed=5)

        def api(comm_round):
            return DistributedFedAvgAPI(
                ds, LogisticRegression(num_classes=16),
                config=DistributedFedAvgConfig(
                    comm_round=comm_round, client_num_per_round=4,
                    frequency_of_the_test=10, model_parallel="fsdp",
                    mp_size=2,
                    train=TrainConfig(epochs=1, batch_size=32, lr=0.1)))

        full = api(4)
        full.train()

        mgr = CheckpointManager(str(tmp_path / "ck"))
        api(2).train(checkpoint_mgr=mgr)
        resumed = api(4)
        resumed.train(checkpoint_mgr=mgr, resume=True)
        _tree_equal(resumed.variables, full.variables)
        kernel = resumed.variables["params"]["Dense_0"]["kernel"]
        assert kernel.addressable_shards[0].data.size == kernel.size // 2
