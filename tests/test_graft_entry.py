"""Driver-contract regression tests for __graft_entry__.py.

The driver compile-checks ``entry()`` single-chip and runs
``dryrun_multichip(n)`` with n virtual CPU devices. A wiring bug here
once zeroed all multi-chip evidence; these tests keep the contract pinned
from inside the suite.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestEntry:
    def test_entry_compiles_and_runs(self):
        import jax

        sys.path.insert(0, REPO)
        try:
            import __graft_entry__ as g
        finally:
            sys.path.remove(REPO)
        fn, args = g.entry()
        out = jax.jit(fn)(*args)
        assert out.shape == (20, 62)


@pytest.mark.slow
class TestDryrun:
    def test_dryrun_multichip_from_hostile_env(self):
        """The driver's exact failure mode: call dryrun_multichip via
        import from a process whose own platform CANNOT satisfy it (we
        simulate with a 1-device CPU parent). The subprocess re-exec must
        deliver n=2 regardless."""
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # parent: single CPU device only
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        code = (
            "import jax\n"
            "assert len(jax.devices()) == 1\n"
            "import __graft_entry__ as g\n"
            "g.dryrun_multichip(2)\n"
            "print('hostile-env dryrun ok')\n"
        )
        # longer than _reexec_dryrun's inner 1200s timeout so its
        # diagnostic RuntimeError (with output tails) fires first
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              cwd=REPO, capture_output=True, text=True,
                              timeout=1500)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "hostile-env dryrun ok" in proc.stdout
