"""Driver-contract regression guard: __graft_entry__.entry() must stay
jittable and dryrun_multichip must keep executing all four parallelism
modes on the 8-device CPU mesh (the driver runs these out-of-band; a
break would otherwise surface only at round end)."""
import sys

import numpy as np

import jax


def _entry_module():
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g

    return g


def test_entry_compiles_single_chip():
    g = _entry_module()
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 10)
    assert np.isfinite(np.asarray(out)).all()


def test_dryrun_multichip_8(capsys):
    g = _entry_module()
    g.dryrun_multichip(8)
    out = capsys.readouterr().out
    assert "dp loss=" in out
    assert "dp4xpp2 1F1B" in out
    assert "dp4xmp2 TP" in out
    assert "GPT dp2xpp2xmp2 +zero1+gm2" in out
    assert "ep8 MoE" in out
    assert "sp8 ring attention" in out
    # state cleaned up for subsequent tests
    from paddle_tpu.distributed import comm

    assert comm.hybrid_mesh() is None


import pytest  # noqa: E402


@pytest.mark.slow
def test_dryrun_multichip_32():
    """Pod-scale factorings (ISSUE 6 / ROADMAP 3): dp8 x mp2 x pp2 and the
    32-device sharded-flash dp16 x mp2 step, each phase printing its
    compile_s stamp (for a person reading the dryrun: no tool reads it).
    Subprocess: the in-process harness is pinned to 8 virtual devices."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # dryrun forces its own device count
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, '/root/repo'); "
         "import __graft_entry__ as g; g.dryrun_multichip(32)"],
        capture_output=True, text=True, timeout=1200, env=env,
        cwd="/root/repo",
    )
    assert p.returncode == 0, p.stdout + p.stderr
    assert "GPT dp8xpp2xmp2" in p.stdout
    assert "sharded-flash dp16xmp2" in p.stdout
    assert p.stdout.count("compile_s=") >= 2
