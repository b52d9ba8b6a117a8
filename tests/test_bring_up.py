"""Bring-up contracts (ISSUE 21): what keeps a run honest about the chip.

Each case runs in a fresh interpreter — the properties are about what a
process does BEFORE and WHILE it first touches jax, which the suite's own
process (conftest has long forced the CPU backend) cannot show.
"""
import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TEST_PATH = os.pathsep.join([REPO, os.path.join(REPO, "tests")])


def _py(code, env=None, args=(), timeout=120):
    """Run `code` in a fresh interpreter from the repo root; the base
    environment pins the CPU backend and carries no cache placement."""
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    base.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    base.update(env or {})
    return subprocess.run(
        [sys.executable, "-c", code, *args], cwd=REPO, env=base,
        capture_output=True, text=True, timeout=timeout)


_CACHE_PROBE = """
import json, jax, paddle_tpu
from paddle_tpu.core import compile_cache
print(json.dumps([jax.config.jax_compilation_cache_dir,
                  compile_cache.cache_dir]))
"""


class TestCompileCachePlacement:
    def test_env_places_the_cache(self, tmp_path):
        """With JAX_COMPILATION_CACHE_DIR set the program names no
        directory of its own and makes none."""
        where = str(tmp_path / "cache_from_env")
        r = _py(_CACHE_PROBE, env={"JAX_COMPILATION_CACHE_DIR": where})
        assert r.returncode == 0, r.stderr
        assert json.loads(r.stdout.splitlines()[-1]) == [where, where]
        assert not os.path.exists(where)  # jax creates it on first write

    def test_default_is_one_fixed_path_in_the_checkout(self):
        outs = [_py(_CACHE_PROBE) for _ in range(2)]
        for r in outs:
            assert r.returncode == 0, r.stderr
        a, b = (json.loads(r.stdout.splitlines()[-1]) for r in outs)
        assert a == b == [os.path.join(REPO, ".jax_cache")] * 2


class TestOneProcessPerChip:
    def test_import_initialises_no_backend(self):
        """The launcher, ElasticManager, monitor and fleet controller
        start the children that own the chip: importing them (or the
        package) must not take it."""
        r = _py("import paddle_tpu\n"
                "import paddle_tpu.distributed.launch\n"
                "import paddle_tpu.distributed.elastic\n"
                "import paddle_tpu.distributed.fleet_controller\n"
                "import paddle_tpu.observability.monitor\n"
                "from jax._src import xla_bridge\n"
                "assert not xla_bridge._backends, xla_bridge._backends\n")
        assert r.returncode == 0, r.stderr

    # the parents below drop JAX_PLATFORMS from their own environment
    # first, so a child that sees it got it from the code under test

    def test_spawn_child_is_pinned_before_jax(self, tmp_path):
        out = tmp_path / "spawn.json"
        r = _py("import os, sys\n"
                "os.environ.pop('JAX_PLATFORMS')\n"
                "from paddle_tpu.distributed.spawn import spawn\n"
                "from helpers.child_env_probe import report\n"
                "spawn(report, (sys.argv[1],), nprocs=1, backend='cpu')\n"
                "assert 'JAX_PLATFORMS' not in os.environ\n",
                env={"PYTHONPATH": _TEST_PATH}, args=(str(out),))
        assert r.returncode == 0, r.stderr
        assert json.loads(out.read_text()) == {
            "env": "cpu", "jax_platforms": "cpu"}

    def test_dataloader_worker_is_pinned_before_jax(self):
        r = _py("import json, os\n"
                "os.environ.pop('JAX_PLATFORMS')\n"
                "import jax\n"
                "jax.config.update('jax_platforms', 'cpu')\n"
                "from paddle_tpu.io import DataLoader\n"
                "from helpers.child_env_probe import ProbeDataset\n"
                "dl = DataLoader(ProbeDataset(), batch_size=2,\n"
                "                num_workers=2, use_shared_memory=True)\n"
                "rows = {tuple(int(v) for v in b.numpy().ravel())"
                " for b in dl}\n"
                "assert dl._pool_is_proc\n"
                "assert 'JAX_PLATFORMS' not in os.environ\n"
                "print(json.dumps(sorted(rows)))\n",
                env={"PYTHONPATH": _TEST_PATH})
        assert r.returncode == 0, r.stderr
        # every sample: (env pinned, jax captured cpu at its import)
        assert json.loads(r.stdout.splitlines()[-1]) == [[1, 1, 1, 1]]


class TestNoSilentFallback:
    def test_chip_smoke_fails_fast_without_a_tpu(self):
        r = subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=60)
        assert r.returncode not in (0, None)
        assert "no TPU" in r.stderr
        assert '"ok"' not in r.stdout

    def test_place_index_out_of_range_raises(self):
        from paddle_tpu.core.device import Place

        with pytest.raises(RuntimeError):
            Place("tpu", 99).jax_device()
        with pytest.raises(RuntimeError, match="only 8 cpu"):
            Place("cpu", 99).jax_device()

    def test_tpu_params_rejects_a_bad_argument(self):
        from paddle_tpu.ops.pallas.flash_attention import _tpu_params

        assert _tpu_params("parallel", "arbitrary").dimension_semantics \
            == ("parallel", "arbitrary")
        with pytest.raises(ValueError, match="dimension_semantics"):
            _tpu_params("parallel", "sequential")


_SMOKE_TRAINER = """
import json, sys
import chip_smoke
model, _, step = chip_smoke._build_trainer(2)
ids, labels = chip_smoke._batch(2, 128)
loss = float(step(ids, labels).numpy())
print(json.dumps([loss, len(model.blocks), "bench" in sys.modules]))
"""


def test_smoke_trainer_stands_on_the_program():
    """`chip_smoke._build_trainer` builds its decoder from the program's
    own parts and takes a finite step (the interpreter standing in for
    Mosaic, as `--rehearse` sets it), with no module named `bench`."""
    r = _py(_SMOKE_TRAINER, timeout=60,
            env={"PADDLE_FLASH_DEFAULT": "interpret",
                 "PADDLE_FUSED_LN": "interpret"})
    assert r.returncode == 0, r.stderr[-2000:]
    loss, layers, has_bench = json.loads(r.stdout.splitlines()[-1])
    assert math.isfinite(loss) and 0 < loss < 20, loss
    assert layers == 2 and not has_bench
