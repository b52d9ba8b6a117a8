"""Round-4 hygiene coverage (VERDICT r3 item 10 + weak #5/#7/#8)."""
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed import comm
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.distributed import fleet
from paddle_tpu.jit import TrainStep


class TestCheckNanInf:
    def test_flag_catches_nan(self):
        paddle.set_flags({"FLAGS_check_nan_inf": True})
        try:
            x = paddle.to_tensor(np.array([1.0, -1.0], np.float32))
            with pytest.raises(RuntimeError, match="log"):
                paddle.log(x)  # log(-1) = nan
        finally:
            paddle.set_flags({"FLAGS_check_nan_inf": False})

    def test_flag_off_is_silent(self):
        x = paddle.to_tensor(np.array([-1.0], np.float32))
        out = paddle.log(x)
        assert np.isnan(out.numpy()).all()


class TestEnvMerged:
    def test_single_source_of_truth(self, monkeypatch):
        assert not hasattr(dist, "env")
        monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "5")
        assert dist.get_rank() == 3
        assert dist.get_world_size() == 5
        assert comm.ParallelEnv().rank == 3

    def test_defaults_without_env(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
        monkeypatch.delenv("PADDLE_TRAINERS_NUM", raising=False)
        assert dist.get_rank() == 0
        assert dist.get_world_size() == 1


class TestZeroShardings:
    """weak #5: actually inspect the state shardings ZeRO produces."""

    def _strategy(self, stage):
        s = DistributedStrategy()
        s.sharding = True
        s.sharding_configs = {"stage": stage}
        return s

    def test_stage1_shards_optimizer_state_over_dp(self):
        fleet.init(is_collective=True, strategy=self._strategy(1))
        model = nn.Linear(16, 24)
        opt = fleet.distributed_optimizer(
            optimizer.Adam(learning_rate=1e-3,
                           parameters=model.parameters())
        )
        step = TrainStep(model, lambda o, y: ((o - y) ** 2).mean(), opt)
        x = np.random.rand(8, 16).astype(np.float32)
        y = np.random.rand(8, 24).astype(np.float32)
        step(x, y)
        inner = opt._inner
        m_w = inner._accumulators["moment1"][id(model.weight)]
        # weight moment [16, 24]: axis 0 divisible by dp=8 -> sharded
        assert len(m_w.sharding.device_set) == 8
        assert not m_w.sharding.is_fully_replicated
        # bias moment [24]: divisible too -> sharded
        m_b = inner._accumulators["moment1"][id(model.bias)]
        assert not m_b.sharding.is_fully_replicated

    def test_non_divisible_leaf_stays_replicated_documented(self):
        fleet.init(is_collective=True, strategy=self._strategy(1))
        model = nn.Linear(16, 10)  # bias [10]: 10 % 8 != 0, size < 1024
        opt = fleet.distributed_optimizer(
            optimizer.Adam(learning_rate=1e-3,
                           parameters=model.parameters())
        )
        step = TrainStep(model, lambda o, y: ((o - y) ** 2).mean(), opt)
        x = np.random.rand(8, 16).astype(np.float32)
        y = np.random.rand(8, 10).astype(np.float32)
        step(x, y)
        inner = opt._inner
        m_b = inner._accumulators["moment1"][id(model.bias)]
        assert m_b.sharding.is_fully_replicated  # tiny leaf: documented
        # the [16, 10] weight moment shards on axis 0
        m_w = inner._accumulators["moment1"][id(model.weight)]
        assert not m_w.sharding.is_fully_replicated

    def test_stage3_odd_embedding_is_distributed(self):
        """VERDICT r4 weak #7: a large leaf with NO dp-divisible axis
        (odd vocab x odd width) must still be distributed — GSPMD pads
        the largest axis internally (the compiler-side pad-to-divisible)
        instead of replicating, so per-device bytes shrink."""
        fleet.init(is_collective=True, strategy=self._strategy(3))
        model = nn.Embedding(30522, 12)  # 30522 % 8 != 0, 12 % 8 != 0
        opt = fleet.distributed_optimizer(
            optimizer.Adam(learning_rate=1e-3,
                           parameters=model.parameters())
        )

        def loss_fn(o, y):
            return (o ** 2).mean()

        step = TrainStep(model, loss_fn, opt)
        ids = (np.arange(16) % 30522).astype(np.int64)
        step(ids, ids)
        inner = opt._inner
        m_w = inner._accumulators["moment1"][id(model.weight)]
        assert not m_w.sharding.is_fully_replicated
        shard_rows = max(
            s.data.shape[0] for s in m_w.addressable_shards
        )
        assert shard_rows < 30522  # per-device bytes actually shrank
        # stage 3 also shards the parameter itself
        assert not model.weight._data.sharding.is_fully_replicated


class TestCollectivesSpmd:
    def test_broadcast_selects_src_without_allgather(self):
        g = comm._default_group()

        from paddle_tpu.core.tensor import Tensor

        def prog(x):
            with comm.spmd_region(g.axis_name):
                return dist.broadcast(
                    Tensor._wrap(x), src=2, group=g
                )._data

        f = comm.shard_map(
            prog, g.mesh,
            in_specs=jax.sharding.PartitionSpec(g.axis_name),
            out_specs=jax.sharding.PartitionSpec(g.axis_name),
        )
        x = np.arange(8, dtype=np.float32).reshape(8, 1)
        out = np.asarray(jax.jit(f)(x))
        np.testing.assert_array_equal(out.reshape(-1), [2.0] * 8)

    def test_scatter_spmd_uses_src(self):
        g = comm._default_group()

        from paddle_tpu.core.tensor import Tensor

        def prog(x):
            with comm.spmd_region(g.axis_name):
                return dist.scatter(
                    Tensor._wrap(x), src=3, group=g
                )._data

        # each rank holds a DIFFERENT stacked [8, 1]; only src's must win
        f = comm.shard_map(
            prog, g.mesh,
            in_specs=jax.sharding.PartitionSpec(g.axis_name),
            out_specs=jax.sharding.PartitionSpec(g.axis_name),
        )
        # global [64, 1]: rank r holds rows 8r..8r+7 = r*100 + arange(8)
        x = np.concatenate([
            (r * 100 + np.arange(8, dtype=np.float32)).reshape(8, 1)
            for r in range(8)
        ])
        out = np.asarray(jax.jit(f)(x)).reshape(-1)
        # src=3's stack is 300+arange(8); rank r receives chunk r
        np.testing.assert_array_equal(out, 300 + np.arange(8))


class TestDataLoaderProcessPool:
    def test_process_pool_matches_sync(self):
        from paddle_tpu.io import DataLoader
        from paddle_tpu.vision.datasets import FakeData

        ds = FakeData(sample_shape=(1, 6, 6), num_samples=32, num_classes=4)
        proc = DataLoader(ds, batch_size=8, num_workers=2,
                          use_shared_memory=True)
        assert len(list(proc)) == 4
        sync = DataLoader(ds, batch_size=8)
        for (a, la), (b, lb) in zip(proc, sync):
            np.testing.assert_allclose(a.numpy(), b.numpy())
            np.testing.assert_array_equal(la.numpy(), lb.numpy())

    def test_unpicklable_falls_back_to_threads(self):
        from paddle_tpu.io import DataLoader
        from paddle_tpu.io.dataset import Dataset

        lock = __import__("threading").Lock()  # unpicklable payload

        class Ds(Dataset):
            def __getitem__(self, i):
                _ = lock
                return np.full((2,), i, np.float32), np.int64(i)

            def __len__(self):
                return 16

        loader = DataLoader(Ds(), batch_size=4, num_workers=2,
                            use_shared_memory=True)
        batches = list(loader)
        assert len(batches) == 4
        assert not loader._pool_is_proc


class TestDistributedBatchSampler:
    """This class's default construction broke once (stale env import)
    without any test noticing — pin the whole contract."""

    def _ds(self, n=10):
        from paddle_tpu.io.dataset import Dataset

        class Ds(Dataset):
            def __getitem__(self, i):
                return np.float32(i)

            def __len__(self):
                return n

        return Ds()

    def test_default_env_construction(self, monkeypatch):
        from paddle_tpu.io.sampler import DistributedBatchSampler

        monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
        monkeypatch.delenv("PADDLE_TRAINERS_NUM", raising=False)
        s = DistributedBatchSampler(self._ds(), batch_size=4)
        assert s.nranks == 1 and s.local_rank == 0
        assert sum(len(b) for b in s) == 10

    def test_sharding_across_ranks(self):
        from paddle_tpu.io.sampler import DistributedBatchSampler

        ds = self._ds(10)
        seen = []
        for rank in range(4):
            s = DistributedBatchSampler(
                ds, batch_size=2, num_replicas=4, rank=rank
            )
            idx = [i for b in s for i in b]
            assert len(idx) == s.num_samples == 3  # ceil(10/4), padded
            seen.extend(idx)
        # every sample appears (padding duplicates allowed)
        assert set(seen) == set(range(10))

    def test_shuffle_is_epoch_seeded(self):
        from paddle_tpu.io.sampler import DistributedBatchSampler

        s = DistributedBatchSampler(self._ds(16), batch_size=4,
                                    num_replicas=2, rank=0, shuffle=True)
        a = [i for b in s for i in b]
        b = [i for bt in s for i in bt]
        assert a == b  # same epoch -> same order
        s.epoch = 1
        c = [i for bt in s for i in bt]
        assert a != c


class TestNamespaceParity:
    """Round-5 namespace tail: paddle.batch / sysconfig / onnx /
    distribution / device resolve with the reference semantics."""

    def test_batch_reader(self):
        import paddle_tpu as paddle

        def reader():
            yield from range(7)

        out = [b for b in paddle.batch(reader, 3)()]
        assert out == [[0, 1, 2], [3, 4, 5], [6]]
        out = [b for b in paddle.batch(reader, 3, drop_last=True)()]
        assert out == [[0, 1, 2], [3, 4, 5]]

    def test_sysconfig_paths_exist(self):
        import os

        import paddle_tpu as paddle

        assert os.path.isdir(paddle.sysconfig.get_include())
        assert os.path.isdir(paddle.sysconfig.get_lib())

    def test_onnx_export_points_to_stablehlo(self):
        import pytest

        import paddle_tpu as paddle

        with pytest.raises(NotImplementedError, match="StableHLO"):
            paddle.onnx.export(None, "/tmp/x")


class TestAliasParity:
    """The `import paddle` compatibility subsystem stays honest in CI:
    tools/check_alias.py must report zero missing reference names, zero
    stale out-of-scope entries, and zero paddle_tpu public names without
    a `paddle` alias — a new paddle_tpu export that is not reachable via
    `paddle.*` (and is not on the out-of-scope list) fails here."""

    @staticmethod
    def _linter():
        import importlib.util
        import os

        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "check_alias.py",
        )
        spec = importlib.util.spec_from_file_location("check_alias", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_reference_coverage_zero_missing(self):
        ca = self._linter()
        rows, missing, stale = ca.check_reference_coverage()
        assert rows, "linter walked no modules"
        assert not missing, f"aliased-but-missing reference names: {missing}"
        assert not stale, f"stale out-of-scope entries: {stale}"

    def test_every_paddle_tpu_name_is_aliased(self):
        ca = self._linter()
        unaliased = ca.check_alias_completeness()
        assert not unaliased, (
            "paddle_tpu public names with no `paddle` alias (add the "
            f"alias or an OUT_OF_SCOPE entry): {unaliased}"
        )

    def test_module_identity_is_exact(self):
        """The alias is the SAME module object, not a copy — mutable
        state (static-mode flag, default programs) must be single-
        sourced."""
        import paddle
        import paddle.nn
        import paddle.static
        import paddle_tpu

        assert paddle.nn is paddle_tpu.nn
        assert paddle.static is paddle_tpu.static
        assert paddle.Tensor is paddle_tpu.Tensor
        import importlib

        assert importlib.import_module("paddle.nn.functional") \
            is paddle_tpu.nn.functional
        # a module paddle_tpu does NOT import eagerly: the alias finder
        # must still return the same object, never re-execute the file
        # through the aliased parent's __path__ (duplicate custom_vjp
        # registrations / second class objects)
        lazy_alias = importlib.import_module(
            "paddle.ops.pallas.flash_attention"
        )
        lazy_src = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention"
        )
        assert lazy_alias is lazy_src

    def test_fluid_mode_policy(self):
        """fluid.data implies static mode; dygraph.guard scopes it off;
        both restore the prior mode (framework.py mode policy)."""
        import paddle.fluid as fluid
        import paddle_tpu.static as static

        was = static._static_mode_on()
        try:
            static._disable()
            with fluid.dygraph.guard():
                assert fluid.in_dygraph_mode()
            assert fluid.in_dygraph_mode()  # restored (was dygraph)
            static._enable()
            with fluid.dygraph.guard():
                assert fluid.in_dygraph_mode()
            assert not fluid.in_dygraph_mode()  # restored (was static)
        finally:
            (static._enable if was else static._disable)()


class TestReaderDecorators:
    """paddle.reader decorator parity (reference reader/decorator.py)."""

    def test_compose_map_shuffle_chain_cache_firstn(self):
        import paddle_tpu as paddle

        r1 = lambda: iter([1, 2, 3])
        r2 = lambda: iter([10, 20, 30])
        assert list(paddle.reader.compose(r1, r2)()) == [
            (1, 10), (2, 20), (3, 30)]
        with pytest.raises(paddle.reader.ComposeNotAligned):
            list(paddle.reader.compose(r1, lambda: iter([1]))())
        assert list(paddle.reader.map_readers(
            lambda a, b: a + b, r1, r2)()) == [11, 22, 33]
        assert list(paddle.reader.chain(r1, r2)()) == [1, 2, 3, 10, 20, 30]
        assert sorted(paddle.reader.shuffle(r1, 2)()) == [1, 2, 3]
        assert list(paddle.reader.firstn(r1, 2)()) == [1, 2]
        assert list(paddle.reader.buffered(r1, 2)()) == [1, 2, 3]

        calls = []

        def counting():
            calls.append(1)
            return iter([5, 6])

        cached = paddle.reader.cache(counting)
        assert list(cached()) == [5, 6]
        assert list(cached()) == [5, 6]
        assert len(calls) == 1

        assert list(paddle.reader.xmap_readers(
            lambda s: s * 2, r1, 2, 4)()) == [2, 4, 6]

    def test_batch_composes_with_reader(self):
        import paddle_tpu as paddle

        r = paddle.reader.shuffle(lambda: iter(range(10)), 10)
        batches = list(paddle.batch(r, 4)())
        assert [len(b) for b in batches] == [4, 4, 2]
        assert sorted(sum(batches, [])) == list(range(10))

    def test_buffered_propagates_reader_errors_and_releases_thread(self):
        import threading
        import time

        import paddle_tpu as paddle

        def bad_reader():
            yield 1
            raise IOError("disk gone")

        it = paddle.reader.buffered(bad_reader, 2)()
        assert next(it) == 1
        with pytest.raises(IOError, match="disk gone"):
            list(it)

        # early abandonment must retire the fill thread (no leak)
        before = threading.active_count()
        gen = paddle.reader.buffered(lambda: iter(range(1000)), 1)()
        assert next(gen) == 0
        gen.close()
        time.sleep(0.2)
        assert threading.active_count() <= before + 1

    def test_compose_rejects_typoed_kwargs(self):
        import paddle_tpu as paddle

        with pytest.raises(TypeError, match="check_aligment"):
            paddle.reader.compose(lambda: iter([1]), check_aligment=False)


class TestTpulintGate:
    """tpulint is the tier-1 static-analysis gate (ISSUE 7): the full
    sweep over `paddle_tpu/` + the verbatim reference scripts must
    produce zero NEW findings (baseline passes, anything new fails),
    zero stale baseline entries, and a baseline whose every entry
    carries a tracking note. The old ad-hoc TestEnvKnobDocs check lives
    on as tpulint's `env-knob-docs` rule inside this same sweep."""

    @staticmethod
    def _sweep():
        import os

        from tools.tpulint import core as lint_core
        from tools.tpulint import rules  # noqa: F401 (registers)

        root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        # the GATE must not inherit a developer's ambient lint env — a
        # leftover PADDLE_LINT_DISABLE would silently skip rules here
        saved = {
            k: os.environ.pop(k)
            for k in ("PADDLE_LINT_DISABLE", "PADDLE_LINT_BASELINE")
            if k in os.environ
        }
        try:
            findings, errors = lint_core.run(
                [os.path.join(root, "paddle_tpu"),
                 os.path.join(root, "tests", "reference_scripts")],
                root=root,
            )
            baseline = lint_core.load_baseline(
                lint_core.default_baseline_path()
            )
        finally:
            os.environ.update(saved)
        new, stale = lint_core.apply_baseline(findings, baseline)
        return findings, errors, new, stale

    def test_sweep_has_no_new_findings(self):
        findings, errors, new, stale = self._sweep()
        assert not errors, errors
        assert not new, "NEW tpulint findings (fix, suppress with a " \
            "reasoned comment, or baseline with a tracking note):\n" \
            + "\n".join(f.render() for f in new)
        assert not stale, "stale baseline entries (the finding no " \
            "longer fires — drop them):\n" + "\n".join(
                f"{e['rule']}@{e['path']}" for e in stale)

    def test_env_knob_rule_still_scans(self):
        """Migration sanity: the env-knob-docs rule sees the knobs the
        old check saw (PADDLE_WATCHDOG_TIMEOUT et al are in scope and
        documented — an undocumented knob would surface as a NEW
        finding in test_sweep_has_no_new_findings)."""
        import os
        import re

        from tools.tpulint.rules.env_knobs import _KNOB_RE

        root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        elastic = os.path.join(root, "paddle_tpu", "distributed",
                               "elastic.py")
        with open(elastic) as fh:
            knobs = set(_KNOB_RE.findall(fh.read()))
        assert "PADDLE_WATCHDOG_TIMEOUT" in knobs  # scanner sanity

    def test_check_alias_reachable_through_tpulint(self):
        """The alias-parity rule is registered in the same framework
        (one static-analysis entry point); its heavy import-time check
        body is exercised by TestAliasParity below."""
        from tools.tpulint import core as lint_core
        from tools.tpulint import rules  # noqa: F401

        rule = lint_core.REGISTRY.get("alias-parity")
        assert rule is not None
        assert not rule.default_enabled  # CLI opt-in (--alias)


class TestDatasetTensorNamespaces:
    def test_tensor_module_paths(self):
        import paddle_tpu as paddle

        assert paddle.tensor.matmul is paddle.matmul
        from paddle_tpu.tensor import creation  # reference import shape

        assert creation.to_tensor is paddle.to_tensor

    def test_dataset_reader_protocol(self, tmp_path):
        import paddle_tpu as paddle

        f = tmp_path / "housing.data"
        rows = np.random.RandomState(0).rand(30, 14)
        with open(f, "w") as fh:
            for r in rows:
                fh.write(" ".join(f"{v:.6f}" for v in r) + "\n")
        reader = paddle.dataset.uci_housing.train(data_file=str(f))
        samples = list(reader())
        assert len(samples) > 0
        feat, label = samples[0]
        assert feat.shape == (13,)
        batches = list(paddle.batch(reader, 4)())
        assert len(batches[0]) == 4


class TestOneYardstick:
    """Since PR 32 the only code that measures speed is under
    `benchmarks/`, the only table of peaks is `benchmarks/peaks.json`,
    and the documents name only what the tree holds."""

    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    #: distinct `PADDLE_<FAMILY>_*` names under `paddle_tpu/` (ROADMAP
    #: D2). A pin moves one way: a removed knob lowers it in the same PR.
    KNOB_PINS = {
        "SERVE": 23, "CTL": 12, "OBS": 10, "GUARD": 9, "COLL": 7,
        "MON": 6, "RESHARD": 3, "FLASH+FUSED+CE": 5, "the rest": 23,
    }

    @classmethod
    def _py_files(cls, *tops):
        for top in tops:
            path = os.path.join(cls.ROOT, top)
            if os.path.isfile(path):
                yield path
            for r, dirs, fns in os.walk(path):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                yield from (os.path.join(r, fn) for fn in sorted(fns)
                            if fn.endswith(".py"))

    @classmethod
    def _knobs(cls, *tops):
        from tools.tpulint.rules.env_knobs import _KNOB_RE

        found = set()
        for fp in cls._py_files(*tops):
            with open(fp, encoding="utf-8") as fh:
                found.update(_KNOB_RE.findall(fh.read()))
        return found

    @pytest.mark.parametrize("family", list(KNOB_PINS))
    def test_knob_families_only_go_down(self, family):
        by_pin = {}
        for k in self._knobs("paddle_tpu"):
            fam = k.split("_")[1]
            key = next((key for key in self.KNOB_PINS
                        if fam in key.split("+")), "the rest")
            by_pin.setdefault(key, []).append(k)
        names, pin = sorted(by_pin.get(family, [])), self.KNOB_PINS[family]
        assert len(names) <= pin, (
            f"{len(names)} PADDLE_* names of {family}, pinned at {pin}: a "
            f"new knob needs two callers that differ: {names}")
        assert len(names) == pin, (
            f"{len(names)} PADDLE_* names of {family}, pinned at {pin}: "
            "lower the pin")

    def test_readme_documents_no_dead_knob(self):
        """The reverse of tpulint's `env-knob-docs`: a name the README
        documents is read by some program file."""
        from tools.tpulint.rules.env_knobs import _KNOB_RE

        with open(os.path.join(self.ROOT, "README.md")) as fh:
            documented = set(_KNOB_RE.findall(fh.read()))
        read = self._knobs("paddle_tpu", "paddle", "tools", "benchmarks",
                           "chip_smoke.py", "__graft_entry__.py")
        dead = sorted(
            k for k in documented - read
            # a documented prefix (`PADDLE_SERVE_ADMIT_*`) stands for
            # the names read under it
            if not (k.endswith("_") and any(r.startswith(k) for r in read)))
        assert not dead, f"README.md documents knobs nothing reads: {dead}"

    @pytest.mark.parametrize(
        "doc", ["README.md", ".claude/skills/verify/SKILL.md"])
    def test_cited_files_exist(self, doc):
        """Every back-ticked path ending in .py, .md or .json that starts
        with a top-level name of the repo is there."""
        with open(os.path.join(self.ROOT, doc)) as fh:
            text = fh.read()
        tops = set(os.listdir(self.ROOT))
        cited = {
            m for m in re.findall(r"`([\w./-]+\.(?:py|md|json))", text)
            if m.split("/")[0] in tops}
        assert cited, f"{doc} cites no file: the pattern is broken"
        gone = sorted(
            c for c in cited
            if not os.path.exists(os.path.join(self.ROOT, c)))
        assert not gone, f"{doc} cites files that do not exist: {gone}"

    def test_one_table_of_peaks(self):
        """No `.py` outside `benchmarks/` holds a TPU peak (FLOP/s or
        bytes/s of any generation the deleted table had), and
        `benchmarks/peaks.json` is what `chip_smoke.py` checks its
        device kind against."""
        peak = re.compile(
            r"\b(?:918|459|197|275|123|45)e12\b|\b(?:819|1640|2765)e9\b")
        held = []
        for fp in self._py_files("paddle_tpu", "paddle", "tools", "tests",
                                 "chip_smoke.py", "__graft_entry__.py"):
            with open(fp, encoding="utf-8") as fh:
                if peak.search(fh.read()):
                    held.append(os.path.relpath(fp, self.ROOT))
        assert not held, f"a second table of peaks: {held}"
        with open(os.path.join(self.ROOT, "benchmarks", "peaks.json")) as fh:
            peaks = json.load(fh)
        # the kind a v5e chip reports, and the key the smoke reads
        assert peaks["TPU v5 lite"]["flops_per_s"] > 0
        with open(os.path.join(self.ROOT, "chip_smoke.py")) as fh:
            assert "peaks.json" in fh.read()
