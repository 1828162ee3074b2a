"""SumTree + helper tests (reference analog: buffer/sumtree unit tests)."""

import os
from pathlib import Path

import numpy as np
import pytest

from alphatriangle_tpu.utils import (
    SumTree,
    dense_policy_from_mapping,
    format_eta,
    is_point_in_polygon,
    mapping_from_dense_policy,
    set_random_seeds,
)


class TestSumTree:
    def test_add_and_total(self):
        t = SumTree(8)
        for i in range(5):
            t.add(float(i + 1), f"item{i}")
        assert t.total_priority == pytest.approx(15.0)
        assert len(t) == 5

    def test_ring_wraparound(self):
        t = SumTree(4)
        for i in range(6):
            t.add(1.0, i)
        assert len(t) == 4
        assert t.total_priority == pytest.approx(4.0)
        assert sorted(d for d in t.data) == [2, 3, 4, 5]

    def test_update_propagates(self):
        t = SumTree(4)
        idx = t.add(1.0, "a")
        t.add(2.0, "b")
        t.update(idx, 5.0)
        assert t.total_priority == pytest.approx(7.0)
        assert t.max_priority == pytest.approx(5.0)

    def test_get_leaf_selects_proportionally(self):
        t = SumTree(4)
        t.add(1.0, "low")
        t.add(99.0, "high")
        idx, prio, data = t.get_leaf(50.0)
        assert data == "high"
        assert prio == pytest.approx(99.0)
        idx, prio, data = t.get_leaf(0.5)
        assert data == "low"

    def test_batched_matches_scalar(self):
        rng = np.random.default_rng(1)
        t = SumTree(33)  # non-power-of-two capacity
        prios = rng.uniform(0.1, 5.0, size=33)
        for i, p in enumerate(prios):
            t.add(float(p), i)
        values = rng.uniform(0, t.total_priority, size=64)
        slots, got_prios = t.get_leaves(values)
        for v, s, p in zip(values, slots, got_prios):
            si, pi, _ = t.get_leaf(float(v))
            assert si == s
            assert pi == pytest.approx(p)

    def test_sample_batch_distribution(self):
        rng = np.random.default_rng(2)
        t = SumTree(16)
        t.add(90.0, "hot")
        for i in range(15):
            t.add(1.0, f"cold{i}")
        slots, _ = t.sample_batch(512, rng)
        hot_frac = float(np.mean(slots == 0))
        assert hot_frac > 0.7  # 90/105 ≈ 0.857 expected

    def test_update_batch_duplicate_indices_last_wins(self):
        t = SumTree(4)
        t.add(1.0, "a")
        t.update_batch(np.array([0, 0]), np.array([3.0, 7.0]))
        assert t.total_priority == pytest.approx(7.0)

    def test_rejects_bad_priorities(self):
        t = SumTree(4)
        with pytest.raises(ValueError):
            t.add(-1.0, "bad")
        with pytest.raises(ValueError):
            t.add(float("nan"), "bad")

    def test_empty_sample_raises(self):
        t = SumTree(4)
        with pytest.raises(ValueError):
            t.sample_batch(2, np.random.default_rng(0))


def test_format_eta():
    assert format_eta(None) == "N/A"
    assert format_eta(-5) == "N/A"
    assert format_eta(3661) == "01:01:01"
    assert format_eta(90061) == "1d 01:01:01"


def test_set_random_seeds_returns_key():
    key = set_random_seeds(7)
    assert key.shape == (2,) or key.dtype.name == "key<fry>" or key.size >= 1


def test_dense_policy_roundtrip():
    mapping = {0: 0.25, 3: 0.75}
    dense = dense_policy_from_mapping(mapping, 5)
    assert dense.sum() == pytest.approx(1.0)
    assert mapping_from_dense_policy(dense) == {0: 0.25, 3: 0.75}


def test_point_in_polygon():
    square = [(0, 0), (2, 0), (2, 2), (0, 2)]
    assert is_point_in_polygon((1, 1), square)
    assert not is_point_in_polygon((3, 3), square)
    assert is_point_in_polygon((0, 0), square)  # vertex counts as inside


class TestFlops:
    """Analytic FLOP accounting (utils/flops.py) used by bench MFU."""

    def test_forward_flops_positive_and_scales(self):
        from alphatriangle_tpu.config import (
            EnvConfig,
            ModelConfig,
            expected_other_features_dim,
        )
        from alphatriangle_tpu.utils.flops import (
            forward_flops,
            train_step_flops,
        )

        env = EnvConfig()
        feat = expected_other_features_dim(env)
        small = ModelConfig(
            OTHER_NN_INPUT_FEATURES_DIM=feat, TRANSFORMER_LAYERS=2
        )
        big = ModelConfig(
            OTHER_NN_INPUT_FEATURES_DIM=feat, TRANSFORMER_LAYERS=4
        )
        f_small = forward_flops(small, env, env.action_dim)
        f_big = forward_flops(big, env, env.action_dim)
        assert 0 < f_small < f_big
        # Two extra layers add exactly the per-layer cost.
        s = env.ROWS * env.COLS
        d, m = small.TRANSFORMER_DIM, small.TRANSFORMER_FC_DIM
        per_layer = 8 * s * d * d + 4 * s * s * d + 4 * s * d * m
        assert f_big - f_small == 2 * per_layer
        # Train step: 3x forward without remat, 4x with.
        assert train_step_flops(small, env, env.action_dim, 8) == (
            3 * 8 * f_small
        )
        remat = small.model_copy(update={"REMAT": True})
        assert train_step_flops(remat, env, env.action_dim, 8) == (
            4 * 8 * f_small
        )
        # A utilization credits no recomputed forward.
        from alphatriangle_tpu.utils.flops import model_step_flops

        for model in (small, remat):
            assert model_step_flops(model, env, env.action_dim, 8) == 3 * 8 * f_small

    def test_peak_table_and_mfu(self, monkeypatch):
        from alphatriangle_tpu.utils.flops import mfu, peak_bf16_tflops_info

        monkeypatch.delenv("ALPHATRIANGLE_PEAK_TFLOPS", raising=False)
        # bf16, not the int8 figure (394) the table once carried.
        assert peak_bf16_tflops_info("TPU v5 lite") == (197.0, "table")
        assert peak_bf16_tflops_info("TPU v5e") == (197.0, "table")
        assert mfu(197e12 / 2, "TPU v5 lite") == 0.5

    @pytest.mark.parametrize("kind", ["cpu", "CPU", ""])
    def test_cpu_peak_stays_unknown(self, monkeypatch, kind):
        from alphatriangle_tpu.utils.flops import mfu, peak_bf16_tflops_info

        monkeypatch.delenv("ALPHATRIANGLE_PEAK_TFLOPS", raising=False)
        assert peak_bf16_tflops_info(kind) == (None, "unknown")
        assert mfu(1.0, kind) is None

    @pytest.mark.parametrize(
        "kind", ["unknown-chip", "TPU v5litepod-8", "TPU v9"]
    )
    def test_unlisted_accelerator_kind_raises(self, monkeypatch, kind):
        """No prefix guess, no None: an accelerator the table does not
        list is an error until someone adds it with its source."""
        from alphatriangle_tpu.utils.flops import peak_bf16_tflops_info

        monkeypatch.delenv("ALPHATRIANGLE_PEAK_TFLOPS", raising=False)
        with pytest.raises(ValueError, match="no peak listed"):
            peak_bf16_tflops_info(kind)


def _stub_jax(monkeypatch, backend=None):
    """Swap utils.helpers' view of jax for a stub that records
    `config.update` calls and resolves to `backend`: the conftest pins
    the CPU process-wide, a stub lets each case say what it sees."""
    import types

    from alphatriangle_tpu.utils import helpers

    recorded: list = []
    monkeypatch.setattr(
        helpers,
        "jax",
        types.SimpleNamespace(
            config=types.SimpleNamespace(
                update=lambda k, v: recorded.append((k, v))
            ),
            default_backend=lambda: backend,
        ),
    )
    return helpers, recorded


class TestCompileCacheGate:
    """The persistent-cache gate must never enable for a CPU backend
    (XLA:CPU AOT reloads log SIGILL-risk feature mismatches), must give
    an accelerator run a cache whether or not a platform is pinned, and
    must name a directory only when the environment names none."""

    def _calls(self, monkeypatch, backend, env_dir=None):
        monkeypatch.delenv("ALPHATRIANGLE_NO_COMPILE_CACHE", raising=False)
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        return _stub_jax(monkeypatch, backend)

    def test_cpu_backend_skips(self, monkeypatch):
        helpers, calls = self._calls(monkeypatch, "cpu")
        helpers.enable_persistent_compilation_cache()
        assert calls == []

    def test_unpinned_tpu_run_gets_the_in_checkout_cache(self, monkeypatch):
        # No JAX_PLATFORMS at all (the chip machine's default run).
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        helpers, calls = self._calls(monkeypatch, "tpu")
        helpers.enable_persistent_compilation_cache()
        repo = Path(helpers.__file__).resolve().parents[2]
        assert calls == [
            ("jax_compilation_cache_dir", str(repo / ".cache" / "jax"))
        ]

    def test_env_dir_set_means_no_code_sets_another(
        self, monkeypatch, tmp_path
    ):
        from alphatriangle_tpu import compile_cache

        helpers, calls = self._calls(
            monkeypatch, "tpu", env_dir=str(tmp_path)
        )
        monkeypatch.delenv("ALPHATRIANGLE_AOT_CACHE_DIR")
        helpers.enable_persistent_compilation_cache()
        assert calls == []  # JAX read the variable itself
        assert helpers.compilation_cache_root() == str(tmp_path)
        assert compile_cache.default_cache_dir() == str(tmp_path / "aot")

    def test_opt_out_env_wins(self, monkeypatch):
        helpers, calls = self._calls(monkeypatch, "tpu")
        monkeypatch.setenv("ALPHATRIANGLE_NO_COMPILE_CACHE", "1")
        helpers.enable_persistent_compilation_cache()
        assert calls == []

    def test_unset_dir_is_fixed_across_processes(self, monkeypatch):
        """Unset, the cache sits at one in-checkout path: two fresh
        processes name the same directory (the path is part of JAX's
        cache key), and it is neither /tmp nor made from a pid."""
        import subprocess
        import sys

        repo = Path(__file__).resolve().parents[1]
        env = {
            k: v
            for k, v in os.environ.items()
            if k
            not in ("JAX_COMPILATION_CACHE_DIR", "ALPHATRIANGLE_AOT_CACHE_DIR")
        }
        env["PYTHONPATH"] = str(repo)
        code = (
            "from alphatriangle_tpu.compile_cache import default_cache_dir;"
            "print(default_cache_dir())"
        )
        outs = [
            subprocess.run(
                [sys.executable, "-c", code],
                env=env,
                cwd=cwd,
                capture_output=True,
                text=True,
                check=True,
            ).stdout.strip()
            for cwd in (str(repo), "/")
        ]
        assert outs == [str(repo / ".cache" / "jax" / "aot")] * 2


class TestEnforcePlatform:
    def test_auto_leaves_the_choice_to_jax(self, monkeypatch):
        helpers, calls = _stub_jax(monkeypatch)
        helpers.enforce_platform("auto")
        assert calls == []

    @pytest.mark.parametrize("device", ["cpu", "tpu"])
    def test_explicit_device_pins_the_platform(self, monkeypatch, device):
        """"tpu" pins too: with none attached backend start-up raises
        instead of the run landing on the host."""
        helpers, calls = _stub_jax(monkeypatch)
        monkeypatch.setenv("JAX_PLATFORMS", "")
        helpers.enforce_platform(device)
        assert calls == [("jax_platforms", device)]
        assert os.environ["JAX_PLATFORMS"] == ("cpu" if device == "cpu" else "")
