"""chip_smoke.py's phases at LlamaConfig.tiny() sizes on the CPU, in this
process, and the device-selection repairs that came with it: no CPU place
under a TPU's name, no peak for a device the table does not know, one fixed
home for the compile cache."""
import dataclasses
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.jit.compile_cache import enable_compile_cache  # noqa: E402
from paddle_tpu.observability import perf  # noqa: E402


@pytest.fixture
def no_mesh_left_behind():
    from paddle_tpu.distributed import topology
    topology.set_hybrid_communicate_group(None)
    yield
    topology.set_hybrid_communicate_group(None)


@pytest.mark.parametrize("phase", ["kernels", "train", "serve", "mesh"])
def test_phase_runs_tiny(phase, no_mesh_left_behind):
    if phase == "mesh" and jax.device_count() < 4:
        pytest.skip("needs the forced multi-device CPU mesh")
    rec = getattr(chip_smoke, f"{phase}_phase")(chip_smoke.Sizes.tiny(), 0)
    assert rec["phase"] == phase
    assert rec["seconds"] > 0
    if phase == "train":
        assert rec["losses"][-1] < rec["losses"][0]
        assert rec["pallas_custom_calls"] == 0      # interpreted here
    if phase == "serve":
        assert rec["prefix_cache_hit_blocks"] >= 1
        # the check prompt's decode steps in both geometries of the step
        # program (ISSUE 32): float32 here, so the rows agree closely
        assert rec["geometries"] == [24, 48]
        gap = rec["geometry_logit_gap"]
        assert gap["slots"] == [24, 48] and gap["decode_steps"] == 4
        assert gap["rel_err"] <= 1e-5 and gap["same_tokens"]
    if phase == "mesh":
        assert rec["rel_diff"] <= 2e-2
        assert rec["collectives"]["all-reduce"] > 0


def test_failed_check_raises():
    sz = chip_smoke.Sizes.tiny()
    # a first loss that cannot be inside the bounds: the phase must raise,
    # not record the failure and carry on
    bad = dataclasses.replace(sz, first_loss_bounds=(0.0, 1.0))
    with pytest.raises(chip_smoke.SmokeFailure, match="first loss"):
        chip_smoke.train_phase(bad, 0)


def test_full_sizes_are_the_published_widths():
    sz = chip_smoke.Sizes.full()
    cfg = sz.config
    assert (cfg.vocab_size, cfg.hidden_size, cfg.intermediate_size,
            cfg.num_attention_heads, cfg.num_key_value_heads) == \
        (32000, 4096, 11008, 32, 32)
    assert cfg.dtype == "bfloat16"
    assert cfg == paddle.models.LlamaConfig(dtype="bfloat16")
    assert sz.n_requests == 2 * sz.max_batch


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""


@pytest.fixture
def unset_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_compile_cache_home(monkeypatch, unset_cache_dir, env_dir):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
    else:
        # JAX reads the variable by itself: the helper sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert enable_compile_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir is None


@pytest.mark.parametrize("ask", [
    lambda: paddle.set_device("tpu"),
    lambda: paddle.set_device("tpu:0"),
    lambda: paddle.TPUPlace(0),
], ids=["set_device", "set_device_idx", "TPUPlace"])
def test_tpu_place_raises_without_a_tpu(ask):
    before = paddle.get_device()
    with pytest.raises(RuntimeError, match="no TPU"):
        ask()
    assert paddle.get_device() == before
    assert not paddle.is_compiled_with_tpu()


@pytest.mark.parametrize("kind,peak", [
    ("cpu", None), ("TPU v5 lite", (197e12, 819e9))])
def test_peaks_by_device_kind(kind, peak):
    assert perf.DEVICE_PEAKS.get(kind) == peak


def test_bench_peak_raises_for_unknown_kind():
    import bench
    with pytest.raises(ValueError, match="no peak"):
        bench._peak_flops(jax.devices()[0])
