"""The training entry point's bring-up pieces: depth cuts at published
widths, the device count, the compile-cache placement, and compilation kept
off the actor runtime's starvation clock."""
import collections
import pathlib
import types

import jax
import pytest

from repro.configs import registry
from repro.launch import compile_cache, train

WIDTHS = ("d_model", "num_heads", "num_kv_heads", "d_ff", "vocab_size",
          "head_dim", "moe", "ssm", "dtype", "act")


@pytest.fixture(autouse=True)
def _restore_compile_cache():
    """``train.main`` turns the persistent cache on; later tests in this
    process get the configuration they started with."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("arch", registry.ARCHS)
def test_depth_cut_keeps_every_width(arch):
    full = registry.get_arch(arch)
    period = registry.depth_period(full)
    n = period * max(1, full.num_layers // period // 2)
    cut = registry.depth_cut(arch, n)
    assert cut.num_layers == n == len(cut.pattern)
    for field in WIDTHS:
        assert getattr(cut, field) == getattr(full, field), field
    if full.layer_pattern is not None:
        assert cut.pattern == full.pattern[:n]
    if full.encoder_layers:
        kinds = collections.Counter(cut.pattern)
        assert kinds["enc"] * full.num_layers == full.encoder_layers * n
    assert registry.depth_cut(arch) == full


@pytest.mark.parametrize("arch,layers", [
    ("xlstm-350m", 7),          # 7 mLSTM : 1 sLSTM period of 8
    ("zamba2-1.2b", 8),         # shared attention every 6 layers
    ("gemma3-4b", 4),           # 5 local : 1 global
    ("seamless-m4t-large-v2", 3),  # encoder and decoder halves
    ("paper-gpt3-large", 0),
    ("paper-gpt3-large", 25),
])
def test_depth_cut_refuses_partial_periods(arch, layers):
    with pytest.raises(ValueError, match="period"):
        registry.depth_cut(arch, layers)


def test_full_size_layers_cut_through_the_entry_point(capsys):
    args = types.SimpleNamespace(arch="paper-gpt3-large", layers=8,
                                 full_size=True)
    cfg = train.model_config(args)
    assert (cfg.num_layers, cfg.d_model, cfg.d_ff) == (8, 1536, 6144)
    assert "depth 8 of 24" in capsys.readouterr().out
    args.layers, args.full_size = 8, False
    assert train.model_config(args).d_model < 1536  # the reduced toy path


def test_devices_beyond_what_jax_finds_fail():
    with pytest.raises(SystemExit, match="--devices"):
        train.main(["--devices", str(jax.device_count() + 1), "--steps", "1"])


def test_compile_cache_honours_the_environment(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_one_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    assert compile_cache.use_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    root = pathlib.Path(__file__).resolve().parents[1]
    assert pathlib.Path(first) == root / ".jax_cache"


def test_cold_first_actor_step_is_not_a_deadlock():
    """Compilation happens before the threads start: a starvation deadline
    shorter than the stage compiles does not fire on the first step."""
    log = train.main([
        "--runtime", "actor", "--arch", "deepseek-7b", "--stages", "2",
        "--layers", "2", "--microbatches", "2", "--seq", "16", "--steps",
        "2", "--deadlock-timeout", "1", "--seed", "11"])
    assert len(log.losses) == len(log.seconds) == 2
