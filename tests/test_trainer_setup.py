"""What the trainer's entry point settles before it compiles anything: the
persistent compilation cache, and whether --shard-scores can be honoured."""
import jax
import pytest

from repro.launch import compile_cache
from repro.launch.train import Trainer, TrainerConfig


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, restore_cache_dir,
                           env_set):
    if env_set:
        # JAX read the variable itself; the helper must leave it be
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.use_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = compile_cache.use_compile_cache()
        second = compile_cache.use_compile_cache()
        want = compile_cache.CACHE_DIR
        assert first == second == str(want)
        assert want.name == ".jax_cache"
        assert (want.parent / "src" / "repro" / "launch").is_dir()
        assert jax.config.jax_compilation_cache_dir == str(want)


@pytest.mark.parametrize("n_dev,n_samples,match", [
    (1, 64, "more than one device"),
    (3, 64, "not divisible"),
])
def test_shard_scores_unhonourable_raises(monkeypatch, n_dev, n_samples,
                                          match):
    """--shard-scores never falls back to a replicated store in silence."""
    dev = jax.devices()[0]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev] * n_dev)
    tc = TrainerConfig(arch="qwen1.5-0.5b", shard_scores=True,
                       n_samples=n_samples, seq_len=32, meta_batch=16,
                       minibatch=4, prefetch=False)
    with pytest.raises(ValueError, match=match):
        Trainer(tc)
