import threading
import time

import pytest

from paddlebox_tpu.config import flags
from paddlebox_tpu.utils import Channel, ChannelClosed, StatRegistry, Timer, TimerScope


def test_flags_defaults_and_set():
    assert flags.get_flag("dataset_disable_shuffle") is False
    assert flags.get_flag("stack_threads") == 4
    flags.set_flag("stack_threads", 2)
    assert flags.get_flag("stack_threads") == 2
    flags.set_flag("stack_threads", 4)
    with pytest.raises(KeyError):
        flags.get_flag("nonexistent_flag")


def test_flag_redefine_rejected():
    with pytest.raises(ValueError):
        flags.define_flag("dataset_disable_shuffle", True)


def test_flag_wiring():
    """Flags that claim behavior must actually drive it."""
    from paddlebox_tpu.config.configs import DataFeedConfig, SlotConfig, \
        TrainerConfig
    feed = DataFeedConfig(slots=(SlotConfig("a", max_len=4),), batch_size=8)
    base = feed.key_capacity()
    flags.set_flag("padbox_max_batch_keys", 999)
    try:
        assert feed.key_capacity() == 999
    finally:
        flags.set_flag("padbox_max_batch_keys", 0)
    assert feed.key_capacity() == base

    flags.set_flag("check_nan_inf", True)
    try:
        assert TrainerConfig().check_nan_inf is True
    finally:
        flags.set_flag("check_nan_inf", False)
    assert TrainerConfig().check_nan_inf is False


def test_timer_accumulates():
    t = Timer()
    with TimerScope(t):
        time.sleep(0.01)
    with TimerScope(t):
        time.sleep(0.01)
    assert t.count == 2
    assert 0.015 < t.elapsed_sec() < 1.0


def test_stats():
    reg = StatRegistry.instance()
    reg.reset()
    reg.add("STAT_gpu0_mem", 100)
    reg.add("STAT_gpu0_mem", -30)
    assert reg.get("STAT_gpu0_mem") == 70
    assert reg.snapshot() == {"STAT_gpu0_mem": 70}


def test_channel_mpmc_and_close():
    ch = Channel(capacity=4)
    results = []

    def consumer():
        for item in ch:
            results.append(item)

    threads = [threading.Thread(target=consumer) for _ in range(3)]
    for th in threads:
        th.start()
    for i in range(100):
        ch.put(i)
    ch.close()
    for th in threads:
        th.join()
    assert sorted(results) == list(range(100))
    with pytest.raises(ChannelClosed):
        ch.put(1)
    with pytest.raises(ChannelClosed):
        ch.get()


def test_channel_get_many():
    ch = Channel()
    ch.put_many(range(10))
    got = ch.get_many(4)
    assert got == [0, 1, 2, 3]
    assert len(ch) == 6


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """ensure_compile_cache: where JAX_COMPILATION_CACHE_DIR is set the
    caller placed the cache and the program sets nothing; unset, the cache
    is the FIXED <checkout>/.jax_cache (the path is part of the cache key —
    no tempfile, pid or time in it)."""
    import os

    import jax

    from paddlebox_tpu.utils import platform

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert platform.DEFAULT_COMPILE_CACHE == os.path.join(repo, ".jax_cache")
    old = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert platform.ensure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None   # left alone
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert platform.ensure_compile_cache() == \
            platform.DEFAULT_COMPILE_CACHE
        assert jax.config.jax_compilation_cache_dir == \
            platform.DEFAULT_COMPILE_CACHE
        # a directory the caller gave jax.config itself is not overridden
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert platform.ensure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
