"""Core resources registry tests. (mirrors cpp/tests/core/handle.cpp,
device_resources_manager.cpp)"""

import threading

import jax
import pytest

from raft_tpu.core import (
    DeviceResources,
    LogicError,
    Resources,
    ResourceType,
    device_resources,
    ensure_resources,
)


def test_lazy_factory_instantiation():
    res = Resources()
    calls = []

    def factory(r):
        calls.append(1)
        return "value"

    res.add_resource_factory(ResourceType.CUSTOM, factory)
    assert calls == []  # lazy
    assert res.get_resource(ResourceType.CUSTOM) == "value"
    assert res.get_resource(ResourceType.CUSTOM) == "value"
    assert calls == [1]  # instantiated once


def test_missing_factory_raises():
    res = Resources()
    with pytest.raises(LogicError):
        res.get_resource(ResourceType.CUSTOM)


def test_shallow_copy_shares_resources():
    res = Resources()
    res.add_resource_factory(ResourceType.CUSTOM, lambda r: object())
    alias = Resources(_shared_from=res)
    assert alias.get_resource(ResourceType.CUSTOM) is res.get_resource(
        ResourceType.CUSTOM
    )


def test_replacing_factory_resets_instance():
    res = Resources()
    res.add_resource_factory(ResourceType.CUSTOM, lambda r: "a")
    assert res.get_resource(ResourceType.CUSTOM) == "a"
    res.add_resource_factory(ResourceType.CUSTOM, lambda r: "b")
    assert res.get_resource(ResourceType.CUSTOM) == "b"


def test_device_resources_defaults():
    res = DeviceResources(seed=7)
    assert res.device in jax.devices()
    assert res.platform == "cpu"  # conftest forces cpu
    assert res.mesh.devices.size == 1
    assert res.rng.seed == 7
    k1 = res.rng.next_key()
    k2 = res.rng.next_key()
    assert not jax.numpy.array_equal(jax.random.key_data(k1), jax.random.key_data(k2))


def test_default_handle_singleton():
    assert device_resources() is device_resources()
    assert ensure_resources(None) is device_resources()
    custom = DeviceResources()
    assert ensure_resources(custom) is custom


def test_workspace_budget():
    res = DeviceResources(workspace_limit=1 << 20)
    assert res.workspace.allocation_limit == 1 << 20
    assert res.workspace.batch_rows(row_bytes=1024) == 1024


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform, self._stats = platform, stats

    def memory_stats(self):
        return self._stats


def test_workspace_default_limit_needs_bytes_limit_on_tpu(monkeypatch):
    """A TPU that reports no bytes_limit is an error, not a silent
    1 GiB budget; the CPU platform keeps its fixed default."""
    from raft_tpu.core.resources import WorkspaceResource

    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("tpu", {})])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        WorkspaceResource()
    monkeypatch.setattr(jax, "devices", lambda *a: [
        _FakeDevice("tpu", {"bytes_limit": 16 << 30})])
    assert WorkspaceResource().allocation_limit == 4 << 30
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("cpu", None)])
    assert WorkspaceResource().allocation_limit == 1 << 30


def test_compile_cache_dir_from_env_or_repo(monkeypatch):
    """The entry-script helper: JAX_COMPILATION_CACHE_DIR wins and is
    left to JAX; otherwise the fixed, git-ignored <repo>/.jax_cache."""
    import os
    import subprocess

    from raft_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.use_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert compile_cache.use_compile_cache() == \
            compile_cache.REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == \
            compile_cache.REPO_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    repo = os.path.dirname(compile_cache.REPO_CACHE_DIR)
    assert os.path.basename(compile_cache.REPO_CACHE_DIR) == ".jax_cache"
    ignored = subprocess.run(["git", "-C", repo, "check-ignore", "-q",
                              ".jax_cache/x"], capture_output=True)
    assert ignored.returncode in (0, 128)   # 128: not a git checkout


def test_compile_cache_lands_in_env_dir(tmp_path):
    """End to end in a fresh process: with JAX_COMPILATION_CACHE_DIR
    set, the compiled programs land there."""
    import os
    import subprocess
    import sys

    from raft_tpu.utils import compile_cache

    repo = os.path.dirname(compile_cache.REPO_CACHE_DIR)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    code = ("from raft_tpu.utils.compile_cache import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "import jax, jax.numpy as jnp\n"
            "jax.block_until_ready(jax.jit(lambda x: x * 2)(jnp.ones(4)))\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path)
    assert any(n.endswith("-cache") for n in os.listdir(tmp_path))


def test_compile_cache():
    res = DeviceResources()
    cache = res.compile_cache
    a = cache.get_or_compile("k", lambda: [1])
    b = cache.get_or_compile("k", lambda: [2])
    assert a is b
    assert cache.hits == 1 and cache.misses == 1


def test_comms_accessors():
    res = DeviceResources()
    assert not res.comms_initialized()
    with pytest.raises(LogicError):
        res.get_comms()
    res.set_comms("fake-comms")
    assert res.comms_initialized()
    assert res.get_comms() == "fake-comms"
    res.set_subcomm("row", "row-comms")
    assert res.get_subcomm("row") == "row-comms"
    with pytest.raises(LogicError):
        res.get_subcomm("col")


def test_registry_thread_safety():
    res = Resources()
    built = []

    def factory(r):
        built.append(1)
        return object()

    res.add_resource_factory(ResourceType.CUSTOM, factory)
    results = []

    def worker():
        results.append(res.get_resource(ResourceType.CUSTOM))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(built) == 1
    assert all(r is results[0] for r in results)
