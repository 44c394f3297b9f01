"""The resources registry — raft_tpu's "handle" system.

(ref: cpp/include/raft/core/resources.hpp:39-120 — a type-indexed container
of lazily-constructed resources: factories are registered per slot and the
resource is instantiated on first ``get_resource``, mutex-guarded; shallow
copies share resources. ref: core/device_resources.hpp:53-228 — the concrete
"handle" pre-registering device/stream factories.)

The registry design is kept — it is a good design — but the resource
vocabulary is TPU-native (see :mod:`raft_tpu.core.resource_types`): instead
of cuBLAS handles and CUDA streams, a handle owns its JAX device, an SPMD
``Mesh``, a threefry PRNG key stream, a compiled-executable cache, workspace
memory budgets, and (optionally) an injected communicator.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from raft_tpu.core.error import LogicError, expects
from raft_tpu.core.resource_types import ResourceType

ResourceFactory = Callable[["Resources"], Any]


class KeyStream:
    """Mutable threefry key stream scoped to a handle.

    The TPU-native replacement for per-call ``RngState`` plumbing: splitting
    is explicit and deterministic given the seed (counter-based threefry, the
    native TPU RNG — ref SURVEY §2.9 TPU mapping note).
    """

    def __init__(self, seed: int = 0):
        self._seed = int(seed)
        self._key = jax.random.key(self._seed)
        self._lock = threading.Lock()

    @property
    def seed(self) -> int:
        return self._seed

    def next_key(self):
        """Split off a fresh subkey (thread-safe)."""
        with self._lock:
            self._key, sub = jax.random.split(self._key)
            return sub

    def reseed(self, seed: int) -> None:
        with self._lock:
            self._seed = int(seed)
            self._key = jax.random.key(self._seed)


class CompileCache:
    """Memoization of AOT-lowered executables keyed by (fn, shapes).

    The TPU-native analog of the reference's precompiled ``libraft.so``
    instantiations (ref: cpp/CMakeLists.txt:275-309): expensive compilation
    happens once per shape signature and is reused.
    """

    def __init__(self):
        self._cache: Dict[Any, Any] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    _MISS = object()

    def get_or_compile(self, key, compile_fn: Callable[[], Any]):
        # observability bridge: lazy import (core must import first) and
        # called outside the cache lock (the hook takes the registry lock)
        from raft_tpu.observability import record_cache

        with self._lock:
            value = self._cache.get(key, CompileCache._MISS)
            if value is not CompileCache._MISS:
                self.hits += 1
        if value is not CompileCache._MISS:
            record_cache(hit=True)
            return value
        value = compile_fn()
        record_cache(hit=False)
        with self._lock:
            self.misses += 1
            self._cache.setdefault(key, value)
            return self._cache[key]

    def clear(self):
        with self._lock:
            self._cache.clear()


class WorkspaceResource:
    """Scratch-memory budget descriptor.

    (ref: core/resource/workspace_resource.hpp — an RMM limiting adaptor over
    the workspace pool). XLA owns allocation on TPU; what algorithms need is
    the *budget* so they can pick batch sizes that fit. ``allocation_limit``
    is in bytes.
    """

    def __init__(self, allocation_limit: Optional[int] = None):
        if allocation_limit is None:
            allocation_limit = self._default_limit()
        self.allocation_limit = int(allocation_limit)

    @staticmethod
    def _default_limit() -> int:
        dev = jax.devices()[0]
        stats = dev.memory_stats()
        if stats and "bytes_limit" in stats:
            # match the reference's default: a fraction of device memory
            return int(stats["bytes_limit"]) // 4
        if dev.platform == "tpu":
            raise RuntimeError(
                "WorkspaceResource: the TPU reports no memory_stats() "
                "bytes_limit — cannot size the workspace budget")
        return 1 << 30  # 1 GiB on the CPU test platform

    def batch_rows(self, row_bytes: int, minimum: int = 1) -> int:
        """How many rows of ``row_bytes`` fit in the budget."""
        return max(minimum, self.allocation_limit // max(1, row_bytes))


class Resources:
    """Type-indexed lazy resource container.

    (ref: core/resources.hpp:39 ``class resources`` — ``add_resource_factory``
    registers, ``get_resource<T>`` instantiates on first use under a mutex;
    copies share the underlying store.)
    """

    def __init__(self, _shared_from: Optional["Resources"] = None):
        if _shared_from is not None:
            # shallow copy shares factories and instantiated resources
            self._factories = _shared_from._factories
            self._resources = _shared_from._resources
            self._lock = _shared_from._lock
        else:
            self._factories: Dict[Any, ResourceFactory] = {}
            self._resources: Dict[Any, Any] = {}
            self._lock = threading.RLock()

    # -- registry ---------------------------------------------------------
    def add_resource_factory(self, rtype, factory: ResourceFactory) -> None:
        """Register (or replace) the factory for a slot.
        (ref: resources.hpp:79)"""
        with self._lock:
            self._factories[rtype] = factory
            self._resources.pop(rtype, None)

    def has_resource_factory(self, rtype) -> bool:
        with self._lock:
            return rtype in self._factories or rtype in self._resources

    def get_resource(self, rtype):
        """Get the resource in a slot, building it lazily on first access.
        (ref: resources.hpp:104-120)"""
        with self._lock:
            if rtype not in self._resources:
                factory = self._factories.get(rtype)
                if factory is None:
                    raise LogicError(f"no resource factory registered for {rtype}")
                self._resources[rtype] = factory(self)
            return self._resources[rtype]

    def set_resource(self, rtype, value) -> None:
        """Directly install an instantiated resource (used e.g. by comms
        injection — ref: core/resource/comms.hpp ``set_comms``)."""
        with self._lock:
            self._resources[rtype] = value

    # -- common accessors (ref: one-file-per-resource accessors under
    #    core/resource/*.hpp) ------------------------------------------------
    @property
    def device(self):
        return self.get_resource(ResourceType.DEVICE)

    @property
    def device_id(self) -> int:
        return self.get_resource(ResourceType.DEVICE_ID)

    @property
    def platform(self) -> str:
        return self.get_resource(ResourceType.PLATFORM)

    @property
    def mesh(self) -> jax.sharding.Mesh:
        return self.get_resource(ResourceType.MESH)

    def set_mesh(self, mesh: jax.sharding.Mesh) -> None:
        self.set_resource(ResourceType.MESH, mesh)

    @property
    def rng(self) -> KeyStream:
        return self.get_resource(ResourceType.RNG)

    @property
    def compile_cache(self) -> CompileCache:
        return self.get_resource(ResourceType.COMPILE_CACHE)

    # metrics sink (ref role: mr/resource_monitor.hpp + nvtx attribution;
    # here: the raft_tpu.observability registry)
    @property
    def metrics(self):
        """The handle's metrics sink. Falls back to the process-global
        :func:`raft_tpu.observability.get_registry` when no factory is
        registered, so every handle is observable by default."""
        if not self.has_resource_factory(ResourceType.METRICS):
            from raft_tpu.observability import get_registry

            return get_registry()
        return self.get_resource(ResourceType.METRICS)

    def set_metrics(self, registry) -> None:
        """Install a handle-scoped MetricsRegistry (e.g. to isolate one
        tenant's counters from the process-global registry)."""
        self.set_resource(ResourceType.METRICS, registry)

    # cost-model profiler (static XLA cost capture + roofline — see
    # raft_tpu.observability.profiler)
    @property
    def profiler(self):
        """The handle's cost-model profiler. Falls back to the
        process-global :func:`raft_tpu.observability.get_profiler` when
        no factory is registered — the same default-observable contract
        as ``metrics``."""
        if not self.has_resource_factory(ResourceType.PROFILER):
            from raft_tpu.observability import get_profiler

            return get_profiler()
        return self.get_resource(ResourceType.PROFILER)

    def set_profiler(self, profiler) -> None:
        """Install a handle-scoped Profiler (e.g. to pin roofline peaks
        to a non-default device, or isolate records per tenant)."""
        self.set_resource(ResourceType.PROFILER, profiler)

    # recovery policies (retry budgets + degradation ladders — see
    # raft_tpu.resilience.policy)
    @property
    def resilience(self):
        """The handle's recovery-policy table. Falls back to the
        process-global :func:`raft_tpu.resilience.get_policy_table`
        when no factory is registered — the same default contract as
        ``metrics``/``profiler``."""
        if not self.has_resource_factory(ResourceType.RESILIENCE):
            from raft_tpu.resilience.policy import get_policy_table

            return get_policy_table()
        return self.get_resource(ResourceType.RESILIENCE)

    def set_resilience(self, table) -> None:
        """Install a handle-scoped PolicyTable (e.g. to disable retries
        for one tenant, or tighten the ladder for a latency-bound
        caller)."""
        self.set_resource(ResourceType.RESILIENCE, table)

    @property
    def workspace(self) -> WorkspaceResource:
        return self.get_resource(ResourceType.WORKSPACE_RESOURCE)

    def set_workspace_resource(self, ws: WorkspaceResource) -> None:
        self.set_resource(ResourceType.WORKSPACE_RESOURCE, ws)

    @property
    def large_workspace(self) -> WorkspaceResource:
        return self.get_resource(ResourceType.LARGE_WORKSPACE_RESOURCE)

    # comms (ref: core/resource/comms.hpp, sub_comms.hpp)
    def set_comms(self, comms) -> None:
        self.set_resource(ResourceType.COMMUNICATOR, comms)

    def get_comms(self):
        expects(
            self.has_resource_factory(ResourceType.COMMUNICATOR)
            or ResourceType.COMMUNICATOR in self._resources,
            "communicator is not set on this handle",
        )
        return self.get_resource(ResourceType.COMMUNICATOR)

    def comms_initialized(self) -> bool:
        with self._lock:
            return ResourceType.COMMUNICATOR in self._resources

    def set_subcomm(self, key: str, comms) -> None:
        with self._lock:
            subs = self._resources.setdefault(ResourceType.SUB_COMMUNICATOR, {})
            subs[key] = comms

    def get_subcomm(self, key: str):
        with self._lock:
            subs = self._resources.get(ResourceType.SUB_COMMUNICATOR, {})
            expects(key in subs, "sub-communicator %r is not set", key)
            return subs[key]

    # sync (ref: device_resources::sync_stream → here: drain dispatched work)
    def sync(self, *arrays):
        """Block until given arrays (or nothing, for API parity) are done."""
        from raft_tpu.core import interruptible

        if arrays:
            return interruptible.synchronize(*arrays)
        return None


def _default_device_index() -> int:
    return 0


def _default_resilience_factory(res: Resources):
    """Default RESILIENCE slot: the process-global recovery-policy
    table (override per handle with ``set_resilience``)."""
    from raft_tpu.resilience.policy import get_policy_table

    return get_policy_table()


def _default_profiler_factory(res: Resources):
    """Default PROFILER slot: a profiler whose roofline peaks match the
    HANDLE's device (not necessarily jax.devices()[0]) and whose records
    publish into the handle's metrics sink."""
    from raft_tpu.observability.profiler import Profiler
    from raft_tpu.utils.arch import chip_spec

    try:
        spec = chip_spec(res.device)
    except Exception:
        spec = None
    return Profiler(registry=None, spec=spec)


class DeviceResources(Resources):
    """The concrete per-device handle.

    (ref: core/device_resources.hpp:53 — pre-registers device_id, stream,
    stream-pool factories and exposes vendor-handle accessors. Here the
    pre-registered slots are device / platform / mesh(single device) /
    rng / compile cache / workspace budgets.)
    """

    def __init__(
        self,
        device: Optional[jax.Device] = None,
        seed: int = 0,
        workspace_limit: Optional[int] = None,
    ):
        super().__init__()
        dev = device if device is not None else jax.devices()[_default_device_index()]
        self.add_resource_factory(ResourceType.DEVICE, lambda r: dev)
        self.add_resource_factory(ResourceType.DEVICE_ID, lambda r: dev.id)
        self.add_resource_factory(ResourceType.PLATFORM, lambda r: dev.platform)
        self.add_resource_factory(
            ResourceType.DEVICE_PROPERTIES,
            lambda r: {
                "device_kind": dev.device_kind,
                "platform": dev.platform,
                "memory_stats": (dev.memory_stats() if hasattr(dev, "memory_stats") else None),
            },
        )
        self.add_resource_factory(
            ResourceType.MESH,
            lambda r: jax.sharding.Mesh(np.array([dev]), ("x",)),
        )
        self.add_resource_factory(ResourceType.RNG, lambda r: KeyStream(seed))
        self.add_resource_factory(ResourceType.COMPILE_CACHE, lambda r: CompileCache())
        self.add_resource_factory(
            ResourceType.WORKSPACE_RESOURCE,
            lambda r: WorkspaceResource(workspace_limit),
        )
        self.add_resource_factory(
            ResourceType.LARGE_WORKSPACE_RESOURCE,
            lambda r: WorkspaceResource(None),
        )
        self.add_resource_factory(ResourceType.MEMORY_KIND, lambda r: "device")
        self.add_resource_factory(ResourceType.HOST_MEMORY_KIND, lambda r: "pinned_host")
        # no METRICS factory: ``metrics`` resolves the process-global
        # registry on every access, so a swapped global (tests, tenants)
        # is never shadowed by a stale cached one
        self.add_resource_factory(ResourceType.PROFILER, _default_profiler_factory)
        self.add_resource_factory(ResourceType.RESILIENCE,
                                  _default_resilience_factory)


def _device_resources_reduce(self):
    # Pickling recreates a FRESH handle (resources are process-local), the
    # contract pylibraft documents for its DeviceResources
    # (ref: common/handle.pyx:113-123). type(self) keeps subclasses
    # (e.g. DeviceResourcesSNMG) reconstructing as themselves.
    return (type(self), ())


DeviceResources.__reduce__ = _device_resources_reduce

# legacy alias (ref: core/handle.hpp ``handle_t``)
Handle = DeviceResources

_default_resources: Optional[DeviceResources] = None
_default_lock = threading.Lock()


def device_resources() -> DeviceResources:
    """Process-default handle, created on first use.

    (ref: core/device_resources_manager.hpp:75 ``get_device_resources()`` —
    the singleton handing out handles; the TPU runtime needs no per-thread
    stream pools, so one shared handle suffices.)
    """
    global _default_resources
    with _default_lock:
        if _default_resources is None:
            _default_resources = DeviceResources()
        return _default_resources


def ensure_resources(res: Optional[Resources]) -> Resources:
    """Accept ``None`` as "use the process-default handle" — the pythonic
    rendering of pylibraft's ``@auto_sync_handle`` default-handle behavior
    (ref: python/pylibraft/pylibraft/common/handle.pyx:196)."""
    return res if res is not None else device_resources()
