"""Shared preamble for the TPU measurement scripts, in ONE place.

Contract: call ``gate()`` first thing in ``main()``, before anything
touches a JAX backend. It points the persistent compile cache at its
directory (:func:`raft_tpu.utils.compile_cache.use_compile_cache`) and
returns ``dry``:

- ``JAX_PLATFORMS=cpu`` set explicitly ⇒ ``True``: the tiny-scale
  harness rehearsal on 8 virtual CPU devices; callers must not write
  TPU artifacts in this mode;
- otherwise ``False`` once JAX reports a TPU, and a ``RuntimeError``
  when it does not — a measurement never falls back to the CPU.
"""

from __future__ import annotations

import os


def gate() -> bool:
    from raft_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        return True
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {platform!r} (set "
                           f"JAX_PLATFORMS=cpu for a CPU rehearsal)")
    return False
