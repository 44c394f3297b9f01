"""The benchmark's own tests run on the CPU, at a tiny size, on eight
virtual devices (set before JAX starts) so that a row-sharded cell's
data and reference run as they do over a host's chips; the other tests
take the first device alone."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
