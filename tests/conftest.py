"""Test config: force a hermetic 8-device virtual CPU mesh.

Before jax initializes a backend: JAX_PLATFORMS=cpu with
xla_force_host_platform_device_count=8 — tier-1 runs on CPU-only
machines, and multi-chip sharding is validated on virtual CPU devices.
The pin lives in vproxy_tpu.utils.jaxenv (shared with the _verify_*
flows, the tools' main() and __graft_entry__.py).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from vproxy_tpu.utils.jaxenv import force_cpu  # noqa: E402

force_cpu(8)
