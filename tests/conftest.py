"""Test harness: force an 8-device virtual CPU platform BEFORE jax is used.

This is the distributed-without-a-cluster strategy from SURVEY.md section 4:
pjit/shard_map collectives run on 8 fake CPU devices, so multi-chip sharding
is validated on any host. JAX_PLATFORMS is only defaulted, so exporting
JAX_PLATFORMS=tpu runs the suite (and its `tpu`-marked tests) on a chip.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Tier-1 shapes are tiny, so XLA *compile* time (not execution) dominates the
# suite's wall clock on the 1-core host. O0 roughly halves compile time and is
# semantically identical for what the tests assert: every bit-parity check in
# the suite compares two programs compiled at the SAME level, and drift-bound
# checks carry explicit tolerances. Export-level override still wins.
if "xla_backend_optimization_level" not in _flags:
    _flags = (_flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = _flags

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """`tpu`-marked tests assert accelerator-only behavior (e.g. bf16 MXU
    speedups) that is meaningless on the virtual-CPU harness above — skip
    them unless the default backend really is a TPU."""
    if jax.default_backend() == "tpu":
        return
    skip = pytest.mark.skip(reason="requires a TPU backend")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip)
