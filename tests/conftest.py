"""Test substrate: an in-process multi-device session.

The whole test session runs with 8 XLA host-platform devices: the flag is
appended to ``XLA_FLAGS`` below, *before* anything can import jax (pytest
loads conftest first; the backend reads the flag at its lazy first
initialisation).  The session-scoped ``mesh8`` fixture hands tests a real
8-device ``("d",)`` mesh, so multi-device paths (shard_map collectives,
GSPMD lowering, sharded restore) run in-process instead of behind
``subprocess.run`` — same coverage, one process, debuggable.  An
externally-set device-count flag wins (that is how CI pins the single- and
multi-device legs); tests needing the mesh skip when fewer than 8 devices
exist.  Single-device numerics are unchanged: computations still place onto
device 0 unless a test shards them explicitly.

Hypothesis runs derandomized and without an example database, so a
property test sees the same examples on every run.
"""
from __future__ import annotations

import os

_DEVCOUNT_FLAG = "--xla_force_host_platform_device_count"
# stash what the user actually set, so tests that spawn CLI subprocesses
# (the test_system smoke tests) can hand them the stock environment
ORIG_XLA_FLAGS = os.environ.get("XLA_FLAGS", "")
if _DEVCOUNT_FLAG not in ORIG_XLA_FLAGS:
    os.environ["XLA_FLAGS"] = (ORIG_XLA_FLAGS + f" {_DEVCOUNT_FLAG}=8").strip()
# the persistent compilation cache stays off in this session and in the CLI
# subprocesses it starts (they inherit the environment)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import pytest  # noqa: E402
from hypothesis import settings  # noqa: E402

# property tests draw the same examples on every run and write no example
# database into the checkout
settings.register_profile("repo", derandomize=True, database=None)
settings.load_profile("repo")


@pytest.fixture(scope="session")
def mesh8():
    """A real 8-device ("d",) mesh on the host platform, in-process."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices "
                    f"(XLA_FLAGS {_DEVCOUNT_FLAG}=8; "
                    f"have {jax.device_count()})")
    return jax.make_mesh((8,), ("d",),
                         axis_types=(jax.sharding.AxisType.Auto,))
