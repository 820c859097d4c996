"""Session setup for the test suite.

1. Force an 8-device CPU host platform *before* jax initializes its
   backend: the distribution / elastic-rescale tests need a real
   multi-device mesh.  (Individual test modules also set this defensively
   for standalone runs, but the backend is process-global — it must be in
   the environment before the first device query anywhere in the session.)
2. Provide the ``jit_recompiles`` fixture: an XLA-compilation counter the
   serving tests use to pin "compiles once per prefill bucket, never per
   prompt length".  Since PR 10 it is a thin wrapper over the library
   counter ``repro.obs.JitCompileWatcher`` (same log-record mechanism,
   now also wirable into a metrics registry).
"""

import os

import pytest

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )


@pytest.fixture
def jit_recompiles():
    # Imported here (not at module top) so the XLA_FLAGS env setup above
    # always runs before anything pulls in jax.
    from repro.obs import watch_jit_compiles

    with watch_jit_compiles() as handler:
        yield handler
