"""Test env: force an 8-device virtual CPU mesh before JAX initialises.

This is the "multi-node without a cluster" fake backend (SURVEY §4): every
sharding/collective path runs against 8 host-platform devices, mirroring the
reference's gloo-on-localhost trick (``/root/reference/src/accelerate/
test_utils/testing.py``) but inside one process.
"""

import os
import sys

#: ``ACCELERATE_TEST_BACKEND=tpu`` runs the suite against the attached
#: real backend instead of the virtual CPU mesh (the reference's
#: ``get_backend`` override) — that is the lane where ``require_tpu``
#: tests (e.g. the bf16-over-ICI GPipe smoke) actually execute.
_TEST_BACKEND = os.environ.get("ACCELERATE_TEST_BACKEND", "cpu").lower()

if _TEST_BACKEND == "cpu":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    if "collective_call_terminate_timeout" not in flags:
        # single-core machines time-slice all 8 device threads: a heavy
        # program can exceed XLA CPU's default 40s collective rendezvous
        # window, which ABORTS the process. Give the scheduler room.
        flags = (flags + " --xla_cpu_collective_call_terminate_timeout_seconds=600").strip()
    os.environ["XLA_FLAGS"] = flags
    # XLA sizes the CPU client's thread pools to the machine's cores
    # (``DefaultThreadPoolSize``), and each of the 8 virtual devices blocks one
    # pool thread while its program waits in a collective's rendezvous: on a
    # host of 8 cores or fewer a second program in flight then finds no thread
    # to run on and the rendezvous never completes ("Expected 8 threads to
    # join"; then the 600 s abort above). ``NPROC`` is the size XLA takes
    # instead of the core count; with room above the device count the
    # examples that hung 4 runs in 10 beside a busy machine hung 0 in 22
    # (ISSUE 47). Children a test starts inherit it.
    os.environ.setdefault("NPROC", "32")

# hermetic runs: no test (and no child a test starts) reads or fills the
# persistent compile cache, so a run never depends on what an earlier one
# left behind and the checkout stays small enough to copy to the chip
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def reset_state():
    """Reset the Borg singletons between tests (reference
    ``AccelerateTestCase``, ``test_utils/testing.py:479``)."""
    yield
    from accelerate_tpu.ops.attention import set_attention_context
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    set_attention_context(None)
    from accelerate_tpu.parallel.pipeline import set_default_microbatches

    set_default_microbatches(0)
    from accelerate_tpu.resilience.preemption import get_active_handler

    handler = get_active_handler()
    if handler is not None:  # restore the process signal handlers
        handler.uninstall()
    from accelerate_tpu.analysis.sanitizer import set_active_sanitizer

    set_active_sanitizer(None)
    from accelerate_tpu.serving.flight import set_active_flight_recorder

    set_active_flight_recorder(None)
