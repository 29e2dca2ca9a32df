"""Test configuration.

Tests run on a virtual 8-device CPU mesh (multi-chip sharding validated
without TPU hardware, per the reference's in-process test philosophy —
SURVEY §4: unit tests need no cluster).  Env must be set before jax import.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the tests' explicit choice of backend (jax reads it at import); on a
# host with an accelerator the program itself would run there
os.environ["JAX_PLATFORMS"] = "cpu"
# persistent compile cache, placed by the program's own rule: the
# sha256/rolling-hash scans compile once per shape class — cached across
# test runs
from pbs_plus_tpu.utils import jaxenv  # noqa: E402

jaxenv.configure_compile_cache()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture
def fs_witness(request):
    """Runtime fs-protocol witness (utils/fswitness.py,
    docs/protocols.md): records every rename/link/unlink/open plus the
    product tree's ``fswitness.note`` events and fails the test on a
    torn durable write, a non-staged publish, or a declared-ordering
    inversion.  The chaos/crash batteries wire this autouse (crashes
    are exactly when publish ordering interleaves); ``PBS_PLUS_FSWITNESS=0``
    opts out globally, ``@pytest.mark.no_fswitness`` per test (for
    tests that deliberately write torn files to prove the READER
    rejects them)."""
    from pbs_plus_tpu.utils import fswitness
    if os.environ.get(fswitness.ENV_VAR, "1") == "0" or \
            request.node.get_closest_marker("no_fswitness"):
        yield None
        return
    with fswitness.watching() as w:
        yield w
    w.assert_clean()


@pytest.fixture
def hold_requests():
    """``hold(feeder, n)``: the first ``n`` requests submitted to a fresh
    ``DeviceFeeder`` are queued without a word to its thread, which
    hears of them when the ``n``-th arrives — so one round carries all
    ``n``, for certain and not by the luck of a linger.  A stream's
    chunker asks for a scan once a segment (models/dedup.py), so short
    streams make one request each and nothing else lets a test count on
    two of them meeting."""
    def hold(feeder, n: int) -> None:
        real, left = feeder._submit, n

        def submit(q, req):
            nonlocal left
            with feeder._cv:
                left -= 1
                if left > 0:
                    q.append(req)
                    return
            real(q, req)
        feeder._submit = submit
    return hold


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: fleet-scale soak profiles (N=500; runs in the default "
        "loop, deselect with -m 'not slow' for a quick pass)")
    config.addinivalue_line(
        "markers",
        "no_fswitness: opt a test out of the default-on fs-protocol "
        "witness (utils/fswitness.py) — for tests that deliberately "
        "write torn files to prove the READER rejects them")
