"""Force JAX onto a virtual 8-device CPU mesh for all tests.

Tier-1 runs on the CPU: multi-chip hardware is not available in CI, so
sharding tests run against xla_force_host_platform_device_count=8, and
the "device" of the feeder tests is the CPU backend or the stub
(block/device_backend.py StubDeviceBackend). The pins are set before
jax is first imported, which is all JAX 0.9 needs. The chip is reached
through `python chip_smoke.py`, never through the tests.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# feeders in tests never take a device verdict of their own accord:
# mode "auto" becomes "off" (JAX is not imported by forked servers),
# and the tests that exercise the device route say so explicitly
os.environ["GARAGE_TPU_DEVICE"] = "off"
# tier-1 is hermetic: what one run compiled must not decide what the
# next one executes, so the persistent compile cache (ops/jaxenv.py)
# stays off here; tests/test_jaxenv.py and tests/test_chip_smoke.py
# turn it back on for the children that are about it
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# enforce the metric naming contract at registration time (the runtime
# half of the static GL07 rule; utils/metrics.py)
os.environ.setdefault("GARAGE_METRICS_STRICT", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402,F401  (imported here so the pins above apply)

import pytest  # noqa: E402

from garage_tpu.utils import sanitizer  # noqa: E402

if sanitizer.armed():
    # runtime asyncio sanitizer (ISSUE 14): loop-stall detector +
    # teardown leak/conservation checks. CI exports GARAGE_SANITIZE=1
    # for tier-1 and the nightly soak.
    sanitizer.install()


@pytest.fixture(autouse=True)
def _sanitizer_reports():
    """Fail the test that stalled the loop / leaked a task or lock /
    broke budget conservation — the report names the culprit frame."""
    if sanitizer.armed():
        sanitizer.drain_reports()  # a prior test's tail must not bleed
    yield
    if not sanitizer.armed():
        return
    reports = sanitizer.drain_reports()
    if reports:
        detail = "\n".join(f"[{r['kind']}] {r['detail']}"
                           for r in reports)
        pytest.fail(f"sanitizer reports (GARAGE_SANITIZE=1):\n{detail}",
                    pytrace=False)


@pytest.fixture(params=["memory", "sqlite", "lsm"])
def db_engine(request) -> str:
    """The engine axis: every db/table test that takes this fixture runs
    once per KV engine, so a new engine (lsm) inherits the whole
    existing suite for free (ISSUE 7 satellite; mirrors src/db/test.rs
    running one suite over every adapter)."""
    return request.param
