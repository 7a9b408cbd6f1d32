"""Test configuration: force an 8-device virtual CPU mesh.

Tests run on the CPU, never on a chip, on a virtual 8-device mesh
(multi-chip shardings are validated here and by
__graft_entry__.dryrun_multichip; chip_smoke.py is the chip's run). This
must run before any backend is initialized, so it happens at conftest
import time.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def _native_build_error(exc) -> "object":
    """The NativeBuildError in ``exc``'s cause/context chain, if any."""
    from spark_rapids_jni_tpu.utils.nativeload import NativeBuildError
    seen = set()
    while exc is not None and id(exc) not in seen:
        seen.add(id(exc))
        if isinstance(exc, NativeBuildError):
            return exc
        exc = exc.__cause__ or exc.__context__
    return None


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Turn failures caused by an unbuildable native library into typed
    skips naming the cached failure reason.

    A host whose g++ can't compile the C++ sources (e.g. g++ 10 vs the
    JSON library) is an environment property, not a regression — the
    loader caches the failed-build signature (utils/nativeload.py) and
    every affected test would otherwise fail with the same stderr."""
    outcome = yield
    rep = outcome.get_result()
    if rep.when not in ("setup", "call") or not rep.failed:
        return
    if call.excinfo is None:
        return
    err = _native_build_error(call.excinfo.value)
    if err is None:
        return
    reason = (f"native toolchain unavailable: cannot build "
              f"{getattr(err, 'so_name', '?')} "
              f"({getattr(err, 'brief', 'g++ failed')})")
    rep.outcome = "skipped"
    rep.longrepr = (str(item.fspath), item.location[1], f"Skipped: {reason}")


# Files whose tests ``--dist loadfile`` hands out one by one instead of as
# one unit. test_multichip.py runs every partition's ops eagerly on its own
# virtual device, so each op compiles once per device and shape: its 27
# tests take over 20 minutes in one process, longer than the rest of the
# suite spread over six workers. Its only module fixture is a mesh handle.
_SPLIT_FILES = frozenset({"tests/test_multichip.py"})


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """Under ``--dist loadfile``, the file scheduler with each test of
    ``_SPLIT_FILES`` a work unit of its own."""
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class _Scheduler(LoadFileScheduling):
        def _split_scope(self, nodeid):
            path = nodeid.split("::", 1)[0]
            return nodeid if path in _SPLIT_FILES else path

    return _Scheduler(config, log)
