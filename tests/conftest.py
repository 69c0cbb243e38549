"""Test configuration.

Tests are CPU tests: JAX is forced onto a virtual 8-device CPU mesh so
the multi-chip sharding logic is exercised without TPU hardware. The
env var is set before jax is imported and the config flag right after,
so no other backend initializes in a test process. The chip itself is
covered by ``chip_smoke.py`` (run on the chip machine) and by
``tests/test_tpu_compile.py`` (compiles for a described chip).
"""

import glob
import json
import os
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
# CPU attach is near-instant; a generous deadline keeps the device path
# deterministic in tests (plugins would otherwise race the attach thread)
os.environ.setdefault("FBTPU_ATTACH_WAIT_S", "120")
# the production launch deadline (120 s) is sized for a first compile
# on the chip; here it only bounds what one wedged launch can cost its
# test — long enough for a first CPU compile under six busy workers,
# far short of the driver's time limit
os.environ.setdefault("FBTPU_LAUNCH_DEADLINE_S", "30")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

try:
    import jax

    jax.config.update("jax_platforms", "cpu")
except Exception:  # pragma: no cover - jax absent: ops tests skip themselves
    pass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    # markers registered here (no pytest.ini in this repo): both stay in
    # the default tier-1 run; the names exist so CI lanes can select or
    # shed them without editing the suite (-m sanitizer / -m 'not ...')
    config.addinivalue_line(
        "markers",
        "sanitizer: subprocess ASan/TSan builds of the native data "
        "plane (tests/test_asan_native.py, tests/test_tsan_native.py)")
    config.addinivalue_line(
        "markers", "slow: long-running; tier-1 runs -m 'not slow'")
    config.addinivalue_line(
        "markers",
        "soak: crash-recovery soak matrix (tests/test_failpoints.py) — "
        "subprocess SIGKILL/restart cycles; the full matrix is also "
        "marked slow so tier-1 keeps only the short deterministic slice")
    config.addinivalue_line(
        "markers",
        "mesh: simulated-mesh lane (8 virtual CPU devices via "
        "--xla_force_host_platform_device_count, set above) — the fast "
        "flux/sharding subset runs unmarked in tier-1; the full mesh "
        "matrix is additionally marked slow")


@pytest.fixture(scope="session")
def counters_of_declared_metrics() -> set:
    """The program's counters that a declared data-only per-layer metric
    of the benchmark reads: both terms of a ``counters:ratio``, and the
    plugin's ``scan_elements`` of an ``element_cost:ns_per_element`` —
    what the "every timing key feeds a metric or a check" tests hold a
    plugin's ``_TIMING_KEYS`` to."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        declared = {m["name"] for m in json.load(f)["per_layer"]}
    read = set()
    for path in glob.glob(os.path.join(repo, "benchmark", "layer_metrics",
                                       "*.json")):
        if os.path.basename(path)[:-5] not in declared:
            continue
        with open(path) as f:
            spec = json.load(f)
        args = spec["args"]
        if spec["reader"] == "counters:ratio":
            read |= {args["num"], args["den"]}
        elif spec["reader"] == "element_cost:ns_per_element":
            read.add(f"filter.{args['plugin']}.scan_elements")
    return read
