"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform (the reference tests a
16-rank in-process job the same way — test/gtest/common/test_ucc.h:209; we
mirror it with 8 virtual chips so multi-chip sharding paths compile and
execute without TPU hardware). Must run before jax is first imported.
"""
import os

# FORCE (not setdefault): tier-1 runs on the CPU even on a host with a
# chip; TPU compiles are checked against a described topology
# (tests/test_tpu_compile.py), which needs no chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# the always-on flight recorder (obs/flight.py) dumps to ucc_flight.json
# in the CWD by default; tests that trigger collection (watchdog dumps,
# rank-failure drills) must not litter the repo checkout — route the
# default to a per-session temp file (read at ucc_tpu import, so this
# must run before the first test import)
if "UCC_FLIGHT_FILE" not in os.environ:
    import tempfile
    os.environ["UCC_FLIGHT_FILE"] = os.path.join(
        tempfile.gettempdir(), f"ucc_flight_test_{os.getpid()}.json")

# the DSL program/search/cost caches (ucc_tpu/dsl, ISSUE 14) default to
# ~/.cache/ucc_tpu — tests must neither read a developer's real caches
# (stale searched winners would change candidate lists under test) nor
# write into them; route all three to per-session temp files
import tempfile as _tf
for _var, _name in (("UCC_GEN_PROG_CACHE", "programs.pkl"),
                    ("UCC_GEN_SEARCH_CACHE", "search.json"),
                    ("UCC_GEN_COST_CACHE", "cost.json")):
    if _var not in os.environ:
        os.environ[_var] = os.path.join(
            _tf.gettempdir(), f"ucc_test_{os.getpid()}_{_name}")

# a jax imported before this file read its config already: force the
# platform through the runtime config too (backends init lazily)
import sys
if "jax" in sys.modules:
    import jax
    jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; long-running acceptance drills (the
    # churn soak) opt out of it with this marker
    config.addinivalue_line(
        "markers", "slow: long-running acceptance drill, excluded from "
        "the tier-1 sweep")
