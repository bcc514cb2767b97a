"""Environment contract for the driver's multi-chip dry-run child.

_child_env is the pure function that builds the CPU child's
environment, tested here without spawning a process.  Reference
analog: topology validation without production hardware (reference
Makefile:74-102 runs the cluster tests against local redis processes).
"""

import importlib.util
import os

_SPEC = importlib.util.spec_from_file_location(
    "graft_entry_under_test",
    os.path.join(os.path.dirname(__file__), "..", "__graft_entry__.py"),
)
graft = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(graft)


def test_child_env_forces_cpu_platform_and_device_count():
    env = graft._child_env({"JAX_PLATFORMS": "tpu"}, 8)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert env["RATELIMIT_TPU_DRYRUN_CHILD"] == "1"


def test_child_env_replaces_stale_device_count_flag():
    env = graft._child_env(
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=2 --xla_foo=1"},
        8,
    )
    flags = env["XLA_FLAGS"].split()
    assert "--xla_foo=1" in flags
    assert "--xla_force_host_platform_device_count=8" in flags
    assert "--xla_force_host_platform_device_count=2" not in flags


def test_child_env_preserves_unrelated_vars():
    env = graft._child_env({"HOME": "/root", "PATH": "/usr/bin"}, 4)
    assert env["HOME"] == "/root"
    assert env["PATH"] == "/usr/bin"


def test_child_env_is_pure():
    base = {"JAX_PLATFORMS": "tpu", "XLA_FLAGS": "--xla_foo=1"}
    graft._child_env(base, 8)
    assert base == {"JAX_PLATFORMS": "tpu", "XLA_FLAGS": "--xla_foo=1"}


def test_parent_process_env_yields_a_cpu_child():
    # Whatever THIS process runs with, the derived child env selects
    # the cpu platform.
    env = graft._child_env(os.environ, 8)
    assert env["JAX_PLATFORMS"] == "cpu"
