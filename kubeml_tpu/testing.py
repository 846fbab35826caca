"""Test/dry-run utilities: a multi-device virtual CPU mesh without
hardware.

Tests and local drives run on the CPU backend (`JAX_PLATFORMS=cpu`)
with N virtual devices, which is the supported way to exercise
multi-chip sharding in a sandbox that has no accelerator. The recipe
lives here once — shared by the test conftest, the driver dry-run
entry, and every launcher that spawns CPU-emulated children.
"""

from __future__ import annotations


def virtual_cpu_env(n: int) -> dict:
    """Env vars that make a CHILD python process CPU-targeted with n
    virtual devices from interpreter start: the one copy of the recipe
    for every launcher that spawns CPU-emulated children (PS standalone
    spawns, the distributed launcher's --emulate-cpu, demo tools, test
    fixtures). JAX-free — safe to import from processes that must not
    initialize a backend."""
    return {"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": str(n)}


def ensure_virtual_cpu_devices(n: int) -> None:
    """Make `jax.devices()` return at least n CPU devices (idempotent).

    Before the first backend initialization this only sets the two
    config options; if a backend was already initialized with fewer
    devices (or another platform) it is cleared and re-targeted."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    try:
        # legal only before the backend initializes
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass
    if len(jax.devices()) >= n and jax.devices()[0].platform == "cpu":
        return
    import jax.extend.backend
    jax.extend.backend.clear_backends()
    jax.config.update("jax_num_cpu_devices", n)
    assert len(jax.devices()) >= n, \
        f"failed to create {n} virtual CPU devices"
