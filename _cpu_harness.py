"""The one copy of the virtual-CPU-mesh bootstrap.

Shared by tests/conftest.py, examples/_setup.py and chip_smoke.py's
rehearsal mode.  Call ``force_cpu_mesh()`` BEFORE the first use of JAX in
the process.

- ``JAX_PLATFORMS=cpu`` in the environment: JAX reads it at start-up, and
  children (multihost forks, example subprocesses) inherit it.
- ``--xla_force_host_platform_device_count``: the virtual device mesh,
  the JAX analog of the reference's ``addprocs`` harness.
- ``jax.config.update`` as well, for a process that imported JAX before
  the environment was set.
"""

import os


def force_cpu_mesh(device_count: int = 8) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count"
            f"={device_count}").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
