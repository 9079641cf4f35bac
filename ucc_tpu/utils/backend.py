"""Backend set-up for the entry points: in-process, no probes, no fallback.

JAX finds its accelerator itself. A caller that wants the CPU sets
``JAX_PLATFORMS=cpu``; only then does ``setup_backend`` give the CPU
platform extra virtual devices. Whether a Pallas kernel runs compiled or
in interpret mode follows the devices it is built for (``is_tpu``), never
the process's default backend.
"""
from __future__ import annotations

import os

#: in-checkout compile cache used when JAX_COMPILATION_CACHE_DIR is unset:
#: a fixed path, since the path is part of the cache key (git-ignored)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_backend(virtual_cpu_devices: int = 0) -> str:
    """Initialise JAX's backend in this process; return its platform.

    With ``JAX_PLATFORMS=cpu`` the CPU platform first gets
    ``virtual_cpu_devices`` devices (this must precede backend init). A
    backend that fails to initialise raises."""
    if virtual_cpu_devices and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{virtual_cpu_devices}").strip()
    import jax
    return jax.devices()[0].platform


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing else; otherwise the cache goes to ``REPO_CACHE_DIR``.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


def is_tpu(mesh=None) -> bool:
    """True iff ``mesh`` (a ``Mesh``; None = the enclosing shard_map's
    abstract mesh) is made of TPU devices — the test that picks compiled
    Pallas over interpret mode. A described topology counts as TPU."""
    if mesh is None:
        import jax
        dev = getattr(jax.sharding.get_abstract_mesh(), "abstract_device",
                      None)
        return dev is not None and \
            str(dev.device_kind).lower().startswith("tpu")
    return mesh.devices.flat[0].platform == "tpu"
