"""Backend/platform helpers.

How the program finds its device: JAX picks the platform (``JAX_PLATFORMS``,
else the best one present). Tests and CPU rehearsals run with
``JAX_PLATFORMS=cpu``; ``use_fake_cpu_devices(n)`` (the ``--fake_devices N``
flag of every example) additionally presents ``n`` virtual CPU devices — the
framework's stand-in for a multi-chip test rig (SURVEY.md §4), so every
DP/mesh code path runs with no TPU attached. It must be called before the
first JAX backend touch (any ``jax.devices()`` / computation).

On a TPU host the chip belongs to ONE process at a time: a process that has
initialised its backend holds every local chip until it exits, and a second
process that asks for the TPU fails at start-up ("The TPU is already in use
by process with pid N"). So a parent that spawns JAX workers must stay off
JAX itself (:func:`holds_accelerator` is the check spawners make), and a host
runs one worker process, which drives all of its chips
(:func:`local_tpu_chips` is how a launcher that must not touch JAX sees
them).
"""

from __future__ import annotations

import glob
import os

#: The checkout root (the directory holding the package directory).
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def use_fake_cpu_devices(n: int = 8) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")


def on_tpu() -> bool:
    """True when the default backend compiles for TPU (the predicate the
    Pallas kernels key on — same check as ops/flash_attention.py)."""
    import jax

    return jax.default_backend() == "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points call this before their first compile. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set (a chip-run machine sets it so the
    cache survives between calls) JAX already uses that directory and
    nothing is set here. Otherwise the cache lives at ONE fixed path inside
    the checkout, ``<repo>/.jax_cache`` — the path is part of the cache key,
    so it is never derived from a temporary name, a pid or the time — and
    the variable is exported so child processes use the same directory.

    Being what every entry point passes before its first compile, this is
    also where the process's set-up timeline starts: from here on every
    compile and every backend's opening writes a slice
    (``obs/xla.py`` ``install_dispatcher``).
    """
    from distributed_pytorch_tpu.obs.xla import install_dispatcher

    install_dispatcher()
    path = os.environ.get(COMPILE_CACHE_ENV)
    if path:
        return path
    path = os.path.join(REPO_ROOT, ".jax_cache")
    os.environ[COMPILE_CACHE_ENV] = path

    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_platform(fake_devices: int = 0) -> None:
    """What every entry point does before its first JAX call: present
    ``fake_devices`` virtual CPU devices when asked (the ``--fake_devices N``
    debug flag), and place the compile cache."""
    if fake_devices:
        use_fake_cpu_devices(fake_devices)
    enable_compile_cache()


def holds_accelerator() -> bool:
    """True when THIS process has already opened a non-CPU backend — from
    then on no child process can open the same chips."""
    import jax
    from jax._src import xla_bridge

    return (
        xla_bridge.backends_are_initialized()
        and jax.default_backend() != "cpu"
    )


def local_tpu_chips() -> int:
    """Number of TPU chips on this host, read from their device nodes —
    for launchers, which must not initialise JAX to find out (0 on a
    machine with no TPU)."""
    nodes = glob.glob("/dev/vfio/[0-9]*") + glob.glob("/dev/accel[0-9]*")
    return len(nodes)


def workers_pinned_to_cpu(env) -> bool:
    """True when ``env`` (a worker's environment) restricts JAX to the CPU,
    so the worker never asks for a TPU."""
    platforms = env.get("JAX_PLATFORMS", "").strip().lower()
    return bool(platforms) and "tpu" not in platforms.split(",")
