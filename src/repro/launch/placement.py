"""Where each ``launch="processes"`` worker runs: one chip per worker.

A chip belongs to one process at a time, so the launcher never opens a
device itself (:func:`keep_launcher_off_chip`) and hands every worker
exactly one chip through libtpu's per-process environment
(:func:`worker_envs`). Chips are counted from the host's device nodes, so
nothing here initialises JAX. Where the launcher's environment pins
``JAX_PLATFORMS=cpu`` (the tests) the workers inherit it unchanged.

Stdlib only: the launcher imports this before any JAX call.
"""

from __future__ import annotations

import glob
import os
import socket

#: exit code of a worker that could not open its device; the launcher
#: fails the run on it and never respawns (a respawn cannot fix a host)
NO_DEVICE_EXIT = 5


class PlacementError(ValueError):
    """The workers cannot each get a chip of their own (raised before any
    worker is spawned)."""


def count_chips(dev_root: str = "/dev") -> int:
    """TPU chips on this host, from ``/dev/accel*`` or, on hosts that
    expose them through VFIO, ``/dev/vfio/<n>``."""
    accel = glob.glob(os.path.join(dev_root, "accel[0-9]*"))
    if accel:
        return len(accel)
    return len(glob.glob(os.path.join(dev_root, "vfio", "[0-9]*")))


def _free_ports(n: int) -> list[int]:
    socks = []
    try:
        for _ in range(n):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.bind(("localhost", 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def chips_for(n_workers: int, env=None, chips: int | None = None) -> bool:
    """True when the workers go one per chip; False when they run on the
    host CPU (CPU-pinned launcher, or no chips on this host). Raises
    :class:`PlacementError` when there are more workers than chips."""
    env = os.environ if env is None else env
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return False
    chips = count_chips() if chips is None else chips
    if chips == 0:
        return False
    if n_workers > chips:
        raise PlacementError(
            f"launch='processes' runs one worker per chip, but the plan has "
            f"{n_workers} shards and this host has {chips} chips — plan with "
            f"MemoryBudget(n_shards<={chips})"
        )
    return True


def worker_envs(n_workers: int, env=None,
                chips: int | None = None) -> list[dict]:
    """Per-worker environment overrides, one dict per worker.

    CPU-pinned launcher or a host without chips: no overrides. Otherwise
    worker ``w`` sees only chip ``w`` (``TPU_VISIBLE_CHIPS``) as a
    one-chip, one-process slice with its own ``TPU_PROCESS_PORT``, and
    ``JAX_PLATFORMS=tpu`` so a failed open raises instead of falling back
    to the CPU. Each worker holds a different chip, so the host-wide libtpu
    lock (which assumes one process per host) is lifted for them alone.
    """
    if not chips_for(n_workers, env, chips):
        return [{} for _ in range(n_workers)]
    ports = _free_ports(n_workers)
    return [
        dict(
            JAX_PLATFORMS="tpu",
            TPU_VISIBLE_CHIPS=str(w),
            TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
            TPU_PROCESS_BOUNDS="1,1,1",
            TPU_PROCESS_PORT=str(ports[w]),
            ALLOW_MULTIPLE_LIBTPU_LOAD="1",
        )
        for w in range(n_workers)
    ]


def keep_launcher_off_chip() -> None:
    """Pin this (launcher) process to the host CPU before its first JAX
    call, so the chips stay free for the workers. A process that already
    opened an accelerator cannot give it back: that is an error."""
    import jax
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        jax.config.update("jax_platforms", "cpu")
        return
    if jax.default_backend() != "cpu":
        raise PlacementError(
            "launch='processes' needs a launcher that has not opened an "
            f"accelerator, but this process already holds "
            f"{jax.default_backend()!r} devices — start the job from a "
            "process that has not run JAX on the chip"
        )


def device_info() -> dict:
    """``platform``/``kind`` of this process's first JAX device."""
    import jax

    d = jax.devices()[0]
    return dict(platform=d.platform, kind=d.device_kind)
