"""Production mesh construction (assignment MULTI-POD DRY-RUN §1) plus the
distributed box-fabric mesh helpers (``repro.parallel.fabric``).

Every constructor is a function, not a module-level constant: importing
this module never touches jax device state (``resolve_fabric_shards`` and
``fabric_mesh`` only enumerate devices when called without an explicit
device list).
"""

from __future__ import annotations

import os
import re
from typing import Optional, Sequence

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) 'data' x 'model' single pod (256 chips), or
    (2, 16, 16) 'pod' x 'data' x 'model' for 2 pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh over the real local device (smoke tests)."""
    return jax.make_mesh((1, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# distributed box fabric (repro.parallel.fabric)
# ---------------------------------------------------------------------------

FABRIC_AXIS = "shards"
FABRIC_SHARDS_ENV = "REPRO_FABRIC_SHARDS"

_FORCED_DEVICES_RE = re.compile(
    r"--xla_force_host_platform_device_count=(\d+)")


def host_device_count_from_flags(flags: Optional[str] = None
                                 ) -> Optional[int]:
    """The forced host-platform device count requested by an ``XLA_FLAGS``
    string (``None`` = read the environment), or ``None`` when the flag is
    absent. When the flag repeats, the last occurrence wins — XLA's own
    parsing rule, so what this returns is what ``jax.devices()`` will
    materialize on the cpu platform."""
    if flags is None:
        flags = os.environ.get("XLA_FLAGS", "")
    hits = _FORCED_DEVICES_RE.findall(flags or "")
    return int(hits[-1]) if hits else None


def resolve_fabric_shards(requested: Optional[int] = None,
                          devices: Optional[Sequence] = None) -> int:
    """Number of fabric shards for this process: an explicit request wins,
    then the ``REPRO_FABRIC_SHARDS`` env override, then one shard per
    local device. Always >= 1. More shards than devices is legal — the
    fabric executes shards as host partitions and only needs devices for
    the optional mesh (``psum``) reduction."""
    if requested is not None:
        return max(1, int(requested))
    env = os.environ.get(FABRIC_SHARDS_ENV, "").strip()
    if env:
        return max(1, int(env))
    if devices is None:
        devices = jax.devices()
    return max(1, len(devices))


def fabric_mesh(n_shards: Optional[int] = None,
                devices: Optional[Sequence] = None):
    """1-D device mesh with the single axis ``"shards"`` over the first
    ``n_shards`` devices — the fabric's reduction mesh (one device per
    shard partial). Raises ``ValueError`` when the host exposes fewer
    devices than shards; under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` the cpu
    platform materializes N of them, which is how the CI fabric job runs
    48-way meshes on a 2-core box."""
    import numpy as np
    from jax.sharding import Mesh

    devices = list(jax.devices()) if devices is None else list(devices)
    n = resolve_fabric_shards(n_shards, devices)
    if n > len(devices):
        raise ValueError(
            f"fabric_mesh: {n} shards but only {len(devices)} device(s); "
            f"force more with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} (cpu platform)")
    return Mesh(np.asarray(devices[:n]), (FABRIC_AXIS,))


def maybe_init_distributed(coordinator: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> bool:
    """Gated ``jax.distributed.initialize`` for multi-process fabrics.

    Configuration comes from the arguments or the environment
    (``REPRO_FABRIC_COORDINATOR``, ``REPRO_FABRIC_NUM_PROCESSES``,
    ``REPRO_FABRIC_PROCESS_ID``). Returns True when the distributed
    runtime is (or already was) initialized, False when unconfigured — the
    fabric worker CLI then falls back to file-based partial merging
    (``fabric.merge_partials``), which needs no cross-process runtime at
    all. A configured initialization that fails raises: a worker must not
    carry on believing it is alone."""
    coordinator = coordinator or os.environ.get("REPRO_FABRIC_COORDINATOR")
    if num_processes is None:
        env = os.environ.get("REPRO_FABRIC_NUM_PROCESSES", "").strip()
        num_processes = int(env) if env else None
    if process_id is None:
        env = os.environ.get("REPRO_FABRIC_PROCESS_ID", "").strip()
        process_id = int(env) if env else None
    if not coordinator or num_processes is None or process_id is None:
        return False
    if not jax.distributed.is_initialized():
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    return True


# TPU v5e-class hardware constants used by the roofline analysis.
HW = dict(
    peak_bf16_flops=197e12,      # per chip
    hbm_bandwidth=819e9,         # bytes/s per chip
    ici_bandwidth=50e9,          # bytes/s per link
    hbm_bytes=16 * 2**30,        # capacity per chip
    chips_single_pod=256,
    chips_multi_pod=512,
)
