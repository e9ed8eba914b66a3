"""Multi-host bring-up: the DCN half of the distributed comm backend.

The reference's whole "distributed backend" is HTTP/JSON between four Node
processes on one machine (SURVEY.md §2 audit table). Intra-model this
framework already speaks XLA collectives over ICI (parallel.mesh); this
module adds the multi-host dimension:

- ``init_multihost`` wraps ``jax.distributed.initialize``: processes find
  the coordinator over DCN, after which ``jax.devices()`` is the GLOBAL
  device list and every jit/shard_map collective can span hosts. On Cloud
  TPU pods the zero-arg form auto-discovers topology; elsewhere the
  coordinator/process-count/process-id triplet comes from args or the
  JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID env vars
  (same env-cascade style as the services, utils/envcfg.py).
- ``multihost_mesh`` lays out (dp, tp) so the tp axis stays INSIDE a host
  (ICI) and dp crosses hosts (DCN) — the scaling-book recipe: the heavy
  per-layer tensor-parallel all-reduces ride the fast fabric, only the
  light batch-sharded traffic crosses the network.

Single-process runs (tests, a one-chip or one-host machine) no-op cleanly:
``init_multihost()`` returns False and ``multihost_mesh`` degenerates to
``parallel.mesh.make_mesh``.
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh


def init_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Connect this process to the multi-host job. Returns True if a
    multi-process runtime was initialized, False for the single-process
    no-op. Must run before any other JAX call in the process."""
    coordinator_address = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])

    if coordinator_address is None and num_processes in (None, 1):
        # no coordinator configured: single-process (the tests' virtual
        # mesh, one chip, one host's chips) — nothing to initialize
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def multihost_mesh(dp: int, tp: int, devices: list | None = None) -> Mesh:
    """(dp, tp) mesh with tp contiguous within a host.

    Devices are ordered (process_index, local order) so each tp group's
    collectives stay on one host's ICI whenever ``tp`` divides the per-host
    device count; raises when a tp group would have to straddle hosts (that
    layout silently moves every per-layer all-reduce onto DCN — refuse
    rather than degrade)."""
    devices = list(devices if devices is not None else jax.devices())
    if dp * tp > len(devices):
        raise ValueError(f"mesh {dp}x{tp} needs {dp * tp} devices, have {len(devices)}")
    devices.sort(key=lambda d: (d.process_index, getattr(d, "id", 0)))
    arr = np.array(devices[: dp * tp]).reshape(dp, tp)
    # the real invariant, checked group by group (a min-per-host heuristic
    # misses uneven layouts like {6, 4} local devices): every tp row must
    # live on ONE host or its per-layer all-reduces ride DCN
    if len({d.process_index for d in arr.flatten()}) > 1:
        for row in arr:
            hosts = {d.process_index for d in row}
            if len(hosts) > 1:
                raise ValueError(
                    f"tp={tp} group straddles hosts {sorted(hosts)}: its "
                    "per-layer all-reduces would ride DCN — shrink tp, raise "
                    "dp, or even out per-host device counts")
    return Mesh(arr, ("dp", "tp"))


def process_info() -> dict:
    """Small observability blob for service /health handlers."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }
