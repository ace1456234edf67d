"""Device-mesh sharding for what-if topology sweeps.

The reference's scale axis is N daemons on N network nodes; ours adds a
compute axis: thousands of topology snapshots data-parallel over a TPU
mesh (SURVEY §2.3, §5 "batched topology parallelism").  Batches shard on
the ``batch`` axis; the (small) shared edge list and candidate tables are
replicated.  XLA inserts the collectives; on multi-host TPU the same code
runs over ICI/DCN unchanged.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BATCH_AXIS = "batch"


def make_mesh(
    num_devices: Optional[int] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """One-axis (``batch``) device mesh.

    ``devices`` pins an explicit placement (survivor meshes after a
    chip quarantine, tests that must land on specific chips); else the
    first ``num_devices`` of ``jax.devices()`` are taken.  Requesting
    more devices than exist raises instead of silently truncating —
    a survivor mesh built on a miscounted pool would shard onto chips
    the health governor never verified."""
    if devices is not None:
        devices = list(devices)
        if num_devices is not None and num_devices != len(devices):
            raise ValueError(
                f"num_devices={num_devices} contradicts the explicit "
                f"devices sequence of length {len(devices)}"
            )
        if not devices:
            raise ValueError("make_mesh needs at least one device")
        return Mesh(np.array(devices), (BATCH_AXIS,))
    avail = jax.devices()
    if num_devices is not None:
        if num_devices < 1:
            raise ValueError(f"num_devices must be >= 1, got {num_devices}")
        if num_devices > len(avail):
            raise ValueError(
                f"requested num_devices={num_devices} but only "
                f"{len(avail)} jax devices are available"
            )
        avail = avail[:num_devices]
    return Mesh(np.array(avail), (BATCH_AXIS,))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(BATCH_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def padded_batch_size(mesh: Mesh, batch: int) -> int:
    n = mesh.devices.size
    return ((batch + n - 1) // n) * n


def shard_batch(mesh: Mesh, *arrays):
    """Place [B, ...] arrays with B sharded across the mesh.

    When B is not a multiple of the mesh size, every array is padded by
    REPLICATING its last batch row — a duplicated snapshot is always a
    semantically valid input regardless of what the array encodes, so
    padding needs no per-array fill rules.  Callers slice kernel outputs
    back to the original B (``padded_batch_size`` tells them the padded
    extent)."""
    sh = batch_sharding(mesh)
    n = mesh.devices.size
    out = []
    for a in arrays:
        b = a.shape[0]
        if b % n:
            # pad path only: pull to host once, replicate the tail row
            a = np.asarray(a)
            pad = np.repeat(a[-1:], padded_batch_size(mesh, b) - b, axis=0)
            a = np.concatenate([a, pad], axis=0)
        out.append(jax.device_put(a, sh))
    return tuple(out) if len(out) > 1 else out[0]


class DevicePool:
    """The live-device set for data-parallel dispatch — per-device
    failure domains made first-class.

    The mesh-collective kernels (shard_map) treat the device set as one
    opaque computer: a single sick chip corrupts the collective output
    with no way to say WHICH chip lied.  The pool instead models each
    device as an individually health-governed shard owner: work batches
    split into contiguous per-device shards, each dispatched as its own
    committed computation on its own chip, so every output row is
    attributable to exactly one device — the property the
    BackendHealthGovernor's per-chip shadow verification and quarantine
    are built on.

    Health writes (``quarantine_device`` / ``restore_device``) are
    owned by the resilience plane (the governor) and chaos — enforced
    statically by orlint's ``resilience-latch`` rule, exactly like the
    whole-backend ``device_failed`` latch.  Everything else reads.
    """

    def __init__(
        self,
        devices: Optional[Sequence] = None,
        max_devices: int = 0,
    ) -> None:
        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        if max_devices and max_devices > len(devices):
            raise ValueError(
                f"max_devices={max_devices} exceeds the {len(devices)} "
                "visible jax devices"
            )
        if max_devices:
            devices = devices[:max_devices]
        if not devices:
            raise ValueError("DevicePool needs at least one device")
        self.devices: List = devices
        self._healthy: List[bool] = [True] * len(devices)
        self.num_quarantines = 0
        self.num_restores = 0
        #: monotonic health-mask generation: bumps on every quarantine /
        #: restore.  Consumers holding per-chip device-resident state
        #: (the backend's SPF-table replicas, the warm-rebuild context)
        #: compare it against the value they captured to detect that the
        #: shard packing re-packed underneath them and stale per-chip
        #: residency must be dropped.
        self.health_seq = 0
        #: per-chip committed-dispatch tally (route-build shards, fleet
        #: root chunks, what-if failure shards all count here — the
        #: pool is the shared dispatch plane), read by the pipeline
        #: attribution gauges and `breeze resilience status`
        self.num_dispatches: List[int] = [0] * len(devices)
        #: per-chip in-flight slot ledger for the streamed dispatch
        #: loops: a dispatch occupies a slot (`note_inflight`) until its
        #: streamed completion drains it (`note_complete`), so a
        #: committed dispatch never queues behind — or waits on — an
        #: UNRELATED chip: the double-buffer loop checks `inflight()`
        #: per chip and drains only that chip's oldest work.
        self.num_inflight: List[int] = [0] * len(devices)
        #: high-watermark of concurrent in-flight dispatches per chip —
        #: the observable proof the double-buffer loop actually overlaps
        self.max_inflight: List[int] = [0] * len(devices)

    # -- read surface ------------------------------------------------------

    @property
    def size(self) -> int:
        return len(self.devices)

    @property
    def num_healthy(self) -> int:
        return sum(self._healthy)

    def is_healthy(self, index: int) -> bool:
        return self._healthy[index]

    def healthy_indices(self) -> List[int]:
        return [i for i, ok in enumerate(self._healthy) if ok]

    def quarantined_indices(self) -> List[int]:
        return [i for i, ok in enumerate(self._healthy) if not ok]

    def device(self, index: int):
        return self.devices[index]

    def healthy_mask(self) -> List[bool]:
        return list(self._healthy)

    def note_dispatch(self, index: int) -> None:
        """Count one committed dispatch on chip ``index`` (called by the
        per-shard dispatch loops alongside the actual device_put/jit
        call — the pool's view of how work actually spread)."""
        self.num_dispatches[index] += 1

    def note_inflight(self, index: int) -> None:
        """A committed dispatch on chip ``index`` entered flight (its
        outputs are not yet drained).  Counts the dispatch too."""
        self.num_dispatches[index] += 1
        self.num_inflight[index] += 1
        if self.num_inflight[index] > self.max_inflight[index]:
            self.max_inflight[index] = self.num_inflight[index]

    def note_complete(self, index: int) -> None:
        """Chip ``index``'s oldest in-flight dispatch was drained."""
        if self.num_inflight[index] > 0:
            self.num_inflight[index] -= 1

    def inflight(self, index: int) -> int:
        return self.num_inflight[index]

    def lead_index(self) -> Optional[int]:
        """Lowest-indexed healthy device (single-device dispatch target);
        None when every chip is quarantined."""
        for i, ok in enumerate(self._healthy):
            if ok:
                return i
        return None

    # -- health mutators (resilience/chaos-owned; orlint-enforced) ---------

    def quarantine_device(self, index: int) -> bool:
        """Mark one chip unhealthy; shard packing re-packs onto the
        survivors from the next dispatch on.  Returns True when the
        mask actually flipped."""
        if not self._healthy[index]:
            return False
        self._healthy[index] = False
        self.num_quarantines += 1
        self.health_seq += 1
        return True

    def restore_device(self, index: int) -> bool:
        if self._healthy[index]:
            return False
        self._healthy[index] = True
        self.num_restores += 1
        self.health_seq += 1
        return True

    # -- shard packing -----------------------------------------------------

    def shard_ranges(
        self, n_rows: int, indices: Optional[Sequence[int]] = None
    ) -> List[Tuple[int, int, int]]:
        """Deterministic contiguous packing of ``n_rows`` over the given
        device indices (default: the healthy set): ``(device_index,
        row_lo, row_hi)`` per shard, even split with the remainder on
        the leading shards.  Devices that would receive zero rows are
        dropped, so tiny batches never pay empty dispatches."""
        if indices is None:
            indices = self.healthy_indices()
        indices = list(indices)
        if not indices:
            raise ValueError("shard_ranges: no devices to pack onto")
        n_dev = len(indices)
        base, rem = divmod(n_rows, n_dev)
        out: List[Tuple[int, int, int]] = []
        lo = 0
        for k, dev in enumerate(indices):
            hi = lo + base + (1 if k < rem else 0)
            if hi > lo:
                out.append((dev, lo, hi))
            lo = hi
        return out

    def survivor_mesh(self) -> Optional[Mesh]:
        """Mesh over the CURRENT healthy set for the shard_map-collective
        engines; None when fewer than two chips survive (the collective
        path needs a real mesh to beat per-device dispatch)."""
        healthy = [self.devices[i] for i in self.healthy_indices()]
        if len(healthy) < 2:
            return None
        return make_mesh(devices=healthy)

    # -- observability -----------------------------------------------------

    def status(self) -> dict:
        return {
            "size": self.size,
            "num_healthy": self.num_healthy,
            "healthy_mask": self.healthy_mask(),
            "quarantines": self.num_quarantines,
            "restores": self.num_restores,
            "dispatches": list(self.num_dispatches),
            "inflight": list(self.num_inflight),
            "max_inflight": list(self.max_inflight),
            "devices": [str(d) for d in self.devices],
        }

    def counter_snapshot(self, prefix: str = "parallel.pool") -> dict:
        out = {
            f"{prefix}.size": float(self.size),
            f"{prefix}.healthy": float(self.num_healthy),
            f"{prefix}.quarantines": float(self.num_quarantines),
            f"{prefix}.restores": float(self.num_restores),
        }
        for i, n in enumerate(self.num_dispatches):
            out[f"{prefix}.dev{i}.dispatches"] = float(n)
            out[f"{prefix}.dev{i}.max_inflight"] = float(
                self.max_inflight[i]
            )
        return out


def sharded_spf_and_select(mesh: Mesh, max_degree: int):
    """Build the sharded flagship kernel: batch-sharded SPF + route
    selection over the mesh.  Shared topology/candidate inputs are
    replicated; per-snapshot inputs and all outputs are batch-sharded."""
    from openr_tpu.ops.route_select import spf_and_select

    b = batch_sharding(mesh)
    r = replicated(mesh)
    fn = functools.partial(spf_and_select, max_degree=max_degree)
    return jax.jit(
        fn,
        in_shardings=(
            r,  # src
            r,  # dst
            r,  # w
            r,  # edge_ok
            b,  # edge_enabled [B, E]
            b,  # overloaded [B, V]
            b,  # soft [B, V]
            b,  # roots [B]
            r,  # cand_node
            r,  # cand_ok
            r,  # drain_metric
            r,  # path_pref
            r,  # source_pref
            r,  # distance
            r,  # min_nexthop
        ),
        out_shardings=(b, b, b, b, b),
    )
