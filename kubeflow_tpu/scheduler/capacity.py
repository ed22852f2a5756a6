"""Cluster capacity model: heterogeneous TPU slice pools built from Nodes.

The model the Gavel-style policy loop places against
(:mod:`kubeflow_tpu.scheduler.controller`): nodes labeled with a GKE TPU
accelerator type form *pools*; within a pool, nodes sharing a slice label
form one contiguous *slice* (the unit a gang must land wholly inside —
the ICI domain). Hosts are the placement grain: one gang pod occupies one
host, matching the one-pod-per-TPU-VM-host layout the job controller
renders.

Occupancy is derived, never stored: a host is busy iff a live placement
annotation (or a still-running pod of a revoked placement) claims it, so
the model is rebuilt from the apiserver every round and survives scheduler
restarts with zero recovery code — the same level-triggered contract as
the controllers.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterable, Mapping

from kubeflow_tpu.apis import scheduling as api


@dataclass
class Slice:
    """One contiguous slice: an ordered set of schedulable hosts."""

    pool: str            # accelerator type, e.g. "v5e"
    slice_id: str
    topology: str = ""
    nodes: list[str] = field(default_factory=list)
    chips_per_host: int = 0

    @property
    def size(self) -> int:
        return len(self.nodes)


class ClusterCapacity:
    """Pools/slices from Node objects + a within-round reservation view.

    ``reserve``/``occupy`` mutate only this in-memory view: one scheduling
    round works against one consistent snapshot, so two gangs admitted in
    the same round can never be handed overlapping hosts — the other half
    of the all-or-nothing guarantee (the first half being the single
    placement annotation per gang).
    """

    def __init__(self, slices: Iterable[Slice]):
        self.slices: list[Slice] = list(slices)
        self._busy: dict[str, str] = {}  # node name -> holder key

    # -- construction --------------------------------------------------

    @classmethod
    def from_nodes(cls, nodes: Iterable[Mapping]) -> "ClusterCapacity":
        by_slice: dict[tuple[str, str], Slice] = {}
        for node in nodes:
            meta = node.get("metadata", {})
            labels = meta.get("labels", {}) or {}
            accel = labels.get(api.NODE_ACCEL_LABEL)
            if not accel:
                continue  # not a TPU host
            if node.get("spec", {}).get("unschedulable"):
                continue  # cordoned / draining
            if _not_ready(node):
                continue  # node-kill churn: a dead kubelet is not capacity
            slice_id = labels.get(api.NODE_SLICE_LABEL,
                                  f"{accel}-{meta.get('name', '')}")
            key = (accel, slice_id)
            sl = by_slice.get(key)
            if sl is None:
                sl = by_slice[key] = Slice(
                    pool=accel, slice_id=slice_id,
                    topology=labels.get(api.NODE_TOPO_LABEL, ""),
                )
            sl.nodes.append(meta.get("name", ""))
            chips = (node.get("status", {}).get("capacity", {})
                     or {}).get("google.com/tpu", 0)
            try:
                sl.chips_per_host = max(sl.chips_per_host, int(chips))
            except (TypeError, ValueError):
                pass
        for sl in by_slice.values():
            sl.nodes.sort()  # deterministic host order
        return cls(sorted(by_slice.values(),
                          key=lambda s: (s.pool, s.slice_id)))

    # -- inspection ----------------------------------------------------

    @property
    def node_names(self) -> set[str]:
        return {n for sl in self.slices for n in sl.nodes}

    def pools(self) -> dict[str, list[Slice]]:
        out: dict[str, list[Slice]] = {}
        for sl in self.slices:
            out.setdefault(sl.pool, []).append(sl)
        return out

    def largest_slice(self, accelerator: str | None = None) -> int:
        sizes = [sl.size for sl in self.slices
                 if accelerator in (None, sl.pool)]
        return max(sizes, default=0)

    def free_hosts(self, sl: Slice) -> list[str]:
        return [n for n in sl.nodes if n not in self._busy]

    def holder(self, node: str) -> str | None:
        return self._busy.get(node)

    # -- reservation view ----------------------------------------------

    def occupy(self, nodes: Iterable[str], holder: str) -> None:
        """Mark hosts busy (existing placements / still-running pods).
        First holder wins: a stale pod of a revoked placement keeps the
        host busy until it actually exits."""
        for node in nodes:
            self._busy.setdefault(node, holder)

    def release(self, holder: str) -> None:
        self._busy = {n: h for n, h in self._busy.items() if h != holder}

    def vacate(self, nodes: Iterable[str]) -> None:
        """Free specific hosts (an elastic shrink returns the tail of a
        grant while the holder keeps the rest)."""
        for node in nodes:
            self._busy.pop(node, None)

    def feasible(self, n_hosts: int,
                 accelerator: str | None = None) -> list[Slice]:
        """Slices with >= n_hosts free right now (accelerator-filtered)."""
        return [sl for sl in self.slices
                if accelerator in (None, sl.pool)
                and len(self.free_hosts(sl)) >= n_hosts]

    def ever_fits(self, n_hosts: int,
                  accelerator: str | None = None) -> bool:
        """Could the request fit an EMPTY cluster? False means the job is
        structurally unschedulable (requests > largest matching slice),
        not merely waiting for capacity."""
        return n_hosts <= self.largest_slice(accelerator)

    def reserve(self, sl: Slice, n_hosts: int, holder: str) -> list[str]:
        """Atomically claim n_hosts on one slice — all or nothing."""
        free = self.free_hosts(sl)
        if len(free) < n_hosts:
            raise ValueError(
                f"slice {sl.slice_id}: {len(free)} free < {n_hosts}")
        nodes = free[:n_hosts]
        for node in nodes:
            self._busy[node] = holder
        return nodes


def _not_ready(node: Mapping) -> bool:
    for cond in node.get("status", {}).get("conditions", []) or []:
        if cond.get("type") == "Ready":
            return cond.get("status") != "True"
    return False  # no conditions reported — assume schedulable (fake nodes)


# ---------------------------------------------------------------------------
# Throughput profiles (the heterogeneity signal)
# ---------------------------------------------------------------------------

# Default normalized throughput book: the pools' documented bf16 peak
# ratio (v5e 197 TFLOP/s, v5p 459 TFLOP/s per chip), which is all a job
# without a measured profile can be ranked by. Measured profiles come
# from a SchedulingPolicy's spec.profiles or from bench output
# (:meth:`ThroughputBook.from_bench_files`); none ships in the code.
DEFAULT_PROFILES: dict[str, dict[str, float]] = {
    "default": {"v5e": 1.0, "v5p": 2.3},
}


class ThroughputBook:
    """(profile, accelerator) -> measured throughput. Scores placements
    Gavel-style: normalized throughput, so a job runs where it is
    *measured* fastest rather than wherever arrived first."""

    def __init__(self, profiles: Mapping[str, Mapping[str, float]]
                 | None = None):
        merged: dict[str, dict[str, float]] = {
            k: dict(v) for k, v in DEFAULT_PROFILES.items()}
        for name, table in (profiles or {}).items():
            if isinstance(table, Mapping):
                merged.setdefault(name, {}).update(
                    {a: float(t) for a, t in table.items()})
        self._profiles = merged

    @classmethod
    def from_bench_files(cls, files: Mapping[str, str],
                         extra: Mapping[str, Mapping[str, float]]
                         | None = None) -> "ThroughputBook":
        """Build profiles from files in the shape a trial prints (one JSON
        line, bare or under a ``"parsed"`` key):
        ``files`` maps accelerator type -> path measured on it. Each file
        contributes its config's leading token (e.g. ``flagship-1b``) as
        the profile name with ``tokens_per_sec_per_chip`` as the
        throughput (plus the deep-model twin when present)."""
        import json as _json

        profiles: dict[str, dict[str, float]] = {}
        for accel, path in files.items():
            try:
                with open(path) as f:
                    data = _json.load(f)
            except (OSError, ValueError):
                continue  # a missing/garbled bench file is not capacity
            rec = data.get("parsed", data)
            if not isinstance(rec, Mapping):
                continue
            for cfg_key, tps_key in (
                    ("config", "tokens_per_sec_per_chip"),
                    ("deep_config", "deep_tokens_per_sec_per_chip")):
                cfg, tps = rec.get(cfg_key), rec.get(tps_key)
                if not cfg or not isinstance(tps, (int, float)):
                    continue
                profile = str(cfg).split()[0]
                profiles.setdefault(profile, {})[accel] = float(tps)
        for name, table in (extra or {}).items():
            profiles.setdefault(name, {}).update(table)
        return cls(profiles)

    def throughput(self, profile: str | None, accelerator: str) -> float:
        table = self._profiles.get(profile or "default") \
            or self._profiles["default"]
        if accelerator in table:
            return float(table[accelerator])
        # Unknown accelerator: neutral 1.0 so it is placeable, not favored.
        return 1.0

    def score(self, profile: str | None, accelerator: str) -> float:
        """Normalized throughput in (0, 1]: 1.0 on the job's best pool."""
        table = self._profiles.get(profile or "default") \
            or self._profiles["default"]
        best = max(table.values(), default=1.0)
        return self.throughput(profile, accelerator) / max(best, 1e-9)
