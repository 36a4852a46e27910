"""Planner-vs-live memory reconciliation.

The planner (``repro.plan``) predicts peak bytes; nothing so far checked
the prediction against what is actually resident.  ``MemStat.sample``
sums ``jax.live_arrays()`` (host-visible handle bytes — the honest
"what is still alive" number on every backend, including the CPU CI
where ``device.memory_stats()`` is None), folds in the allocator's
``peak_bytes_in_use`` (read on every sample on a TPU, where a failed read
raises), and scores the result against the plan budget: ``mem_sample``
events carry ``frac_of_plan`` so a trace shows exactly when live bytes
cross the planned peak.
"""
from __future__ import annotations


class MemStat:
    def __init__(self, *, sink=None, registry=None, plan_bytes=None,
                 replica=None) -> None:
        self.sink = sink
        self.registry = registry
        self.plan_bytes = plan_bytes
        self.replica = replica
        self.peak_bytes = 0
        self.samples = 0

    def sample(self, step: int) -> dict:
        import jax

        arrays = jax.live_arrays()
        live, n = sum(a.nbytes for a in arrays), len(arrays)
        dev = jax.devices()[0]
        stats = dev.memory_stats()   # None on the CPU backend
        if dev.platform == "tpu" and "peak_bytes_in_use" not in (stats or {}):
            raise RuntimeError(f"memory_stats() of {dev} has no "
                               f"peak_bytes_in_use: {stats!r}")
        dev_peak = (int(stats["peak_bytes_in_use"])
                    if stats and "peak_bytes_in_use" in stats else None)
        rec = {"step": step, "live_bytes": live, "n_arrays": n}
        if dev_peak is not None:
            rec["device_peak_bytes"] = dev_peak
        if self.plan_bytes:
            rec["plan_bytes"] = int(self.plan_bytes)
            rec["frac_of_plan"] = round(live / self.plan_bytes, 4)
        if self.replica is not None:
            rec["replica"] = self.replica
        self.samples += 1
        if live > self.peak_bytes:
            self.peak_bytes = live
        if self.registry is not None:
            self.registry.set("mem.live_bytes", live)
            self.registry.observe("mem.live_mb", live / 2**20)
        if self.sink is not None:
            self.sink.emit("mem_sample", **rec)
        return rec

    def banner(self) -> str:
        """One line for the launch banner."""
        peak_mb = self.peak_bytes / 2**20
        if self.plan_bytes:
            return (f"mem: live peak {peak_mb:.1f} MB, plan "
                    f"{self.plan_bytes / 2**20:.1f} MB "
                    f"({self.peak_bytes / self.plan_bytes:.2f}x) "
                    f"over {self.samples} samples")
        return f"mem: live peak {peak_mb:.1f} MB over {self.samples} samples"
