"""What a per-layer metric's reader is given: one run's observations.

A reader is ``read(ctx) -> float | None`` in a file named after its metric
under ``layer_metrics/``. It returns None where it finds nothing to read (no
device plane in the trace, no kernel of that kind in the job), and the
harness then leaves the metric out of the line.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Optional

from benchmarks import flops, trace_reduce


def window_drift(rates) -> Optional[float]:
    """How far a window's rate moved from its start to its end: the median
    of the last third of its segments over the median of the first third,
    less 1 (negative where the run slowed down). Stationary traffic reads 0
    to the segments' own noise; a window of fewer than three segments has
    no thirds and reads None."""
    third = len(rates) // 3
    if third < 1:
        return None
    return statistics.median(rates[-third:]) \
        / statistics.median(rates[:third]) - 1.0


@dataclasses.dataclass
class RunContext:
    job: Any                    # the job object (jobs/<job>.py::Job)
    chips: int
    peak: dict                  # this device kind's row of peaks.json
    throughput: float           # samples/s over all chips, untraced median
    spans: dict                 # the untraced window's: "place" | "dispatch"
                                # | "fence" -> [(start, end)], in ns
    first_step_s: float
    step_compiles: int
    memory_peak_bytes: int
    trace: Optional[trace_reduce.Trace] = None
    steps_traced: int = 0
    rates: tuple = ()           # the untraced window's segments, samples/s,
                                # in the order they ran

    def span_median_ms(self, name: str) -> Optional[float]:
        spans = self.spans.get(name)
        return 1e-6 * statistics.median(b - a for a, b in spans) \
            if spans else None

    def mfu_pct(self) -> float:
        """Model FLOP/s utilization: operations the forward and backward
        passes need (recomputation not counted) times the untraced
        throughput, over chips times peak."""
        return 100.0 * self.job.flops_per_sample * self.throughput \
            / (self.chips * self.peak["bf16_flops_per_s"])

    def has_device_trace(self) -> bool:
        return self.trace is not None and bool(self.trace.devices) \
            and self.steps_traced > 0

    def device_step_ms(self) -> Optional[float]:
        if not self.has_device_trace():
            return None
        busy, _ = trace_reduce.busy_seconds(self.trace)
        return 1e3 * busy / self.steps_traced

    def host_wait_ms(self) -> Optional[float]:
        """What a chip waits for the host in a step of the traced stretch
        (feed, dispatch, fence): the stretch's length less the time a chip
        was busy in it, both from the trace."""
        if not self.has_device_trace():
            return None
        busy, window = trace_reduce.busy_seconds(self.trace)
        return 1e3 * (window - busy) / self.steps_traced

    def op_ms_per_step(self, pattern: str, exposed: bool = False
                       ) -> Optional[float]:
        if not self.has_device_trace():
            return None
        fn = trace_reduce.exposed_seconds if exposed \
            else trace_reduce.op_seconds
        return 1e3 * fn(self.trace, pattern) / self.steps_traced

    def kernel_ms_per_step(self, kernel: str) -> Optional[float]:
        cost = self.job.kernel_costs.get(kernel)
        return self.op_ms_per_step(cost["match"]) if cost else None

    def kernel_roofline(self, kernel: str) -> Optional[tuple]:
        """(share of the roofline in %, which bound sets the roofline)."""
        ms = self.kernel_ms_per_step(kernel)
        if not ms:
            return None
        least, bound = flops.roofline_seconds(self.job.kernel_costs[kernel],
                                              self.peak)
        return 100.0 * least / (ms * 1e-3), bound
