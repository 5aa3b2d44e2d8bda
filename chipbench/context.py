"""What a per-layer metric reader is handed: the traced window and the
cell's counts.  Each reader in ``metrics/`` is ``read(ctx) -> float or
None``; it returns None where the trace holds nothing for it to read."""
from __future__ import annotations

import dataclasses

from . import counts
from . import trace as tr

KERNELS = ("perturbed_matmul_pair", "mgd_update_window")


@dataclasses.dataclass
class Context:
    trace: tr.Trace
    t0: float               # traced window on the trace's clock
    t1: float
    steps: int              # MGD steps completed in the traced window
    chips: int
    config: dict
    traffic: dict
    peak_flops: float       # per chip
    peak_bw: float          # bytes/s per chip

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def busy_s(self) -> float:
        """Seconds some op ran, averaged over the cell's chips."""
        return sum(tr.busy_seconds(self.trace.devices.get(i, []), self.t0,
                                   self.t1) for i in range(self.chips)) / self.chips

    def op_seconds(self, names) -> tuple:
        """(seconds, calls) of the named ops, averaged over the chips."""
        tot, calls = 0.0, 0
        for i in range(self.chips):
            s, c = tr.op_seconds(self.trace.devices.get(i, []), self.t0,
                                 self.t1, names)
            tot, calls = tot + s, calls + c
        return tot / self.chips, calls / self.chips

    @property
    def tokens_per_step(self) -> int:
        return int(self.traffic["batch"]) * int(self.traffic["seq_len"])

    def least_seconds(self, calls: list) -> float:
        """Least time of ``calls`` at the chip's peaks, over the window."""
        per_step = sum(t for t, _ in counts.roofline_seconds(
            calls, self.peak_flops, self.peak_bw))
        return per_step * self.steps

    def roofline(self, kernel: str, calls: list):
        """Percent: least time of the kernel's ``calls`` over its device
        time; None where the trace holds no such kernel."""
        seconds, n = self.op_seconds((kernel,))
        if not n:
            return None
        return 100.0 * self.least_seconds(calls) / seconds

    def pair_roofline(self):
        """Percent: the probe-pair kernel's least time — for each call the
        larger of its FLOPs (2 probes, bf16 products) over the bf16 peak
        and its bytes (W read once, both activation streams read, both
        outputs written) over the HBM bandwidth — over its device time."""
        return self.roofline("perturbed_matmul_pair", counts.pair_calls(
            self.config, self.tokens_per_step))

    def update_roofline(self):
        """Percent: the update kernel's least time — each ndim ≥ 2 leaf
        read and written once in the weights' dtype over the HBM
        bandwidth (its multiply-adds are far under the compute bound) —
        over its device time."""
        return self.roofline("mgd_update_window",
                             counts.update_calls(self.config))

    def other_ms_per_step(self):
        """Busy milliseconds per step outside the two kernels: the
        embedding perturbation, attention, norms, head cross-entropy and
        the XLA update of 1-D leaves.  None where neither kernel is in the
        trace."""
        kernel_s, n = self.op_seconds(KERNELS)
        if not n:
            return None
        return 1e3 * (self.busy_s - kernel_s) / self.steps

    def mfu(self) -> float:
        """Percent: the two probe forwards' FLOPs (weight products and
        causal attention; MGD has no backward pass and the update is not
        counted) of the traced steps over the window's length times the
        chips' bf16 peak."""
        t = self.traffic
        flops = counts.model_flops(self.config, int(t["batch"]),
                                   int(t["seq_len"])) * self.steps
        return 100.0 * flops / (self.window_s * self.chips * self.peak_flops)
