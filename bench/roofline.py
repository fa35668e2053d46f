"""A kernel's share of its roofline: the least time the chip could take
for its calls, the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s, summed over the calls in the window, over the device time the
calls took."""
from __future__ import annotations

from typing import Optional, Sequence


def share(view, kernel: str, op_names: Sequence[str]) -> Optional[float]:
    cost = view.flops.kernels(view.sizes, view.traffic).get(kernel)
    calls = view.trace.op_events(*op_names)
    if cost is None or not calls:
        return None
    flops, nbytes = cost
    least = max(flops / view.peak["bf16_flops_per_s"],
                nbytes / view.peak["hbm_bytes_per_s"])
    seconds = sum(ev[2] for ev in calls) * 1e-9
    return 100.0 * len(calls) * least / seconds
