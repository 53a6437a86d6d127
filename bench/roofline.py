"""A kernel's share of its roofline, from the trace and its byte count.

The least time of one call is the larger of its operations over the peak
rate and its bytes over the peak bandwidth; the share is the calls' least
time over the device time the trace gives their events, as a percentage.
"""
from __future__ import annotations

from bench.harness import kernel
from bench.peaks import peaks


def share(run, name: str):
    if run.trace is None or "frame_shape" not in run.counters:
        return None
    k = kernel(run.root, name)
    calls, seconds = run.trace.op_time(k.is_call)
    if calls == 0 or seconds <= 0:
        return None
    p = peaks(run.device_kind)
    shape, dtype = run.counters["frame_shape"], run.counters["frame_dtype"]
    least = max(k.bytes_moved(shape, dtype) / p["hbm_bytes_per_s"],
                k.flops(shape, dtype) / p["bf16_flops_per_s"])
    return 100.0 * calls * least / seconds
