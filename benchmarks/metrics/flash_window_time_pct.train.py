"""Share of device 0's busy time spent in the window layers' flash attention
kernels: events of the operations line whose name starts with `flash_` and
contains `window` (`ops/flash.py` names a window's calls so; a global
layer's calls, and `flash_delta`, which both kinds share, are not in it).
A program whose kernels take no window has no such event: nothing read."""

from benchmarks.lib import flops_window

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def window_time_ns(trace, device: int = 0) -> int:
    return sum(
        ns for name, ns in trace.op_time_ns.get(device, {}).items()
        if flops_window.window_kernel_kind(name) is not None
    )


def read(trace, spans, cell):
    busy = trace.busy_ns.get(0, 0)
    took = window_time_ns(trace)
    if not busy or not took:
        return None
    return 100.0 * took / busy
