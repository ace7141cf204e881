"""The three windowed flash-attention kernels' share of their roofline
(``flash_win_fwd``, ``flash_win_bwd_dq``, ``flash_win_bwd_dkv``: calls with
a window that moves with the query), as ``flash_roofline`` takes the full
calls': the least time the chip could take for the calls seen in the trace
(the larger of the band's FLOPs over peak and the arrays' bytes over
bandwidth, from ``flops/<family>.window_kernel_call``) over the time they
took on the device.  A program without such kernels gives ``None``."""
from benchmark import common, trace_reduce

KERNELS = ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv")


def read(ctx):
    t = ctx["trace"]
    flops = ctx["flops_module"]
    if t is None or flops is None or \
            not hasattr(flops, "window_kernel_call"):
        return None
    peaks, rows = ctx["peaks"], ctx["cell"]["rows"]
    least = took = 0.0
    for kernel in KERNELS:
        events = trace_reduce.kernel_events(t["ops"], kernel)
        if not events:
            continue
        f, b = flops.window_kernel_call(ctx["cfg"], rows, kernel)
        by_flops = f / peaks["bf16_flops_per_s"]
        by_bytes = b / peaks["hbm_bytes_per_s"]
        floor = max(by_flops, by_bytes)
        seconds = sum(e.dur for e in events) / 1e9
        least += len(events) * floor
        took += seconds
        common.say(f"flash_window_roofline: {kernel} {len(events)} calls, "
                   f"{1e3 * seconds / len(events):.3f} ms each, floor "
                   f"{1e3 * floor:.3f} ms "
                   f"({'FLOPs' if by_flops >= by_bytes else 'bytes'} bound)")
    if took <= 0.0:
        return None
    return 100.0 * least / took
