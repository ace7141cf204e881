"""The three flash-attention kernels' share of their roofline: the least
time the chip could take for the calls seen in the trace (the larger of
FLOPs over peak and bytes over bandwidth, from the call's shapes) over the
time they took on the device."""
from benchmark import common, trace_reduce

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(ctx):
    t = ctx["trace"]
    flops = ctx["flops_module"]
    if t is None or flops is None or not hasattr(flops, "kernel_call"):
        return None
    peaks, rows = ctx["peaks"], ctx["cell"]["rows"]
    least = took = 0.0
    for kernel in KERNELS:
        events = trace_reduce.kernel_events(t["ops"], kernel)
        if not events:
            continue
        f, b = flops.kernel_call(ctx["cfg"], rows, kernel)
        by_flops = f / peaks["bf16_flops_per_s"]
        by_bytes = b / peaks["hbm_bytes_per_s"]
        floor = max(by_flops, by_bytes)
        seconds = sum(e.dur for e in events) / 1e9
        least += len(events) * floor
        took += seconds
        common.say(f"flash_roofline: {kernel} {len(events)} calls, "
                   f"{1e3 * seconds / len(events):.3f} ms each, floor "
                   f"{1e3 * floor:.3f} ms "
                   f"({'FLOPs' if by_flops >= by_bytes else 'bytes'} bound)")
    if took <= 0.0:
        return None
    return 100.0 * least / took
