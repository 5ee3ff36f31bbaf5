"""Device time of the selective-scan kernel per thousand tokens it
scanned: the summed time of the kernel's events in the traced window over
the program's counter `ssm.scan_tokens` (dispatched rows x bucket edge x
state-space layers, pad rows and pad tokens included: what the kernel
ran over, not what was asked for)."""

from benchmarks.layer_metrics.selective_scan_roofline import kernel_seconds


def read(ctx):
    trace = ctx["trace"]
    tokens = ctx["counters"].get("ssm.scan_tokens", 0)
    if trace is None or tokens <= 0:
        return None
    seconds = kernel_seconds(trace)
    if seconds <= 0:
        return None
    return 1e3 * seconds * ctx["chips"] / (tokens / 1e3)
