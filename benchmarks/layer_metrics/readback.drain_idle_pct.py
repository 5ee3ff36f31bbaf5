"""Share of the traced window in which the device was idle while the
program waited on a result (`drain_wait`, or `device_wait` under the
synchronous arm): the host waits and the chip has nothing queued, so
what is left of the wait is the copy back to the host."""

from benchmarks import host_spans


def read(ctx):
    return host_spans.reading(ctx, "drain_wait", "device_wait")
