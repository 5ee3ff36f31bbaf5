"""Device time of the routed experts' grouped product per thousand slots
it computed: the summed time of the kernel's events in the traced window
(three calls an expert layer: gate, up, down) over the program's counter
`moe.slots_held`, the (token, expert) slots that fell on experts this
chip holds, counted on the device and read back with each row."""

from benchmarks.layer_metrics.mla_attention_roofline import kernel_seconds
from benchmarks.layer_metrics.moe_grouped_matmul_roofline import EVENT_NAME_PARTS


def read(ctx):
    trace = ctx["trace"]
    slots = ctx["counters"].get("moe.slots_held", 0)
    if trace is None or slots <= 0:
        return None
    seconds = kernel_seconds(trace, EVENT_NAME_PARTS)
    if seconds <= 0:
        return None
    return 1e3 * seconds * ctx["chips"] / (slots / 1e3)
