"""The routed experts' grouped product's share of its roofline: the least
time the chip could take for the slots that fell on held experts in the
window (the program's device-measured counter `moe.slots_held`: 2 x 3 x
hidden x expert width operations a slot; the slots' rows in and out and
each held expert's three matrices once a dispatch and expert layer), the
larger of operations over the peak bf16 rate and bytes over the memory
bandwidth, over the summed device time of the kernel's events in the
trace. Says which of the two bounds. The counter counts the slots of the
rows dispatched in the window, the trace the calls that ran in it: the
two differ by the one batch in flight at either edge."""

from benchmarks.layer_metrics.mla_attention_roofline import roofline

EVENT_NAME_PARTS = ("%moe_grouped_matmul", 'custom_call_target="tpu_custom_call"')
KERNEL = "moe_grouped_matmul"


def measured_work(ctx):
    """The driver's `work` with what the program counted of the routed
    experts: the slots held, and the dispatches (every dispatched row,
    the rows that only fill a batch too, over the batch size). None
    where the program has no such counter."""
    counters = ctx["counters"]
    slots = counters.get("moe.slots_held", 0)
    if slots <= 0:
        return None
    rows = counters.get("feeder.rows", 0) + counters.get("feeder.pad_rows", 0)
    return dict(
        ctx["work"],
        slots_held=slots,
        dispatches=rows / ctx["cell"].traffic["batch_rows"],
    )


def read(ctx):
    work = measured_work(ctx)
    if work is None:
        return None
    return roofline(ctx, KERNEL, EVENT_NAME_PARTS, work)
