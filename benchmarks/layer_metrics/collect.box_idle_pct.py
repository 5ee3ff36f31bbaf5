"""Share of the traced window in which the device was idle while
`DataFrame.collect` built a `Row` for every row (`collect.box`)."""

from benchmarks import host_spans


def read(ctx):
    return host_spans.reading(ctx, "collect.box")
