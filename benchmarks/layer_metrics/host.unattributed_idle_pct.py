"""Share of the traced window in which the device was idle and no
program span was open on any host thread (the envelopes
`executor.map_partitions` and `executor.partition` aside): what the
spans do not cover yet. Beside it, the idle seconds under every span
name, and `idle_seconds` = `seconds` + `attributed_seconds`."""

from benchmarks import host_spans


def read(ctx):
    return host_spans.unattributed(ctx)
