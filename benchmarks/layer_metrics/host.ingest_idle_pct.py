"""Share of the traced window in which the device was idle while a
partition thread was in the program's `ingest` span: packing tokenized
rows into a batch and handing it to the feeder."""

from benchmarks import host_spans


def read(ctx):
    return host_spans.reading(ctx, "ingest")
