"""Share of the traced window in which the device was idle while a
partition thread was in the program's `tokenize` span (`text/bucketing.py`:
every row of the partition is tokenized before any batch can form)."""

from benchmarks import host_spans


def read(ctx):
    return host_spans.reading(ctx, "tokenize")
