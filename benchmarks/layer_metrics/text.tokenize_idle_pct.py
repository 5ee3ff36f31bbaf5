"""Share of the traced window in which the device was idle while a
partition thread was in the program's `tokenize` span
(`text/bucketing.py:_route_chunk`: one span bounds the tokenizing and
routing of one chunk of a partition's rows, and the chunk's rows go to
their buckets' feeders before the next chunk is tokenized)."""

from benchmarks import host_spans


def read(ctx):
    return host_spans.reading(ctx, "tokenize")
