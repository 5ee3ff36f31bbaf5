"""Device time of the Mamba mixer around its scan kernel per thousand
tokens it ran over: the self seconds under every `mamba.*` scope of the
program but `mamba.scan` in the traced window (`mamba.in_proj` with the
norm that feeds it, `mamba.conv`, `mamba.ssm_inputs`, `mamba.out_proj`
with the residual sum) over the program's counter `ssm.scan_tokens`, the
divisor of `ssm.scan_ms_per_ktoken`: the two add up to the mixer. Says
the parts."""

from benchmarks import program_scopes

PREFIX = "mamba."
KERNEL = "mamba.scan"


def read(ctx):
    found = program_scopes.reading(ctx)
    if found is None:
        return None
    parts = [n for n in found.names(PREFIX) if n != KERNEL]
    return program_scopes.per_thousand(
        ctx,
        found.seconds(*parts),
        ctx["counters"].get("ssm.scan_tokens", 0),
        mixed_seconds=found.mixed_seconds(*parts),
        **{f"{n}_s": found.seconds(n) for n in parts},
    )
