"""Device time of latent attention's projections per thousand tokens
they ran over: the self seconds under the program's scopes `mla.q` (the
norm, `q_a`, its norm, `q_b`, the rotation), `mla.kv` (`kv_a`, its norm,
`kv_b`, the rotary key) and `mla.out` (`o` and the residual sum) in the
traced window over the program's counter `mla.attention_tokens`, the
divisor of `mla.attention_ms_per_ktoken`: the two add up to the
attention block. Says the three parts."""

from benchmarks import program_scopes

SCOPES = ("mla.q", "mla.kv", "mla.out")


def read(ctx):
    found = program_scopes.reading(ctx)
    if found is None:
        return None
    return program_scopes.per_thousand(
        ctx,
        found.seconds(*SCOPES),
        ctx["counters"].get("mla.attention_tokens", 0),
        mixed_seconds=found.mixed_seconds(*SCOPES),
        **{f"{n}_s": found.seconds(n) for n in SCOPES},
    )
