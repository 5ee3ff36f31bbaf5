"""Device time of the full causal attention layers' kernel call per
thousand tokens it ran over: the seconds of every operation that carries
the program's scope `attn.full` at any level in the traced window (the
call of the blocked causal kernel, `flash_attention`) over the program's
counter `attn.full_tokens` (dispatched rows x bucket edge x full layers,
pad rows and pad tokens included). Beside `attn.sliding_ms_per_ktoken`."""

from benchmarks import program_scopes

SCOPE = "attn.full"


def read(ctx):
    found = program_scopes.reading(ctx)
    if found is None:
        return None
    return program_scopes.per_thousand(
        ctx,
        found.any_s.get(SCOPE, 0.0),
        ctx["counters"].get("attn.full_tokens", 0),
        mixed_seconds=found.mixed_seconds(SCOPE),
    )
