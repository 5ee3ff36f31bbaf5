"""Device time of the sliding-window attention layers' kernel call per
thousand tokens it ran over: the seconds of every operation that carries
the program's scope `attn.window` at any level in the traced window (the
call of the window kernel, `flash_attention_window`) over the program's
counter `attn.window_tokens` (dispatched rows x bucket edge x sliding
layers, pad rows and pad tokens included: what the kernel ran over).
Beside `attn.full_ms_per_ktoken`, the same for the full layers: the two
kinds of attention layer, a token each."""

from benchmarks import program_scopes

SCOPE = "attn.window"


def read(ctx):
    found = program_scopes.reading(ctx)
    if found is None:
        return None
    return program_scopes.per_thousand(
        ctx,
        found.any_s.get(SCOPE, 0.0),
        ctx["counters"].get("attn.window_tokens", 0),
        mixed_seconds=found.mixed_seconds(SCOPE),
    )
