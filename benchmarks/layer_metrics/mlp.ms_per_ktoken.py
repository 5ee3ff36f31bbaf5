"""Device time of the dense feed-forward parts per thousand tokens they
ran over: the self seconds under the program's scope `mlp` in the traced
window (bert's feed-forward with the norm that closes it; Jamba's and
DeepSeek's SwiGLU with the norm that feeds it: the dense layer and the
shared experts, not the routed ones) over the tokens the programs were
dispatched, pad tokens too (counters `text.tokens` + `text.pad_tokens`)."""

from benchmarks import program_scopes

SCOPE = "mlp"


def read(ctx):
    found = program_scopes.reading(ctx)
    if found is None:
        return None
    c = ctx["counters"]
    return program_scopes.per_thousand(
        ctx,
        found.seconds(SCOPE),
        c.get("text.tokens", 0) + c.get("text.pad_tokens", 0),
        mixed_seconds=found.mixed_seconds(SCOPE),
    )
