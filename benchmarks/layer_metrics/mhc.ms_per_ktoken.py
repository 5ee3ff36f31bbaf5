"""Device time of the manifold-constrained hyper-connections per thousand
tokens they mixed: the seconds of every operation that carries the
program's scope `mhc.pre` (the pre-mix kernel's call: the stream's norm,
phi's product, the gates, the Sinkhorn steps, the pre-mix) or `mhc.post`
(the post-mix kernel's call, the residual sum's place) at any level in the
traced window, over the program's counter `mhc.tokens` (dispatched rows x
bucket edge x 2 x layers, pad rows and pad tokens included: what the
kernels ran over). Says the two parts."""

from benchmarks import program_scopes

SCOPES = ("mhc.pre", "mhc.post")


def read(ctx):
    found = program_scopes.reading(ctx)
    if found is None:
        return None
    return program_scopes.per_thousand(
        ctx,
        sum(found.any_s.get(s, 0.0) for s in SCOPES),
        ctx["counters"].get("mhc.tokens", 0),
        mixed_seconds=found.mixed_seconds(*SCOPES),
        **{f"{s}_s": found.any_s.get(s, 0.0) for s in SCOPES},
    )
