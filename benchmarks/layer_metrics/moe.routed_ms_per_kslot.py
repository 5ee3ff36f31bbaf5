"""Device time of the routed experts' path per thousand slots it
computed: the seconds of every operation that carries the program's
scope `moe.routed` at any level in the traced window (the sort into
slots, the conditional, and inside it the gather, the three grouped
products with `silu . up`, the combine; the router is `moe.route` and is
not in it) over the program's counter `moe.slots_held`, the divisor of
`moe.expert_ms_per_kslot`. Says the parts: `own_s` is what lies under
`moe.routed` alone, `worst_case_seconds` what ran in the worst-case arm
(every operation that carries `moe.worst_case`), and the window's
counters of the expert layers that took each arm."""

from benchmarks import program_scopes

SCOPE = "moe.routed"
PARTS = ("moe.gather", "moe.experts", "moe.combine")


def read(ctx):
    found = program_scopes.reading(ctx)
    if found is None:
        return None
    c = ctx["counters"]
    inside = (SCOPE, "moe.worst_case", *PARTS)
    return program_scopes.per_thousand(
        ctx,
        found.any_s.get(SCOPE, 0.0),
        c.get("moe.slots_held", 0),
        mixed_seconds=found.mixed_seconds(*inside),
        own_s=found.seconds(SCOPE),
        **{f"{n}_s": found.seconds(n) for n in PARTS},
        worst_case_seconds=found.any_s.get("moe.worst_case", 0.0),
        **{n: c.get(n, 0) for n in ("moe.buffer_sized", "moe.buffer_full")},
    )
