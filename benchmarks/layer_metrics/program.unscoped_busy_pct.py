"""Share of the first device's busy time in the traced window that ran
under no scope of the program (`benchmarks/program_scopes.py`): what the
scopes do not name yet. Beside it the whole table: `scope.<name>`, the
self seconds under every scope (with `seconds`, the unscoped ones, they
are `busy_seconds`); `under.<name>`, the seconds of the operations that
carry an outer scope at any level, where that is more than its self
time; `mixed_seconds`, the part of the busy time in fusions that hold
more than one scope; and `unscoped.<kind>`, the unscoped seconds by the
kind of instruction (`copy-done`, `fusion`: the name less the compiler's
number), the five largest."""

from benchmarks import program_scopes

TOP = 5


def read(ctx):
    found = program_scopes.reading(ctx)
    if found is None or found.busy_s <= 0:
        return None
    seconds = found.seconds(program_scopes.UNSCOPED)
    largest = sorted(found.unscoped_ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "value": 100.0 * seconds / found.busy_s,
        "seconds": seconds,
        "busy_seconds": found.busy_s,
        "mixed_seconds": sum(found.mixed_s.values()),
        **{f"scope.{n}": found.self_s[n] for n in found.names()},
        **{
            f"under.{n}": s
            for n, s in sorted(found.any_s.items())
            if s > found.self_s.get(n, 0.0)
        },
        **{f"unscoped.{n}": s for n, s in largest},
    }
