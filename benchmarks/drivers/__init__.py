"""Drivers: one module per way of driving the program. A traffic file
names its driver. A driver offers:

    setup(cell) -> state      everything up to the first timed job
    window(state, seconds) -> Window   the measured window itself
    counters(state) -> {name: number}  the program's own counts, now
    work(state, counter_delta, window) -> what the window completed,
        as the counts modules take it (`counts/__init__.py`); called
        after `release`. A `lengths_check` in it goes into the result
        line as `work_check`
    release(state)            frees what the program holds on the device
    check(cell, state, window) -> {name: number}   against the reference
"""
