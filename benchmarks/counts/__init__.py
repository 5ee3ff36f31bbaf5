"""Operations and bytes the algorithm needs, from shapes alone, one module
per model family. The benchmark's own: nothing here reads the program's
`utils/flops.py`. `tests/benchmarks/test_counts.py` holds each against
XLA's `cost_analysis()` of the plain reference.

A family module offers:

    forward_flops(config, work) -> FLOPs of the forward passes for `work`,
        the driver's account of what the window completed:
        {"rows": n} or {"rows_by_length": {padded length: rows}}
    kernel_work(config, kernel, work) -> (FLOPs, bytes) the named kernel's
        calls needed for `work`, or None where the family has no such kernel
"""
