"""Entry builders, one module per kind of entry into the program. A
configuration's `entry` block names its `kind`; the driver finds
`entries/<kind>.py`. A kind offers:

    build(cell, weights_path, out_col) -> the program's own transformer,
        reading column "in" and writing `out_col`, at the cell's
        configuration and the traffic's `batch_rows`, with the
        benchmark's weights loaded from `weights_path`
"""
