"""Row generators, one module per kind of data. A traffic file's `data`
block names its `kind`; `traffic_gen.make_rows` finds `data/<kind>.py`.
A kind offers:

    rows(data, rng, nulls) -> iterator over the job's rows in order,
        `None` at the positions in `nulls`. The multiset of sizes may not
        depend on the seed behind `rng`: only their order and contents do
    stored(row) -> the row as the DataFrame holds it (optional; a kind
        without it stores rows as they are generated)
"""
