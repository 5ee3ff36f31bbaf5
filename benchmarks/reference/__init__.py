"""Plain references, one module per model family, in straightforward
jax.numpy at float32 and `highest` matmul precision. They import nothing
of `sparkdl_tpu`; the weights they use are the benchmark's own, made here
from a seed, and are handed to the program as a weights file.

A family module offers:

    make_weights(config, seed) -> {flat/name: float32 ndarray}
        in the layout of the program's `.npz` weights file
    outputs(config, weights, inputs, precision, block_rows) -> [N, D] float32
        `inputs` are raw rows as the job holds them (uint8 HWC images as
        stored, or text strings); `precision` is "reference" (the
        reference at the precision the configuration states), "highest"
        (every product in float32; the same thing unless the configuration
        states otherwise), or the control's lower precision
    CONTROL_PRECISION: {configuration's stated precision: the control's}
"""
