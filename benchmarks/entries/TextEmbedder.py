"""`TextEmbedder` over a registry text model in `embed` mode, with the
default `HashingTokenizer`:

    "entry": {"kind": "TextEmbedder", "model": <registry name>,
              "attention": <the kind the built program has to report>}
"""


def build(cell, weights_path, out_col):
    import jax.numpy as jnp

    from sparkdl_tpu.models import get_model
    from sparkdl_tpu.transformers.text import TextEmbedder

    entry = cell.config["entry"]
    mf = get_model(entry["model"]).model_function(
        mode="embed",
        dtype=jnp.dtype(cell.config["compute_dtype"]),
        weights_file=weights_path,
    )
    want = entry.get("attention")
    if want and not cell.rehearsal and mf.attention != want:
        raise RuntimeError(
            f"{entry['model']} was built with {mf.attention!r} attention; "
            f"the configuration states {want!r}"
        )
    return TextEmbedder(
        inputCol="in",
        outputCol=out_col,
        modelFunction=mf,
        maxLength=cell.config["max_length"],
        batchSize=cell.traffic["batch_rows"],
    )


def row_length(cell, transformer):
    """Stored row -> the tokens of its own that the program will find in
    it: the transformer's own tokenizer, cut at the configuration's
    `max_length`. Holds the tokenizer alone, so it outlives the program's
    release."""
    tokenize, cap = transformer._tokenizer(), cell.config["max_length"]
    return lambda text: min(len(tokenize(text)), cap)
