"""Text-embedding transformer (the non-image path, BASELINE config[3]:
"KerasTransformer BERT-base text-embedding UDF over text DataFrame").

A text column is tokenized host-side (any callable str -> list[int];
the offline-friendly HashingTokenizer is the default) and embedded by a
BERT-family ModelFunction on device — fixed (batch, seq_len) shapes so XLA
compiles one program. Pre-tokenized workloads can instead feed id arrays
through ModelTransformer directly.
"""

from __future__ import annotations

from typing import Callable, List, Optional

import numpy as np

from sparkdl_tpu.dataframe import DataFrame
from sparkdl_tpu.params import (
    HasBatchSize,
    HasInputCol,
    HasModelFunction,
    HasOutputCol,
    Param,
    TypeConverters,
    keyword_only,
)
from sparkdl_tpu.pipeline import Transformer
from sparkdl_tpu.transformers.execution import (
    dispatch_env_key,
    model_device_fn,
    run_batched_shared,
)
from sparkdl_tpu.utils.metrics import metrics


class HashingTokenizer:
    """Deterministic offline tokenizer: lowercased whitespace/punct split,
    stable FNV-1a hash into [n_reserved, vocab_size). Reserved ids:
    0=pad, 1=cls, 2=sep, 3=unk. Not a linguistic tokenizer — it exists so
    text pipelines run end-to-end with no downloaded vocab; swap in any
    callable (e.g. a transformers tokenizer) via the tokenizer param."""

    def __init__(self, vocab_size: int = 30522, add_special: bool = True):
        self.vocab_size = vocab_size
        self.add_special = add_special

    @staticmethod
    def _fnv1a(word: str) -> int:
        h = 0xCBF29CE484222325
        for b in word.encode("utf-8"):
            h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
        return h

    def __call__(self, text: str) -> List[int]:
        import re

        words = re.findall(r"[\w']+", text.lower())
        ids = [3 + 1 + self._fnv1a(w) % (self.vocab_size - 4) for w in words]
        if self.add_special:
            ids = [1] + ids + [2]
        return ids


def pad_or_truncate(ids: List[int], max_len: int) -> np.ndarray:
    if len(ids) > max_len:
        # Silent token loss is unobservable otherwise: rows past the
        # geometry lose their tail with no signal anywhere. Counted
        # here — the one choke point both text paths (bucketed and
        # pad-to-maxLength) shear rows through.
        metrics.inc("text.truncated_rows")
    arr = np.zeros((max_len,), np.int32)
    n = min(len(ids), max_len)
    arr[:n] = ids[:n]
    return arr


class TextEmbedder(
    Transformer, HasInputCol, HasOutputCol, HasBatchSize, HasModelFunction
):
    """text column -> tokenize -> model.embed -> embedding vector column.

    ``modelFunction`` must accept ``(ids, mask)`` int32 batches and return
    [B, D] embeddings (e.g. ModelIngest.from_flax(BertEncoder, ...,
    method='embed') or from_hf_flax(..., output='pooler_output')).
    """

    _persist_ignore = ("_jit_cache",)

    maxLength = Param(
        None, "maxLength", "token sequence length (pad/truncate)",
        TypeConverters.toInt,
    )
    tokenizer = Param(
        None, "tokenizer", "callable str -> list[int]",
        TypeConverters.identity,
    )

    @keyword_only
    def __init__(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        modelFunction=None,
        tokenizer: Optional[Callable] = None,
        maxLength: Optional[int] = None,
        batchSize: Optional[int] = None,
    ):
        super().__init__()
        self._setDefault(maxLength=128, batchSize=32)
        self._set(**self._input_kwargs)

    def _device_fn(self):
        mf = self.getModelFunction()
        if mf is None:
            raise ValueError("modelFunction param must be set")
        # Entries hold the ModelFunction itself so the id() key can never be
        # recycled by a GC'd-and-reallocated object. The (ids, attn) wrapper
        # is cached too: the shared device feeder keys streams by callable
        # identity, so a per-transform closure would defeat coalescing.
        key = (id(mf), dispatch_env_key())
        cache = self.__dict__.setdefault("_jit_cache", {})
        if key not in cache or cache[key][0] is not mf:
            fn = model_device_fn(mf)
            # a model with state-space layers says how many: every
            # dispatched token (pad rows and pad tokens too) is scanned
            # once by each
            scan_layers = getattr(mf, "scan_layers", 0)
            # and a model says what else its layers run over: counters
            # per dispatched token and per real (non-pad) token
            dispatched = dict(getattr(mf, "dispatched_token_counters", {}))
            real = dict(getattr(mf, "real_token_counters", {}))
            # and what neither is a multiple of (a count that depends on
            # the bucket, or on each row's length): {name: count} of a
            # batch, from its ids and which of them are real
            of_batch = getattr(mf, "batch_counters", None)
            if scan_layers:
                dispatched["ssm.scan_tokens"] = scan_layers

            def device_call(ids_batch, _fn=fn):
                attn = (ids_batch != 0).astype(np.int32)
                for name, each in dispatched.items():
                    metrics.inc(name, int(ids_batch.size) * each)
                if real:
                    real_tokens = int(attn.sum())
                    for name, each in real.items():
                        metrics.inc(name, real_tokens * each)
                if of_batch:
                    for name, count in of_batch(ids_batch, attn.astype(bool)).items():
                        metrics.inc(name, count)
                return _fn((ids_batch, attn))

            device_call.n_devices = getattr(fn, "n_devices", 1)
            device_call.single_stream = getattr(fn, "single_stream", False)
            cache[key] = (mf, device_call)
        return cache[key][1]

    def _strip_row_counters(self, rows):
        """Rows as the program returned them -> rows of embeddings. A model
        whose result carries counts made on the device names them in
        ``row_counters``: that many trailing columns go to their counters."""
        names = getattr(self.getModelFunction(), "row_counters", ())
        if not names:
            return rows
        width = len(names)
        live = [r for r in rows if r is not None]
        if live:
            totals = np.sum([r[-width:] for r in live], 0)
            for name, total in zip(names, totals):
                metrics.inc(name, int(round(float(total))))
        return [None if r is None else r[:-width] for r in rows]

    def _tokenizer(self):
        if self.isDefined("tokenizer"):
            return self.getOrDefault("tokenizer")
        # Bound the hash space by the model's vocab when it advertises one —
        # out-of-vocab ids would be out-of-bounds embedding gathers.
        vocab = getattr(self.getModelFunction(), "vocab_size", None) or 30522
        return HashingTokenizer(vocab_size=vocab)

    def _transform(self, dataset: DataFrame) -> DataFrame:
        in_col, out_col = self.getInputCol(), self.getOutputCol()
        max_len = self.getOrDefault("maxLength")
        tok = self._tokenizer()
        batch_size = self.getBatchSize()
        device_fn = self._device_fn()

        from sparkdl_tpu.text.bucketing import bucketing_enabled, run_bucketed

        if bucketing_enabled() and not getattr(
            device_fn, "single_stream", False
        ):
            # Length-aware path (default): rows pad only to their
            # bucket's edge and route to sibling feeder geometries of
            # THIS device fn — one compiled program per bucket seen,
            # instead of every row paying maxLength. Whole-mesh
            # single_stream fns keep the fixed geometry: their sequence
            # sharding was built for exactly max_len.
            def run_partition_bucketed(part):
                return {
                    out_col: self._strip_row_counters(
                        run_bucketed(
                            part[in_col],
                            tok,
                            device_fn,
                            batch_size,
                            max_len,
                        )
                    )
                }

            return dataset.withColumnPartition(
                out_col, run_partition_bucketed
            )

        def to_batch(chunk):
            n = len(chunk)
            ids = np.zeros((n, max_len), np.int32)
            mask = np.zeros((n,), bool)
            for i, text in enumerate(chunk):
                if text is None:
                    continue
                try:
                    ids[i] = pad_or_truncate(tok(text), max_len)
                    mask[i] = True
                except Exception:
                    continue
            return ids, mask

        def run_partition(part):
            outputs = run_batched_shared(
                part[in_col],
                to_batch=to_batch,
                device_fn=device_fn,
                batch_size=batch_size,
            )
            return {out_col: self._strip_row_counters(outputs)}

        return dataset.withColumnPartition(out_col, run_partition)
