"""Sequence-bucketed text engine: ladder election, routing, scatter
parity, truncation observability, registry text models, and the
router's seq-bucket grouping."""

import numpy as np
import pytest

import jax.numpy as jnp

from sparkdl_tpu.dataframe import DataFrame
from sparkdl_tpu.models import NamedTextModel, get_model, supported_models
from sparkdl_tpu.models.bert import bert_model_function
from sparkdl_tpu.text.bucketing import (
    bucket_for,
    bucket_ladder,
    next_bucket,
    run_bucketed,
)
from sparkdl_tpu.transformers.text import (
    HashingTokenizer,
    TextEmbedder,
    pad_or_truncate,
)
from sparkdl_tpu.utils.metrics import metrics


@pytest.fixture
def tiny_mf():
    return bert_model_function(size="tiny", max_length=64)


def _texts(lengths):
    """Token length == words + 2 under the HashingTokenizer."""
    return [
        None
        if l is None
        else " ".join(f"w{i}x{j}" for j in range(max(1, l - 2)))
        for i, l in enumerate(lengths)
    ]


def _embed(mf, texts, bucketing, max_len=64, batch=4, parts=2):
    import os

    os.environ["SPARKDL_TEXT_BUCKETING"] = "1" if bucketing else "0"
    try:
        emb = TextEmbedder(
            inputCol="t", outputCol="e", modelFunction=mf,
            maxLength=max_len, batchSize=batch,
        )
        df = DataFrame.fromColumns({"t": texts}, numPartitions=parts)
        return [r.e for r in emb.transform(df).collect()]
    finally:
        os.environ.pop("SPARKDL_TEXT_BUCKETING", None)


# -- ladder election ---------------------------------------------------------


def test_ladder_half_default():
    assert bucket_ladder(512) == (
        16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512,
    )


def test_ladder_pow2_and_custom():
    assert bucket_ladder(512, "pow2") == (16, 32, 64, 128, 256, 512)
    # custom edges below min_bucket / above max drop; top edge is
    # always exactly max_length
    assert bucket_ladder(100, "8,32,48,600") == (16, 32, 48, 100)


def test_ladder_non_pow2_max_and_tiny_max():
    assert bucket_ladder(300, "pow2")[-1] == 300
    assert bucket_ladder(8) == (8,)  # max under min_bucket collapses


def test_ladder_rejects_garbage():
    with pytest.raises(ValueError, match="SPARKDL_TEXT_BUCKETS"):
        bucket_ladder(128, "32,forty八")
    with pytest.raises(ValueError, match="max_length"):
        bucket_ladder(0)


def test_bucket_for_and_next_bucket():
    lad = bucket_ladder(512)
    assert bucket_for(1, lad) == 16
    assert bucket_for(16, lad) == 16
    assert bucket_for(17, lad) == 24
    assert bucket_for(97, lad) == 128
    assert bucket_for(10_000, lad) == 512  # top edge: truncation case
    # the serving grid is UNCAPPED
    assert next_bucket(17) == 24
    assert next_bucket(1400) == 1536
    assert next_bucket(1800) == 2048
    assert next_bucket(2048) == 2048


# -- run_bucketed edge cases -------------------------------------------------


def test_empty_partition(tiny_mf):
    from sparkdl_tpu.transformers.text import HashingTokenizer

    out = run_bucketed(
        [], HashingTokenizer(1000), lambda b: b, 4, 64
    )
    assert out == []


def test_all_rows_one_length(tiny_mf):
    metrics.reset()
    texts = _texts([30] * 10)
    out = _embed(tiny_mf, texts, bucketing=True)
    assert all(e is not None and e.shape == (128,) for e in out)
    counters = metrics.snapshot()["counters"]
    routed = {
        k: v for k, v in counters.items()
        if k.startswith("text.bucket_rows.")
    }
    assert routed == {"text.bucket_rows.32": 10.0}


def test_row_longer_than_largest_bucket_truncates(tiny_mf):
    """A row past the top edge truncates to it — and embeds exactly
    like the unbucketed path, which truncates to the same maxLength."""
    metrics.reset()
    texts = _texts([100, 20])  # 100 > maxLength 64
    b = _embed(tiny_mf, texts, bucketing=True)
    assert metrics.counter("text.truncated_rows") >= 1
    u = _embed(tiny_mf, texts, bucketing=False)
    for x, y in zip(b, u):
        np.testing.assert_allclose(x, y, rtol=2e-5, atol=2e-5)


def test_cross_bucket_ordering_parity(tiny_mf):
    """Mixed lengths spread across several buckets: results must land
    at their ORIGINAL row positions, identical to the unbucketed path,
    nulls riding through."""
    rng = np.random.default_rng(0)
    lengths = [int(x) for x in rng.integers(3, 64, size=30)]
    lengths[4] = None
    lengths[17] = None
    texts = _texts(lengths)
    b = _embed(tiny_mf, texts, bucketing=True, parts=3)
    u = _embed(tiny_mf, texts, bucketing=False, parts=3)
    assert b[4] is None and b[17] is None
    for i, (x, y) in enumerate(zip(b, u)):
        if y is None:
            assert x is None
        else:
            np.testing.assert_allclose(
                x, y, rtol=2e-5, atol=2e-5, err_msg=f"row {i}"
            )


def test_pad_ratio_accounting(tiny_mf):
    metrics.reset()
    _embed(tiny_mf, _texts([17] * 8), bucketing=True)
    counters = metrics.snapshot()["counters"]
    # 17-token rows in the 24 bucket: 7 pad tokens each
    assert counters["text.tokens"] == 8 * 17
    assert counters["text.pad_tokens"] == 8 * 7


# -- tokenizer pad/truncate boundary ----------------------------------------


def test_pad_or_truncate_boundary_counter():
    metrics.reset()
    exact = pad_or_truncate(list(range(1, 9)), 8)
    assert exact.tolist() == list(range(1, 9))
    assert metrics.counter("text.truncated_rows") == 0  # exact fit
    over = pad_or_truncate(list(range(1, 10)), 8)
    assert over.tolist() == list(range(1, 9))  # tail sheared
    assert metrics.counter("text.truncated_rows") == 1
    short = pad_or_truncate([5], 4)
    assert short.tolist() == [5, 0, 0, 0]
    assert metrics.counter("text.truncated_rows") == 1


def test_hashing_tokenizer_length_contract():
    tok = HashingTokenizer(vocab_size=500)
    assert len(tok("one two three")) == 5  # words + CLS/SEP


# -- registry text models ----------------------------------------------------


def test_text_registry_entries():
    names = supported_models()
    for name in ("bert-base", "bert-tiny", "bert-long-2048"):
        assert name in names
        spec = get_model(name)
        assert isinstance(spec, NamedTextModel)
        est = spec.param_bytes_estimate()
        assert est and est > 0
        assert spec.flops_per_item(128) > 0
    rows = {
        r["name"]: r for r in supported_models(with_memory=True)
    }
    assert rows["bert-long-2048"]["kind"] == "text"
    assert rows["bert-long-2048"]["max_length"] == 2048
    assert rows["ResNet50"]["kind"] == "image"


def test_text_model_mask_derivation_matches_tuple_call():
    """The registry fn must embed a zero-padded bare-ids batch exactly
    like the explicit (ids, mask) call — the invariant both the bucket
    edges and the router's seq padding rely on."""
    spec = get_model("bert-tiny")
    mf = spec.model_function(mode="embed")
    rng = np.random.default_rng(1)
    ids = np.zeros((2, 32), np.int32)
    ids[0, :20] = rng.integers(4, 1000, 20)
    ids[1, :32] = rng.integers(4, 1000, 32)
    bare = np.asarray(mf.fn(mf.params, jnp.asarray(ids)))
    masked = np.asarray(
        mf.fn(mf.params, (jnp.asarray(ids), jnp.asarray(ids != 0)))
    )
    np.testing.assert_allclose(bare, masked, rtol=1e-6, atol=1e-6)
    # and padding the seq axis must not move the embedding
    wide = np.zeros((2, 48), np.int32)
    wide[:, :32] = ids
    padded = np.asarray(mf.fn(mf.params, jnp.asarray(wide)))
    np.testing.assert_allclose(bare, padded, rtol=1e-4, atol=1e-4)


def test_text_model_mode_validation():
    spec = get_model("bert-tiny")
    with pytest.raises(ValueError, match="mode"):
        spec.model_function(mode="probabilities")


def test_text_model_refuses_overwide_geometry():
    """The offline registry fn must refuse sequences past the position
    table at trace time (shapes are static) — never let JAX clamp the
    gather into a silently wrong embedding."""
    mf = get_model("bert-tiny").model_function(mode="embed")
    with pytest.raises(ValueError, match="position table"):
        mf.fn(mf.params, jnp.ones((1, 256), jnp.int32))


def test_image_surfaces_reject_text_models_cleanly():
    """Image-only APIs list only image specs and fail a text name with
    a pointer to the right surface, not a downstream AttributeError."""
    from sparkdl_tpu.models.registry import get_image_model
    from sparkdl_tpu.transformers import DeepImageFeaturizer

    names = DeepImageFeaturizer.supportedModels()
    assert "ResNet50" in names and "bert-tiny" not in names
    with pytest.raises(ValueError, match="text model"):
        get_image_model("bert-tiny")
    with pytest.raises(ValueError, match="text model"):
        DeepImageFeaturizer(
            inputCol="image", outputCol="f", modelName="bert-tiny"
        )._inner()


def test_image_spec_flops_wired():
    from sparkdl_tpu.utils.flops import model_flops_per_image

    spec = get_model("ResNet50")
    assert spec.flops_per_item() == model_flops_per_image("ResNet50")


# -- serving: seq buckets in the grouping key --------------------------------


def test_router_seq_buckets_token_payloads():
    from sparkdl_tpu.serving import Router, ServingClient, choose_seq_bucket
    from sparkdl_tpu.serving.router import _bucket_token_payload

    assert choose_seq_bucket(30) == 32
    # int64 JSON ids normalize to int32 and pad to the bucket edge
    p, tokens, pad = _bucket_token_payload(
        "bert-tiny", np.ones((2, 30), np.int64)
    )
    assert p.dtype == np.int32 and p.shape == (2, 32)
    assert (p[:, 30:] == 0).all()
    assert tokens == 60 and pad == 4
    # integral float payloads against a REGISTRY text model coerce to
    # int32 and bucket (the omitted-"dtype" HTTP case); float payloads
    # for non-registry models pass through untouched (see
    # test_float_token_payload_coerced_not_bypassed)
    f = np.ones((2, 30), np.float32)
    coerced, _, _ = _bucket_token_payload("bert-tiny", f)
    assert coerced.dtype == np.int32 and coerced.shape == (2, 32)
    # registry spec's position table is the ceiling: over-long rejects
    # (JAX would clamp the position gather and answer silently wrong),
    # and the bucket edge caps at max_length even under a coarse grid
    with pytest.raises(ValueError, match="position table"):
        _bucket_token_payload("bert-tiny", np.ones((1, 200), np.int64))
    capped, _, _ = _bucket_token_payload(
        "bert-tiny", np.ones((1, 100), np.int64)
    )
    assert capped.shape == (1, 128)
    # custom-loader models (no registry spec) bucket uncapped
    wide, _, _ = _bucket_token_payload(
        "my-custom-model", np.ones((1, 200), np.int64)
    )
    assert wide.shape == (1, 256)

    metrics.reset()
    router = Router(max_batch=8)
    client = ServingClient(router)
    try:
        rng = np.random.default_rng(0)
        outs = []
        for length in (20, 24):  # both bucket to 24: ONE stream
            ids = rng.integers(4, 1000, (1, length)).astype(np.int64)
            outs.append(
                client.predict("bert-tiny", ids, mode="embed", timeout=300)
            )
        assert all(o.shape == (1, 128) for o in outs)
        assert metrics.counter("text.pad_tokens") == 4  # 20 -> 24
    finally:
        router.close()


def test_features_alias_still_buckets_and_guards():
    """Registry text models accept mode='features' as an alias of
    'embed' — the seq bucketing AND the position-table guard must
    engage under the alias too, or the default client mode bypasses
    both (silently clamped position gathers)."""
    from sparkdl_tpu.serving import Router, ServingClient

    metrics.reset()
    router = Router(max_batch=8)
    client = ServingClient(router)
    try:
        rng = np.random.default_rng(2)
        ids = rng.integers(4, 1000, (1, 20)).astype(np.int64)
        out = client.predict("bert-tiny", ids, timeout=300)  # mode default
        assert out.shape == (1, 128)
        assert metrics.counter("text.pad_tokens") == 4  # 20 -> 24
        with pytest.raises(ValueError, match="position table"):
            client.predict(
                "bert-tiny", np.ones((1, 200), np.int64), timeout=60
            )
    finally:
        router.close()


def test_float_token_payload_coerced_not_bypassed():
    """HTTP bodies default to float32 when "dtype" is omitted — the
    guard and the bucketing must still engage for registry text models:
    integral floats coerce to int32, real-valued payloads reject."""
    from sparkdl_tpu.serving.router import _bucket_token_payload

    p, tokens, pad = _bucket_token_payload(
        "bert-tiny", np.ones((1, 20), np.float32) * 7
    )
    assert p.dtype == np.int32 and p.shape == (1, 24)
    assert tokens == 20 and pad == 4
    with pytest.raises(ValueError, match="position table"):
        _bucket_token_payload("bert-tiny", np.ones((1, 200), np.float32))
    with pytest.raises(ValueError, match="integer token ids"):
        _bucket_token_payload("bert-tiny", np.full((1, 20), 1.5))
    # custom-loader float payloads (image features) stay untouched
    f = np.ones((2, 30), np.float32)
    out, _, _ = _bucket_token_payload("my-custom-model", f)
    assert out is f


def test_client_prepadded_rows_count_real_tokens_only():
    """text.tokens uses the masking invariant (ids != 0), not payload
    width: a client that pre-pads its rows must not deflate pad_ratio
    relative to the offline accounting."""
    from sparkdl_tpu.serving.router import _bucket_token_payload

    pre = np.zeros((1, 24), np.int64)
    pre[0, :20] = 7
    p, tokens, pad = _bucket_token_payload("bert-tiny", pre)
    assert p.shape == (1, 24)  # already on the grid edge
    assert tokens == 20 and pad == 4


def test_rejected_submit_counts_no_tokens():
    """Token accounting records only ADMITTED work: a rejected submit
    (or a client retrying one) must not inflate text.tokens."""
    from sparkdl_tpu.serving import AdmissionRejected, Router, ServingClient

    metrics.reset()
    router = Router(max_batch=8)
    router.queue._cap_rows = 1
    client = ServingClient(router)
    try:
        with pytest.raises(AdmissionRejected):
            client.submit(
                "bert-tiny", np.ones((4, 30), np.int64), mode="embed"
            )
        assert metrics.counter("text.tokens") == 0
        assert metrics.counter("text.pad_tokens") == 0
    finally:
        router.close()


def test_single_stream_model_keeps_fixed_geometry():
    """Whole-mesh sequence-parallel fns must NOT bucket: their sharding
    was built for exactly max_length (execution honors single_stream,
    and the TextEmbedder bucketing gate must too)."""
    from sparkdl_tpu.models.bert import (
        bert_model_function_sequence_parallel,
    )
    from sparkdl_tpu.parallel import make_mesh

    dense = bert_model_function(size="tiny", max_length=32)
    mf_sp = bert_model_function_sequence_parallel(
        size="tiny", mesh=make_mesh({"sp": 8}), max_length=32,
        params=dense.params,
    )
    texts = _texts([10, 25, None, 31])
    sp = _embed(mf_sp, texts, bucketing=True, max_len=32, batch=2)
    d = _embed(dense, texts, bucketing=True, max_len=32, batch=2)
    assert sp[2] is None and d[2] is None
    for a, b in zip(d, sp):
        if a is not None:
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


# -- a partition streams into the feeder chunk by chunk -----------------------

_LADDER = (8, 16, 32)


def _cell(length, salt):
    """A cell the stub tokenizer turns into `length` ids that no other
    row of the job shares: a misrouted answer cannot pass for its own."""
    return f"{length}:{salt}"


def _stub_tokenize(text):
    if text == "bad":
        raise ValueError("tokenizer refused the row")
    length, salt = map(int, text.split(":"))
    return [2 + (salt + j) % 97 for j in range(length)]


def _stub_device(batch):
    """Per row: the ids' sum, how many there are, and the bucket edge it
    was dispatched at."""
    b = np.asarray(batch)
    return np.stack(
        [b.sum(1), (b != 0).sum(1), np.full(len(b), b.shape[1])], axis=1
    ).astype(np.float32)


def _text_counters():
    counters = metrics.scalar_snapshot()["counters"]
    return {
        k: v
        for k, v in counters.items()
        if k.startswith("text.") or k == "feeder.pad_rows"
    }


def _bucketed_job(
    parts, batch_size, tokenize=_stub_tokenize, device=_stub_device
):
    """One job over `parts`, all partitions at once; the outputs by
    partition and what the job added to each text counter."""
    from sparkdl_tpu.runtime.executor import Executor
    from sparkdl_tpu.runtime.feeder import shutdown_feeders

    before = _text_counters()
    ex = Executor(max_workers=len(parts), max_failures=1)
    try:
        out = ex.map_partitions(
            lambda i, cells: run_bucketed(
                cells, tokenize, device, batch_size, 32, ladder=_LADDER
            ),
            parts,
        )
    finally:
        shutdown_feeders()
        ex.close()
    after = _text_counters()
    return out, {
        k: after[k] - before.get(k, 0)
        for k in after
        if after[k] != before.get(k, 0)
    }


def _partitions(n_parts, rows, length_of):
    """`length_of(r)` is row r's token length, None for a null cell, or
    the cell itself where it is a string."""

    def cell(p, r):
        length = length_of(r)
        if length is None or isinstance(length, str):
            return length
        return _cell(length, 1000 * p + r)

    return [[cell(p, r) for r in range(rows)] for p in range(n_parts)]


_MIXED = (3, 12, 30, 7, 16, 20)

#: name -> (rows a partition, batchSize, row number -> token length, None
#: for a null cell, or the cell itself)
_STREAM_CASES = {
    "one_bucket": (100, 64, lambda r: 9 + r % 8),
    "three_buckets": (100, 64, lambda r: _MIXED[r % 6]),
    "null_rows": (100, 64, lambda r: None if r % 7 == 3 else _MIXED[r % 6]),
    "tokenizer_raises": (
        100, 64, lambda r: "bad" if r % 5 == 2 else _MIXED[r % 6],
    ),
    "longer_than_top_edge": (100, 64, lambda r: 40 if r % 9 == 4 else 25),
    "smaller_than_one_chunk": (20, 64, lambda r: _MIXED[r % 6]),
    "no_multiple_of_the_chunk": (75, 64, lambda r: _MIXED[r % 6]),
    "batch_size_4": (50, 4, lambda r: _MIXED[r % 6]),
    "chunk_over_the_floor": (300, 1024, lambda r: _MIXED[r % 6]),
}


@pytest.mark.parametrize("n_parts", [2, 4])
@pytest.mark.parametrize("case", sorted(_STREAM_CASES))
def test_chunked_streaming_matches_whole_partition(monkeypatch, case, n_parts):
    """Handing a partition over chunk by chunk changes the order in
    which the feeder sees rows and nothing else: the same answer in
    every cell, the same text counters, no more padded rows."""
    from sparkdl_tpu.text import bucketing

    rows, batch_size, length_of = _STREAM_CASES[case]
    parts = _partitions(n_parts, rows, length_of)
    chunk = max(32, batch_size // (4 * n_parts))
    assert bucketing._MIN_CHUNK_ROWS == 32

    chunked, counted = _bucketed_job(parts, batch_size)
    # the whole partition as one chunk: what the engine did before
    monkeypatch.setattr(bucketing, "_MIN_CHUNK_ROWS", 10**9)
    whole, counted_whole = _bucketed_job(parts, batch_size)

    assert counted.pop("text.tokenize_chunks") == n_parts * -(-rows // chunk)
    assert counted_whole.pop("text.tokenize_chunks") == n_parts
    assert counted.pop("feeder.pad_rows", 0) <= counted_whole.pop(
        "feeder.pad_rows", 0
    )
    assert counted == counted_whole
    assert counted["text.tokens"] > 0 and counted["text.pad_tokens"] > 0
    assert (counted.get("text.truncated_rows", 0) > 0) == (
        case == "longer_than_top_edge"
    )
    assert sum(
        v for k, v in counted.items() if k.startswith("text.bucket_rows.")
    ) == sum(c not in (None, "bad") for part in parts for c in part)
    for part, got, want in zip(parts, chunked, whole):
        assert len(got) == len(want) == len(part)
        for cell, a, b in zip(part, got, want):
            if cell in (None, "bad"):
                assert a is None and b is None
                continue
            ids = _stub_tokenize(cell)
            edge = bucket_for(len(ids), _LADDER)
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(
                a, [sum(ids[:edge]), min(len(ids), edge), edge]
            )


def test_device_starts_before_the_partition_is_tokenized():
    """The mechanism engages: the first batch is dispatched while every
    partition still has rows to tokenize. Each partition's last row
    waits for the device's first call, which on an engine that
    tokenizes the whole partition first never comes."""
    import threading

    dispatched = threading.Event()
    seen_at_last_row = []

    def tokenize(text):
        if text.endswith(":last"):
            seen_at_last_row.append(dispatched.wait(timeout=20))
            text = text[: -len(":last")]
        return _stub_tokenize(text)

    def device(batch):
        dispatched.set()
        return _stub_device(batch)

    # batchSize 512 over 2 partitions: chunks of 512 / (4 * 2) = 64 rows
    parts = _partitions(2, 400, lambda r: 12)
    parts[1] = parts[1][:330]
    for part in parts:
        part[-1] += ":last"
    out, counted = _bucketed_job(parts, 512, tokenize, device)
    assert seen_at_last_row == [True, True]
    assert counted["text.tokenize_chunks"] == 7 + 6
    assert counted["text.bucket_rows.16"] == 730
    assert all(y is not None for part in out for y in part)


@pytest.mark.parametrize("fault", ["tokenizers_caller", "device_function"])
def test_streaming_failure_leaves_no_handle_open(fault):
    """A partition that fails mid-stream, with chunks already handed
    over in three buckets, fails every handle it holds and ends them: a
    device error reaches every partition, an error of run_bucketed's
    own only its partition, and either way the feeders go idle."""
    import time

    from sparkdl_tpu.runtime import feeder as feeder_mod
    from sparkdl_tpu.runtime.executor import Executor

    parts = _partitions(4, 100, lambda r: _MIXED[r % 6])

    def tokenize(text):
        if text == _cell(_MIXED[70 % 6], 2070) and fault != "device_function":
            return 7  # no sequence: `len` raises in run_bucketed itself
        return _stub_tokenize(text)

    def device(batch):
        if fault == "device_function":
            raise RuntimeError("device fell over")
        return _stub_device(batch)

    def partition(i, cells):
        try:
            return run_bucketed(cells, tokenize, device, 16, 32, ladder=_LADDER)
        except Exception as e:
            return e

    ex = Executor(max_workers=4, max_failures=1)
    try:
        out = ex.map_partitions(partition, parts)
        if fault == "device_function":
            assert all(
                isinstance(o, RuntimeError) and "fell over" in str(o)
                for o in out
            )
        else:
            assert isinstance(out[2], TypeError)
            for p in (0, 1, 3):
                assert all(y is not None for y in out[p])
        deadline = time.monotonic() + 10
        feeders = list(feeder_mod._feeders.values())
        assert len(feeders) == 3  # one stream a bucket
        while not all(f.idle() for f in feeders):
            assert time.monotonic() < deadline, "a handle was left open"
            time.sleep(0.02)
        assert all(f._open == 0 for f in feeders)
    finally:
        feeder_mod.shutdown_feeders()
        ex.close()
