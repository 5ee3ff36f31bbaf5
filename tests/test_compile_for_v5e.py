"""The kernels of the benchmark's cells compiled at the published widths
for a v5e that is described and not attached: what the chip's compiler would
refuse (a slice off the tiling, too much fast memory) it refuses here, at
no chip time. Nothing runs, so nothing here says a result or a time.

The topology is described inside a fixture, after a test of this file has
started: only one process may load the TPU's library, and every worker
imports every test file. All such tests live in this one file."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sparkdl_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_packed,
)
from sparkdl_tpu.models import jamba
from sparkdl_tpu.ops import moe_combine
from sparkdl_tpu.ops.selective_scan import selective_scan

ROWS, D_INNER, D_STATE = 8, 5120, 16  # a dispatch of the cell; Jamba2-3B
HEADS, HEAD_DIM = 20, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)


@pytest.mark.parametrize("length", [1024, 2048])
def test_selective_scan_compiles_at_the_published_widths(shape, length):
    bf16, f32 = jnp.bfloat16, jnp.float32
    wide, narrow = (ROWS, length, D_INNER), (ROWS, length, D_STATE)
    compiled = (
        jax.jit(functools.partial(selective_scan, out_dtype=bf16))
        .lower(
            shape(wide, f32), shape(wide, f32), shape(narrow, f32),
            shape(narrow, f32), shape(wide, f32), shape((D_INNER, D_STATE), f32),
            shape((D_INNER,), f32),
        )
        .compile()
    )
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the name the trace will show: `%selective_scan.N = ... custom-call`
    assert "%selective_scan" in text
    # the state never leaves the kernel: what is written out is y, and
    # the transposed B and C
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * ROWS * length * D_STATE * 4 + (1 << 20)


@pytest.mark.parametrize("length", [1024, 2048])
def test_nothing_stands_between_the_projections_and_the_scan(shape, length):
    """The call as `models/jamba.py` makes it, inside a jitted Mamba mixer
    of the published widths: the kernel reads ``h``, ``dt`` and ``z`` as
    ``[8, L, 5120]`` with the tokens on the sublanes, as the fusions
    before it write them. A relayout outside the kernel would be a
    ``copy`` or a ``transpose`` of that shape in this text (0.8 ms each
    on the chip); what the kernel needs turned, it turns in VMEM."""
    config, bf16 = jamba.jamba2_3b(), jnp.bfloat16
    params = {
        path.split("/")[1]: shape(dims, jamba._leaf_dtype(path, dims, bf16))
        for path, dims in jamba.layer_shapes(config, 0).items()
        if path.startswith("mamba/")
    }
    scan = functools.partial(selective_scan, out_dtype=bf16)
    text = (
        jax.jit(lambda p, u: jamba._mamba(config, p, u, scan))
        .lower(params, shape((ROWS, length, config.hidden_size), bf16))
        .compile()
        .as_text()
    )
    call = re.search(
        r"%selective_scan\S* = .*custom-call\((.*?)\), custom_call_target", text
    )
    assert call, "no %selective_scan custom call in the compiled mixer"
    wide = rf"\[{ROWS},{length},({D_INNER}|{2 * D_INNER})\]"
    moved = re.findall(rf"%\S+ = \w+{wide}\S* (?:copy|transpose)\(", text)
    assert not moved, moved
    # h, dt and z come straight from fusions (z as one output of the
    # fusion that splits in_proj's product)
    operands = [name.strip() for name in call.group(1).split(",")]
    made_by = [
        made.group(2)
        for name in operands
        if (made := re.search(rf"{re.escape(name)} = \w+{wide}\S* ([\w-]+)\(", text))
    ]
    assert len(made_by) == 3, made_by
    assert set(made_by) <= {"fusion", "get-tuple-element"}, made_by


def test_causal_flash_with_one_shared_head_compiles(shape):
    bf16 = jnp.bfloat16
    q = shape((ROWS, HEADS, 2048, HEAD_DIM), bf16)
    kv = shape((ROWS, 1, 2048, HEAD_DIM), bf16)
    compiled = (
        jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, block_q=512, block_k=512, causal=True
            )
        )
        .lower(q, kv, kv)
        .compile()
    )
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the key/value head is found by the index map: nothing of the size of
    # 20 copies of it is made on the way in
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * ROWS * 2048 * HEAD_DIM * 2


@pytest.mark.parametrize("rows,length", [(2048, 128), (1024, 256), (512, 512)])
def test_packed_flash_compiles_at_bert_base_widths(shape, rows, length):
    """A dispatch of `bert-base-embed` (the 128 bucket) and as many
    tokens at the 256 and 512 buckets (two and four key blocks: the
    online softmax's scratch), float32, twelve heads of 64 side by side."""
    f32 = jnp.float32
    x = shape((rows, length, 768), f32)
    compiled = (
        jax.jit(functools.partial(flash_attention_packed, num_heads=12))
        .lower(x, x, x, shape((rows, length), f32))
        .compile()
    )
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the name the trace's readers look for
    assert "%flash_attention" in text
    # no padded, transposed or sliced copy on the way in or out: nothing
    # of the size of q is made beside the output
    assert compiled.memory_analysis().temp_size_in_bytes < rows * length * 768 * 4


@pytest.mark.parametrize("length", [1024, 2048])
def test_the_expert_layers_two_buffers_share_and_copy_no_expert(shape, length):
    """One expert layer of `deepseek-v2` as `models/moe.py:routed`
    builds it for a chip that holds 40 of the 160 experts: the sized slot
    buffer and the worst-case one are the arms of one conditional. The
    arms never live together, so the layer's temporaries are the larger
    arm's and not their sum; the 2.8 GB of expert matrices go into the
    conditional as they are; the kernels inside it keep the name the
    trace's readers look for; and the combine kernel reads the last
    product's rows where they lie, through a bitcast, so that its event
    names no product kernel."""
    from sparkdl_tpu.models import deepseek_v2
    from sparkdl_tpu.models import moe as moe_layer
    from sparkdl_tpu.ops.grouped_matmul import grouped_matmul

    config = deepseek_v2.deepseek_v2()
    bf16, f32 = jnp.bfloat16, jnp.float32
    shapes = deepseek_v2.layer_shapes(config, config.first_k_dense)
    moe = {
        "router": shape(shapes["moe/router"], f32),
        "experts": {
            k: shape(shapes[f"moe/experts/{k}"], bf16) for k in ("gate", "up", "down")
        },
    }
    tokens, hidden = ROWS * length, config.hidden_size
    slots = tokens * config.num_experts_per_tok
    capacity = moe_layer.slot_capacity(config, tokens)
    assert capacity == 15 * length == 1.25 * slots / 4

    def layer(p, u, real):
        return moe_layer.routed(
            config, p, u, real, grouped_matmul, gate=moe_layer.route,
            combine_fn=moe_combine.moe_combine,
        )

    compiled = (
        jax.jit(layer)
        .lower(moe, shape((ROWS, length, hidden), f32), shape((ROWS, length), bool))
        .compile()
    )
    text = compiled.as_text()
    assert len(re.findall(r" conditional\(", text)) == 1
    # gate, up and down in each arm: `%moe_grouped_matmul.N = ... custom-call`
    assert len(re.findall(r"%moe_grouped_matmul[.\w]* = ", text)) == 6
    # the combine, one an arm, reads the down products' rows where they
    # lie: nothing turns or copies a buffer of slot rows between. A
    # kernel's trace event lists its operands, and the readers of the
    # product's time take every event that names `%moe_grouped_matmul`:
    # the combine takes the rows through a bitcast, the same bytes
    combines = re.findall(r"%moe_combine[.\w]* = .*", text)
    assert len(combines) == 2
    assert not re.findall(rf"= f32\[\d+,(?:1,)?{hidden}\]\S* (?:copy|transpose)\(", text)
    tiles = rf"%bitcast[.\w]* = f32\[\d+,{hidden // 128},8,1,128\]\S* bitcast\(%moe_grouped_matmul"
    assert len(re.findall(tiles, text)) == 2
    assert not [line for line in combines if "%moe_grouped_matmul" in line]
    made = re.findall(
        r"= bf16\[40,(?:5120,1536|1536,5120)\]\S* (?!parameter|get-tuple-element)(\w[\w-]*)\(",
        text,
    )
    assert made == [], made
    # the worst-case arm at its fullest: its y in float32 and no gathered
    # part (the kernel writes the layer's result, no temporary); what else
    # the arm holds is under one [tokens, hidden] float32; the sized arm
    # (y a third of that) lies under it
    f32_rows = lambda n: n * hidden * 4  # noqa: E731
    worst, sized = f32_rows(slots), f32_rows(capacity)
    assert sized < worst
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert worst <= temp < worst + f32_rows(tokens), (temp, worst, sized)


#: (tokens, k, slot rows, hidden) of the combine in the three expert
#: cells: `deepseek-v2` sized and worst-case at both buckets (k = 6),
#: `deepseek-v3.2-exp` sized (8 held experts) and worst-case, `trinity-mini`
#: (every expert held: one buffer of every slot)
COMBINE_SHAPES = [
    (16384, 6, 30720, 5120), (16384, 6, 98304, 5120), (8192, 6, 15360, 5120),
    (16384, 8, 5120, 7168), (16384, 8, 131072, 7168), (8192, 8, 2560, 7168),
    (16384, 8, 131072, 2048), (8192, 8, 65536, 2048),
]


def _lowered_combine(shape, tokens, k, rows, hidden):
    return jax.jit(moe_combine.moe_combine).lower(
        shape((rows, hidden), jnp.float32), shape((tokens, k), jnp.int32),
        shape((tokens, k), jnp.float32),
    )


@pytest.mark.parametrize("tokens, k, rows, hidden", COMBINE_SHAPES)
def test_the_combine_compiles_at_the_cells_shapes(shape, tokens, k, rows, hidden):
    """One kernel under the name the trace's readers find, the slots in
    SMEM (every token's k rows: 512 KB at 16,384 x 8, half the v5e's),
    nothing padded or copied: the kernel reads `y` where it lies and
    writes the result alone."""
    compiled = _lowered_combine(shape, tokens, k, rows, hidden).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert len(re.findall(r"%moe_combine[.\w]* = ", text)) == 1
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == tokens * hidden * 4
    assert memory.temp_size_in_bytes < tokens * k * 4 + (1 << 20)


def test_the_combine_loop_is_rolled(shape):
    """The kernel's Mosaic module at 2,048 and at 16,384 tokens (and at
    another buffer of slot rows) is one text but for the numbers of its
    shapes, so the same size within their digits: its token loop is a
    loop, and what it costs to lower does not grow with the tokens."""
    texts = [
        _mosaic_text(_lowered_combine(shape, tokens, 6, rows, 5120).as_text())
        for tokens, rows in [(2048, 3840), (16384, 30720), (16384, 98304)]
    ]
    assert len({re.sub(r"\d+", "0", t) for t in texts}) == 1
    # about 90 K characters at k = 6: a body unrolled over a tile of 128
    # tokens would be some hundreds of thousands
    assert max(map(len, texts)) < 120_000


def test_a_buckets_expert_layers_lower_one_combine_an_arm(shape, monkeypatch):
    """The four expert layers of a `deepseek-v2` bucket call the combine
    at eight sites (two arms each), of two shapes: the kernel's body is
    traced and lowered twice, once an arm, since the call sites of one
    shape share one `pallas_call`."""
    from sparkdl_tpu.models import deepseek_v2, moe
    from sparkdl_tpu.ops.grouped_matmul import grouped_matmul

    traced = []
    kernel = moe_combine._kernel
    monkeypatch.setattr(
        moe_combine, "_kernel", lambda *a: traced.append(1) or kernel(*a)
    )
    moe_combine._build.cache_clear()
    config = deepseek_v2.deepseek_v2()
    bf16, f32 = jnp.bfloat16, jnp.float32
    shapes = deepseek_v2.layer_shapes(config, config.first_k_dense)
    layers = [
        {
            "router": shape(shapes["moe/router"], f32),
            "experts": {
                k: shape(shapes[f"moe/experts/{k}"], bf16) for k in ("gate", "up", "down")
            },
        }
        for _ in range(4)
    ]

    def bucket(layers, u, real):
        for p in layers:
            routed, _, _ = moe.routed(
                config, p, u, real, grouped_matmul, gate=moe.route,
                combine_fn=moe_combine.moe_combine,
            )
            u = u + routed
        return u

    text = (
        jax.jit(bucket)
        .lower(layers, shape((ROWS, 2048, config.hidden_size), f32), shape((ROWS, 2048), bool))
        .as_text()
    )
    moe_combine._build.cache_clear()
    assert text.count('kernel_name = "moe_combine"') == 8
    assert len(traced) == 2


# -- DeepSeek sparse attention at the published widths -------------------------


@pytest.mark.parametrize("length", [8192, 16384])
def test_the_indexers_two_kernels_compile_at_the_published_widths(shape, length):
    """A dispatch of `deepseek-v3.2-exp-embed-long-docs` (one row): 64
    index heads of 128 against the one index key, and the search for each
    query's 2,048th score. What leaves the first kernel is the [L, L]
    float32 of summed scores and nothing of the 64 per-head tiles; what
    leaves the second is a byte a pair."""
    from sparkdl_tpu.ops import dsa_indexer

    bf16, f32 = jnp.bfloat16, jnp.float32

    def indexer(q, k, w):
        scores = dsa_indexer.dsa_index_scores(q, k, w, num_heads=64)
        return dsa_indexer.dsa_select(scores, top_k=2048)

    compiled = (
        jax.jit(indexer)
        .lower(shape((1, length, 64 * 128), bf16), shape((1, length, 128), bf16),
               shape((1, length, 64), f32))
        .compile()
    )
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # the names the trace's readers look for, each call by its own name
    assert len(re.findall(r"%dsa_index_scores[.\w]* = ", text)) == 1
    assert len(re.findall(r"%dsa_select[.\w]* = ", text)) == 1
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == length * length  # int8
    assert memory.temp_size_in_bytes < length * length * 4 + (64 << 20)


@pytest.mark.parametrize("length", [8192, 16384])
def test_the_indexers_two_kernels_compile_with_the_rows_lengths(shape, length):
    """The same dispatch given the row's length: one call of each kernel
    under its own name, each taking its count of live query blocks as a
    scalar-prefetch operand, and what leaves them as without lengths."""
    from sparkdl_tpu.ops import dsa_indexer

    bf16, f32 = jnp.bfloat16, jnp.float32

    def indexer(q, k, w, n):
        scores = dsa_indexer.dsa_index_scores(q, k, w, n, num_heads=64)
        return dsa_indexer.dsa_select(scores, n, top_k=2048)

    compiled = (
        jax.jit(indexer)
        .lower(shape((1, length, 64 * 128), bf16), shape((1, length, 128), bf16),
               shape((1, length, 64), f32), shape((1,), jnp.int32))
        .compile()
    )
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for kernel in ("dsa_index_scores", "dsa_select"):
        calls = re.findall(rf"%{kernel}(?:\.\d+)? = .*", text)
        assert len(calls) == 1
        assert "operand_layout_constraints={s32[1]{0}, " in calls[0]
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == length * length  # int8
    assert memory.temp_size_in_bytes < length * length * 4 + (64 << 20)


#: sha256 of the Mosaic module (debug locations aside) of the indexer's two
#: kernels called without lengths at the cell's shapes, taken from the
#: commit before lengths were added
DSA_PINNED = {
    (8192, "dsa_index_scores"): "7155c3f707d81aca",
    (8192, "dsa_select"): "0fabcaf5ac463461",
    (16384, "dsa_index_scores"): "1e191fb00d4903e4",
    (16384, "dsa_select"): "bb40ac1ee7a4a7a4",
}


@pytest.mark.parametrize("length, kernel", sorted(DSA_PINNED))
def test_the_indexers_kernels_without_lengths_lower_as_the_parent_did(shape, length, kernel):
    """Without lengths the index-scores and the selection kernel at the
    V3.2 cell's shapes are the parent's Mosaic modules to the byte: the
    call with lengths adds nothing to the call without them."""
    import hashlib

    from sparkdl_tpu.ops import dsa_indexer

    bf16, f32 = jnp.bfloat16, jnp.float32
    if kernel == "dsa_index_scores":
        lowered = jax.jit(
            lambda q, k, w: dsa_indexer.dsa_index_scores(q, k, w, num_heads=64)
        ).lower(shape((1, length, 64 * 128), bf16), shape((1, length, 128), bf16),
                shape((1, length, 64), f32))
    else:
        lowered = jax.jit(lambda s: dsa_indexer.dsa_select(s, top_k=2048)).lower(
            shape((1, length, length), f32)
        )
    digest = hashlib.sha256(_mosaic_text(lowered.as_text()).encode()).hexdigest()[:16]
    assert digest == DSA_PINNED[(length, kernel)]


@pytest.mark.parametrize("lengths", [False, True], ids=["whole", "lengths"])
@pytest.mark.parametrize(
    "rows, length, selected",
    [(1, 8192, True), (1, 16384, True), (8, 2048, False), (8, 1024, False)],
)
def test_latent_flash_compiles_at_the_cells_shapes(shape, rows, length, selected, lengths):
    """128 heads of 128 + 2 x 64 query lanes over the up-projected latent,
    blocks of 1,024 walked in key sub-tiles: a dispatch of
    `deepseek-v3.2-exp-embed-long-docs` (one row, the selection a fifth
    operand read a [1024, 1024] int8 block a step) and of
    `deepseek-v2-embed-windows` (eight rows, no selection), without and
    with the rows' lengths (a scalar-prefetch operand of `rows` int32).
    Nothing is padded, repeated or converted in HBM."""
    from sparkdl_tpu.ops.flash_attention import flash_attention_latent

    bf16 = jnp.bfloat16
    wide = shape((rows, length, 128 * 256), bf16)
    operands = [
        wide, wide, shape((rows, length, 128), bf16),
        shape((rows, length, length), jnp.int8) if selected else None,
        shape((rows,), jnp.int32) if lengths else None,
    ]
    compiled = (
        jax.jit(
            lambda q, kv, k_rope, selection, lengths: flash_attention_latent(
                q, kv, k_rope, selection, lengths, num_heads=128, scale=0.1353,
                block=1024,
            )
        )
        .lower(*operands)
        .compile()
    )
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%flash_attention" in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < (1 << 20)
    # the operands as they came and the [rows, length, 128 * 128] result
    given = 2 * wide.size * 2 + rows * length * 128 * 2 + selected * rows * length * length
    assert memory.argument_size_in_bytes - given in ((0, 512)[lengths],)
    assert memory.output_size_in_bytes == rows * length * 128 * 128 * 2


def test_the_v32_program_holds_the_indexers_kernels_a_layer(shape):
    """The whole program of the DeepSeek-V3.2 cell's larger bucket (one
    row of 16,384, five layers at the published widths, weights as
    arguments), its indexer and attention each handed the row's length:
    one call of each indexer kernel a layer under its own name, the names
    the trace's readers match, each taking the row's live query blocks as
    its first operand."""
    from sparkdl_tpu.models import deepseek_v32
    from sparkdl_tpu.models.layers import leaf_dtype, unflatten
    from sparkdl_tpu.ops import dsa_indexer
    from sparkdl_tpu.ops.flash_attention import flash_attention_latent
    from sparkdl_tpu.ops.grouped_matmul import grouped_matmul

    config, bf16 = deepseek_v32.deepseek_v32(), jnp.bfloat16
    leaves = {
        p: shape(s, leaf_dtype(p, s, bf16))
        for p, s in deepseek_v32.param_shapes(config).items()
    }

    def attention(q, kv, k_rope, dtype, selection=None, lengths=None):
        return flash_attention_latent(
            q, kv, k_rope, selection, lengths, num_heads=config.num_heads,
            scale=config.softmax_scale, block=1024,
        ).astype(dtype)

    def indexer(q, k, w, lengths=None):
        scores = dsa_indexer.dsa_index_scores(q, k, w, lengths, num_heads=64)
        return dsa_indexer.dsa_select(scores, lengths, top_k=config.index_topk)

    attention.takes_lengths = indexer.takes_lengths = True

    def program(p, ids):
        return deepseek_v32.forward(
            config, p, ids, dtype=bf16, attention_fn=attention, experts_fn=grouped_matmul,
            indexer_fn=indexer, combine_fn=moe_combine.moe_combine,
        )

    text = (
        jax.jit(program).lower(unflatten(leaves), shape((1, 16384), jnp.int32))
        .compile().as_text()
    )
    for kernel in ("dsa_index_scores", "dsa_select"):
        calls = re.findall(rf"%{kernel}(?:\.\d+)? = .*", text)
        assert len(calls) == config.num_layers
        assert all("operand_layout_constraints={s32[1]{0}, " in c for c in calls)


# -- Trinity-Mini: the window kernel and the whole program ----------------------


def _mosaic_text(compiled_or_lowered_text: str) -> str:
    """The Mosaic module of the one kernel in a lowered program's text, as
    MLIR without its debug locations (a line moved in the kernel's source
    file changes those and nothing else)."""
    import base64

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    body = re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', compiled_or_lowered_text)
    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        tpu.register_dialect(ctx)
        module = ir.Module.parse(base64.b64decode(body.group(1)))
        return module.operation.get_asm(enable_debug_info=False)


def test_window_none_lowers_jambas_kernel_as_the_parent_did(shape):
    """The causal kernel at Jamba's cell's shapes (8 rows, 20 query heads
    over 1, blocks of 512) is the parent commit's Mosaic module to the
    byte, debug locations aside: the window mode added nothing to it.
    The hash is the parent's (PR 38's tree), taken when the mode was
    added."""
    import hashlib

    q = shape((ROWS, HEADS, 2048, HEAD_DIM), jnp.bfloat16)
    kv = shape((ROWS, 1, 2048, HEAD_DIM), jnp.bfloat16)
    text = (
        jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, block_q=512, block_k=512, causal=True, window=None
            )
        )
        .lower(q, kv, kv)
        .as_text()
    )
    mosaic = _mosaic_text(text)
    assert hashlib.sha256(mosaic.encode()).hexdigest()[:16] == "faa96c9389a3baa5"


@pytest.mark.parametrize("length", [8192, 16384])
def test_window_kernel_compiles_at_the_cells_shapes(shape, length):
    """A sliding layer of `trinity-mini-embed-long-docs`: one row, 32
    query heads over 4 key/value heads of 128, a window of 2,048 in
    blocks of 512: a band of 5 key steps. Nothing is repeated or padded
    in HBM; the call carries its own name."""
    bf16 = jnp.bfloat16
    q = shape((1, 32, length, HEAD_DIM), bf16)
    kv = shape((1, 4, length, HEAD_DIM), bf16)
    compiled = (
        jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, block_q=512, block_k=512, causal=True, window=2048
            )
        )
        .lower(q, kv, kv)
        .compile()
    )
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert len(re.findall(r"%flash_attention_window[.\w]* = ", text)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


@pytest.mark.parametrize("window", [None, 2048], ids=["causal", "window"])
@pytest.mark.parametrize("length", [8192, 16384])
def test_the_blocked_kernels_with_lengths_compile_at_the_cells_shapes(shape, length, window):
    """A full and a sliding layer of `trinity-mini-embed-long-docs` (one
    row, 32 query heads over 4 of 128, blocks of 512) given the row's
    length: one call each under its own name, each row's count of live
    query blocks the one operand more (an int32, padded to 512 bytes),
    nothing made in HBM."""
    bf16 = jnp.bfloat16
    q = shape((1, 32, length, HEAD_DIM), bf16)
    kv = shape((1, 4, length, HEAD_DIM), bf16)
    compiled = (
        jax.jit(
            lambda q, k, v, n: flash_attention(
                q, k, v, block_q=512, block_k=512, causal=True, window=window, lengths=n
            )
        )
        .lower(q, kv, kv, shape((1,), jnp.int32))
        .compile()
    )
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    name = "flash_attention_window" if window else r"flash_attention(?!_window)"
    assert len(re.findall(rf"%{name}[.\w]* = ", text)) == 1
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < (1 << 20)
    given = (q.size + 2 * kv.size) * 2
    assert memory.argument_size_in_bytes - given == 512
    assert memory.output_size_in_bytes == q.size * 2


#: sha256 of the Mosaic module (debug locations aside) of Trinity's two
#: kernels called without lengths at the cell's shapes, taken from the
#: commit before lengths were added
TRINITY_PINNED = {
    (8192, None): "7fea0fff9391f310",
    (16384, None): "8276668f45083bd8",
    (8192, 2048): "de6ea12108effb45",
    (16384, 2048): "7fac7fc6082ab9ee",
}


@pytest.mark.parametrize("length, window", sorted(TRINITY_PINNED, key=str))
def test_trinitys_kernels_without_lengths_lower_as_the_parent_did(shape, length, window):
    """Without lengths the causal and the window kernel at the Trinity
    cell's shapes are the parent's Mosaic modules to the byte: the call
    with lengths adds nothing to the call without them."""
    import hashlib

    q = shape((1, 32, length, HEAD_DIM), jnp.bfloat16)
    kv = shape((1, 4, length, HEAD_DIM), jnp.bfloat16)
    text = (
        jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, block_q=512, block_k=512, causal=True, window=window
            )
        )
        .lower(q, kv, kv)
        .as_text()
    )
    digest = hashlib.sha256(_mosaic_text(text).encode()).hexdigest()[:16]
    assert digest == TRINITY_PINNED[(length, window)]


@pytest.mark.parametrize("length", [8192, 16384])
def test_trinity_programs_fit_the_chip(shape, length):
    """The whole program of a bucket of the Trinity-Mini cell (one row,
    five layers at the published widths, every expert held, weights as
    arguments): the window kernel in the four sliding layers, the causal
    kernel in the full one, each handed the row's length, three grouped
    products an expert layer, and no conditional (every expert held: one
    slot buffer). The weights and the larger bucket's temporaries lie
    well under the chip's 16 GiB."""
    from sparkdl_tpu.models import afmoe
    from sparkdl_tpu.models.layers import leaf_dtype, unflatten
    from sparkdl_tpu.ops.grouped_matmul import grouped_matmul

    config, bf16 = afmoe.trinity_mini(), jnp.bfloat16
    leaves = {
        p: shape(s, leaf_dtype(p, s, bf16))
        for p, s in afmoe.param_shapes(config).items()
    }

    def attention(window):
        def fn(q, k, v, mask, dtype, lengths=None):
            return flash_attention(
                q, k, v, mask, block_q=512, block_k=512, causal=True, window=window,
                lengths=lengths,
            ).astype(dtype)

        fn.takes_lengths = True
        return fn

    def program(p, ids):
        return afmoe.forward(
            config, p, ids, dtype=bf16, attention_fn=attention(None),
            window_attention_fn=attention(2048), experts_fn=grouped_matmul,
            combine_fn=moe_combine.moe_combine,
        )

    compiled = (
        jax.jit(program).lower(unflatten(leaves), shape((1, length), jnp.int32)).compile()
    )
    text = compiled.as_text()
    assert len(re.findall(r"%flash_attention_window[.\w]* = ", text)) == 4
    assert len(re.findall(r"%flash_attention(?:\.\d+)? = ", text)) == 1
    # each of the five takes the row's live query blocks, its first operand
    calls = re.findall(r"%flash_attention\S* = .*", text)
    assert len(calls) == 5
    assert all("operand_layout_constraints={s32[1]{0}, bf16[32," in c for c in calls)
    assert len(re.findall(r"%moe_grouped_matmul[.\w]* = ", text)) == 12
    combines = re.findall(r"%moe_combine[.\w]* = .*", text)
    assert len(combines) == 4
    assert not [line for line in combines if "%moe_grouped_matmul" in line]
    assert len(re.findall(r" conditional\(", text)) == 0
    memory = compiled.memory_analysis()
    weights = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves.values())
    assert weights == pytest.approx(7.665e9, rel=1e-3)
    assert memory.argument_size_in_bytes - weights < (1 << 20)
    # every slot of the row's 8 x 4 expert layers in one buffer: 2.2 GB at
    # 16,384 tokens, half that at 8,192
    assert memory.temp_size_in_bytes < length * 160 * 1024
    assert weights + memory.temp_size_in_bytes < 0.65 * 16 * (1 << 30)


# -- Xing4.0: the hyper-connections' two kernels and the whole program ----------


XING_WIDTH = 4 * 3584  # four streams of the hidden size


def _hyper(n=4):
    from sparkdl_tpu.ops import hyper_connection

    constants = hyper_connection.Constants(n, 20, 1e-6, (-30.0, 30.0), 1e-6)
    return constants, hyper_connection.HyperConnection(constants, "pallas")


@pytest.mark.parametrize("tokens", [8 * 1024, 8 * 2048])
def test_the_hyper_connections_compile_at_the_cells_shapes(shape, tokens):
    """A dispatch of `xing4.0-29b-a4b-embed-windows` (eight rows of 1,024 or
    2,048 tokens, four streams of 3,584): one kernel each under its own
    name, nothing padded or copied in HBM (the pre-mix's only temporary is
    phi turned, 1.4 MB), and the post-mix writing the new stream into the
    old one's buffer."""
    from sparkdl_tpu.ops import hyper_connection

    constants, _ = _hyper()
    f32 = jnp.float32
    pre = (
        jax.jit(lambda x, p, b, a: hyper_connection.hc_pre(constants, x, p, b, a, jnp.bfloat16))
        .lower(shape((tokens, XING_WIDTH), f32), shape((XING_WIDTH, 24), f32),
               shape((24,), f32), shape((3,), f32))
        .compile()
    )
    text = pre.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert len(re.findall(r"%hc_pre[.\w]* = ", text)) == 1
    assert pre.memory_analysis().temp_size_in_bytes < XING_WIDTH * 24 * 4 + (1 << 20)
    post = (
        jax.jit(lambda x, f, hp, hr: hyper_connection.hc_post(constants, x, f, hp, hr),
                donate_argnums=0)
        .lower(shape((tokens, XING_WIDTH), f32), shape((tokens, 3584), f32),
               shape((tokens, 4), f32), shape((tokens, 16), f32))
        .compile()
    )
    text = post.as_text()
    assert len(re.findall(r"%hc_post[.\w]* = ", text)) == 1
    memory = post.memory_analysis()
    assert memory.temp_size_in_bytes < (1 << 20)
    assert memory.alias_size_in_bytes == tokens * XING_WIDTH * 4


@pytest.mark.parametrize("length", [1024, 2048])
def test_xing_programs_fit_the_chip(shape, length):
    """The whole program of a bucket of the Xing4.0 cell (eight rows, five
    layers at the published widths, all 64 experts held, weights as
    arguments): ten pre-mixes and ten post-mixes, the latent kernel in
    every layer, three grouped products and a combine an expert layer, and
    no conditional. The weights and the larger bucket's temporaries lie
    within the chip's 16 GiB, with room for the batches."""
    from sparkdl_tpu.models import xing4_0
    from sparkdl_tpu.models.layers import unflatten
    from sparkdl_tpu.ops.flash_attention import flash_attention_latent
    from sparkdl_tpu.ops.grouped_matmul import grouped_matmul

    config, bf16 = xing4_0.xing4_0_29b_a4b(), jnp.bfloat16
    leaves = {
        p: shape(s, xing4_0._leaf_dtype(p, s, bf16))
        for p, s in xing4_0.param_shapes(config).items()
    }

    def attention(q, kv, k_rope, dtype, lengths=None):
        return flash_attention_latent(
            q, kv, k_rope, None, lengths, num_heads=32, scale=config.softmax_scale,
            block=1024,
        ).astype(dtype)

    attention.takes_lengths = True

    def program(p, ids):
        return xing4_0.forward(
            config, p, ids, dtype=bf16, attention_fn=attention, experts_fn=grouped_matmul,
            hyper=_hyper()[1], combine_fn=moe_combine.moe_combine,
        )

    compiled = (
        jax.jit(program).lower(unflatten(leaves), shape((ROWS, length), jnp.int32)).compile()
    )
    text = compiled.as_text()
    assert len(re.findall(r"%hc_pre[.\w]* = ", text)) == 10
    assert len(re.findall(r"%hc_post[.\w]* = ", text)) == 10
    assert len(re.findall(r"%flash_attention[.\w]* = ", text)) == 5
    assert len(re.findall(r"%moe_grouped_matmul[.\w]* = ", text)) == 12
    assert len(re.findall(r"%moe_combine[.\w]* = ", text)) == 4
    assert len(re.findall(r" conditional\(", text)) == 0
    memory = compiled.memory_analysis()
    weights = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves.values())
    # the parameters in bfloat16, the mixes' 2.75 M in float32
    assert weights == pytest.approx(7.16e9, rel=2e-3)
    assert memory.argument_size_in_bytes - weights < (1 << 20)
    print("temporaries", length, memory.temp_size_in_bytes)
    assert weights + memory.temp_size_in_bytes < 0.9 * 16 * (1 << 30)
