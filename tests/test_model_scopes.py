"""The six text families name their parts on the device's timeline
(`sparkdl_tpu.utils.profiler.scope`): the compiled program of each tiny
preset carries every scope of its family's vocabulary and no other, and
is, its metadata aside, the program it is without them."""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from sparkdl_tpu.models import afmoe, bert, deepseek_v2, deepseek_v32, jamba, moe, xing4_0
from sparkdl_tpu.models.registry import get_model
from sparkdl_tpu.utils import profiler

ENCODER = {"embed", "attn.qkv", "attn.core", "attn.out", "mlp", "pool"}
MAMBA = {
    "mamba.in_proj", "mamba.conv", "mamba.ssm_inputs", "mamba.scan", "mamba.out_proj",
}
EXPERTS = {
    "embed", "mla.q", "mla.kv", "mla.core", "mla.out", "mlp", "moe.route",
    "moe.routed", "moe.gather", "moe.experts", "moe.combine", "moe.worst_case",
    "pool",
}
INDEXER = {"dsa.index_inputs", "dsa.select", "dsa.count"}
#: every expert held: one routed body, no worst-case arm
WINDOWED = {
    "embed", "attn.qkv", "attn.window", "attn.full", "attn.out", "mlp", "moe.route",
    "moe.routed", "moe.gather", "moe.experts", "moe.combine", "pool",
}
#: DeepSeek's parts around a residual of four streams; every expert held
HYPER = (EXPERTS - {"moe.worst_case"}) | {"mhc.pre", "mhc.post"}

#: family -> (its vocabulary (docs/OBSERVABILITY.md), a batch shape at
#: which its program holds every part: both arms of the routed path's
#: conditional, a row longer than the indexer's top-k)
FAMILIES = {
    "bert-tiny": (ENCODER, (2, 16)),
    "jamba-tiny": (ENCODER | MAMBA, (2, 32)),
    "deepseek-v2-tiny": (EXPERTS, (8, 128)),
    "deepseek-v3.2-exp-tiny": (EXPERTS | INDEXER, (2, 64)),
    "trinity-mini-tiny": (WINDOWED, (2, 64)),
    "xing4.0-tiny": (HYPER, (2, 64)),
}
#: every module that names a scope: the families and the routed layer
MODULES = (bert, jamba, deepseek_v2, deepseek_v32, afmoe, xing4_0, moe)


@functools.lru_cache(maxsize=None)
def compiled_text(name: str, scoped: bool) -> str:
    shape = FAMILIES[name][1]
    with contextlib.ExitStack() as stack:
        if not scoped:
            patch = stack.enter_context(pytest.MonkeyPatch.context())
            for module in MODULES:
                patch.setattr(module, "scope", lambda name: contextlib.nullcontext())
        mf = get_model(name).model_function(mode="embed")
        lowered = jax.jit(mf.fn).lower(mf.params, jnp.ones(shape, jnp.int32))
        return lowered.compile().as_text()


def names_in(text: str) -> set:
    return set(re.findall(re.escape(profiler.SCOPE_PREFIX) + r"([\w.]+)", text))


def without_metadata(text: str) -> str:
    """The program alone: every instruction's `metadata={...}` cut out,
    and the tables of files, functions and stack frames they point into."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    return re.sub(
        r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(.+\n)*", "\n", text
    )


def test_scope_is_a_named_scope_under_the_spans_prefix():
    from sparkdl_tpu.obs import spans

    assert profiler.SCOPE_PREFIX == spans.ANNOTATION_PREFIX == "sparkdl:"

    def f(x):
        with profiler.scope("mlp"):
            return jnp.tanh(x)

    text = jax.jit(f).lower(jnp.ones(4)).compile().as_text()
    assert 'op_name="jit(f)/sparkdl:mlp/tanh"' in text
    # a scope is no operation: the jaxpr's text does not know it
    assert str(jax.make_jaxpr(f)(jnp.ones(4))) == str(jax.make_jaxpr(jnp.tanh)(jnp.ones(4)))


@pytest.mark.parametrize("name", FAMILIES)
def test_program_names_every_scope_of_its_vocabulary(name):
    missing = FAMILIES[name][0] - names_in(compiled_text(name, True))
    assert not missing, f"{name} lost {sorted(missing)}"


@pytest.mark.parametrize("name", FAMILIES)
def test_program_names_no_scope_outside_its_vocabulary(name):
    extra = names_in(compiled_text(name, True)) - FAMILIES[name][0]
    assert not extra, f"{name} names {sorted(extra)}: docs/OBSERVABILITY.md lists the scopes"


@pytest.mark.parametrize("name", FAMILIES)
def test_program_is_the_one_without_scopes(name):
    bare = compiled_text(name, False)
    assert not names_in(bare)
    assert without_metadata(compiled_text(name, True)) == without_metadata(bare)
    assert "metadata=" not in without_metadata(bare)


@pytest.mark.parametrize("name", ["deepseek-v2-tiny", "deepseek-v3.2-exp-tiny"])
def test_the_worst_case_arm_is_nested_in_the_routed_path(name):
    """An operation of the conditional's worst-case branch carries both
    outer scopes and its own, the innermost last."""
    text = compiled_text(name, True)
    paths = set(re.findall(r'op_name="([^"]*sparkdl:moe\.worst_case[^"]*)"', text))
    assert paths
    for path in paths:
        assert re.search(r"sparkdl:moe\.routed/cond/branch_\d_fun/sparkdl:moe\.worst_case", path)
    assert any("sparkdl:moe.worst_case/" in p and "sparkdl:moe.experts" in p for p in paths)
    # the sized arm's operations carry no worst-case scope
    sized = set(re.findall(r'op_name="([^"]*branch_0_fun[^"]*sparkdl:moe\.experts[^"]*)"', text))
    sized |= set(re.findall(r'op_name="([^"]*branch_1_fun[^"]*sparkdl:moe\.experts[^"]*)"', text))
    assert any("moe.worst_case" not in p for p in sized)
