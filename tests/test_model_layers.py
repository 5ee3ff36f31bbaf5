"""The model layer's three levels: family modules over the shared
`models/layers.py` and `models/moe.py`, over `ops/`. No family reaches
into another's private names, and the four routed families' programs
carry the one column format `moe.py` owns."""

import ast
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models import afmoe, deepseek_v2, deepseek_v32, moe, xing4_0
from sparkdl_tpu.models.registry import get_model

MODELS = pathlib.Path(__file__).resolve().parents[1] / "sparkdl_tpu" / "models"

#: the plumbing that registers, fetches and converts models: not a family
#: and not a shared level
PLUMBING = {"__init__", "registry", "fetcher", "manifest", "keras_weights", "prepare_artifacts"}

#: (module, private name) -> the planted fault of the benchmark's that
#: patches the name in its module, so that every family must call it there
PINNED = {("deepseek_v2", "_swiglu"): "tests/benchmarks/afmoe_tiny.py:shared_dropped"}


def _private_reaches(path: pathlib.Path):
    """(line, module, name) of every underscore name of another module
    under `models/` that the module at `path` imports or reads."""
    me = path.stem
    tree = ast.parse(path.read_text())
    aliases, found = {}, []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if node.level:
                module = "sparkdl_tpu.models" + ("." + module if module else "")
            if module == "sparkdl_tpu.models":
                aliases.update({a.asname or a.name: a.name for a in node.names})
            elif module.startswith("sparkdl_tpu.models."):
                other = module.rsplit(".", 1)[1]
                found += [
                    (node.lineno, other, a.name)
                    for a in node.names
                    if a.name.startswith("_") and other != me
                ]
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("sparkdl_tpu.models.") and a.asname:
                    aliases[a.asname] = a.name.rsplit(".", 1)[1]
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and aliases.get(node.value.id, me) != me
            and node.attr.startswith("_")
            and not node.attr.startswith("__")
        ):
            found.append((node.lineno, aliases[node.value.id], node.attr))
    return found


def test_no_family_reaches_into_another_modules_private_names():
    checked = [p for p in sorted(MODELS.glob("*.py")) if p.stem not in PLUMBING]
    assert {"layers", "moe", "jamba", "deepseek_v2", "afmoe"} <= {p.stem for p in checked}
    reaches = {
        f"{path.name}:{line} reads {module}.{name}"
        for path in checked
        for line, module, name in _private_reaches(path)
        if (module, name) not in PINNED
    }
    assert not reaches, sorted(reaches)


def test_the_scan_sees_every_way_of_reaching_a_private_name(tmp_path):
    source = tmp_path / "family.py"
    source.write_text(
        "from sparkdl_tpu.models import jamba as j\n"
        "from sparkdl_tpu.models.deepseek_v2 import _rotate\n"
        "from .layers import rms, _hidden\n"
        "import sparkdl_tpu.models.moe as m\n"
        "x = j._causal_conv, m._ROW_TILE, j.forward, m.__name__\n"
    )
    assert sorted(_private_reaches(source)) == [
        (2, "deepseek_v2", "_rotate"), (3, "layers", "_hidden"),
        (5, "jamba", "_causal_conv"), (5, "moe", "_ROW_TILE"),
    ]


#: registered tiny preset -> (its family's module, the kernel roles its
#: builder reports)
ROUTED = {
    "deepseek-v2-tiny": (deepseek_v2, {"attention", "experts", "combine"}),
    "deepseek-v3.2-exp-tiny": (deepseek_v32, {"attention", "indexer", "experts", "combine"}),
    "trinity-mini-tiny": (afmoe, {"attention", "window_attention", "experts", "combine"}),
    "xing4.0-tiny": (xing4_0, {"attention", "residual", "experts", "combine"}),
}


@pytest.mark.parametrize("name", sorted(ROUTED))
def test_a_routed_familys_program_carries_the_one_column_format(name):
    module, roles = ROUTED[name]
    mf = get_model(name).model_function(mode="embed")
    config = module._SIZES[name]()
    assert mf.row_counters[: len(moe.ROW_COUNTERS)] == moe.ROW_COUNTERS
    assert mf.real_token_counters == {
        "moe.slots_routed": config.num_experts_per_tok * config.expert_layers
    }
    assert mf.weights_as_arguments and mf.vocab_size == config.vocab_size
    # each kernel's kind under its role, the build-time choice, and no other role
    assert {r for r in moe.KERNEL_ROLES.values() if hasattr(mf, r)} == roles
    assert all(getattr(mf, r) not in ("", "custom") for r in roles)
    # the columns: the embedding, then one a counter, as `row_counters` names them
    ids = np.zeros((2, 16), np.int32)
    ids[0, :11], ids[1, :3] = 5, 9
    out = np.asarray(mf.fn(mf.params, jnp.asarray(ids)))
    assert out.shape == (2, config.hidden_size + len(mf.row_counters))
    held, sized, full = out[:, config.hidden_size : config.hidden_size + 3].T
    assert (sized + full == config.expert_layers).all()
    routed = config.num_experts_per_tok * config.expert_layers * np.array([11, 3])
    if config.experts_held == (0, config.n_routed_experts):
        assert (held == routed).all()
    assert (held <= routed).all()
