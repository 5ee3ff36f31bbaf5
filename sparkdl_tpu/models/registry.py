"""Named pretrained-architecture registry.

Reference analogue: ``KERAS_APPLICATION_MODELS`` in
python/sparkdl/transformers/keras_applications.py (SURVEY.md §3 #8b) — the
table behind DeepImageFeaturizer/DeepImagePredictor mapping a model *name*
to (input geometry, preprocessing convention, feature layer, graph builder).

TPU-native twist: each entry builds a pure :class:`ModelFunction` in one of
two backends —

- ``flax``: in-tree flax.linen implementations (NHWC, bf16 compute on the
  MXU) — the performance path;
- ``keras``: keras.applications architectures on the Keras-3 JAX backend —
  the compatibility path that makes every upstream-named model available.

Offline weight policy (no network in TPU pods by design here): models
initialize randomly unless ``weights_file`` is given — a .npz / pickled
pytree for flax backends, a .keras/.h5 file for keras backends, and (for
the flax perf-path architectures — see keras_weights._CONVERTERS) a stock
keras-format file, converted exactly via models/keras_weights.py. Parity
tests are therefore weight-independent (they compare pipelines, not
pretrained accuracy); real deployments point weights_file at their
artifact store.
"""

from __future__ import annotations

import pickle
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.graph.ingest import ModelIngest


@dataclass(frozen=True)
class NamedImageModel:
    name: str
    height: int
    width: int
    preprocessing: str  # normalization convention: 'tf' | 'caffe' | 'torch'
    feature_dim: int
    backend: str  # 'flax' | 'keras'
    builder: Callable[..., ModelFunction]
    num_classes: int = 1000
    #: flax module factory (dtype=, num_classes=) for the in-tree perf
    #: path — lets :meth:`param_bytes_estimate` size the params via
    #: ``jax.eval_shape`` (trace only, no init compute, no weights).
    #: None for keras-backend entries, whose size needs a real build.
    module_factory: Optional[Callable[..., Any]] = None
    #: Serving mesh election: 'dp' (the default) lets the residency
    #: loader fan this model's global batches data-parallel across the
    #: serving mesh (SPARKDL_SERVE_MESH_WIDTH); 'none' pins single-chip
    #: programs — for models whose dispatch shape the mesh would break.
    mesh: str = "dp"

    @property
    def input_shape(self) -> Tuple[int, int, int]:
        return (self.height, self.width, 3)

    def flops_per_item(self) -> Optional[float]:
        """Analytic forward FLOPs for one image at the registry
        geometry (``utils/flops.py`` published-MAC table), or None for
        entries the table doesn't cover — the per-model number
        ``bench.py`` feeds ``_mfu`` so banked records carry a real
        utilization instead of ``"mfu": null``."""
        from sparkdl_tpu.utils.flops import MODEL_GMACS, model_flops_per_image

        if self.name not in MODEL_GMACS:
            return None
        return model_flops_per_image(self.name)

    def param_bytes_estimate(self) -> Optional[int]:
        """Device-memory estimate (bytes) for this model's float32 param
        pytree, WITHOUT initializing weights — shapes come from
        ``jax.eval_shape`` over the flax module's init. The residency
        manager's admission sizing for models not yet loaded; ``None``
        when the backend can't be sized without a build (keras)."""
        if self.module_factory is None:
            return None
        cached = _ESTIMATE_CACHE.get(self.name)
        if cached is not None:
            return cached
        module = self.module_factory(
            dtype=jnp.float32, num_classes=self.num_classes
        )
        shaped = jax.eval_shape(
            module.init,
            jax.random.PRNGKey(0),
            jnp.zeros((1, self.height, self.width, 3), jnp.float32),
        )
        total = param_bytes(shaped)
        _ESTIMATE_CACHE[self.name] = total
        return total

    def model_function(
        self,
        mode: str = "features",
        dtype: Any = jnp.float32,
        weights_file: Optional[str] = None,
        seed: int = 0,
    ) -> ModelFunction:
        """mode: 'features' (bottleneck vector), 'logits', or
        'probabilities' (softmax over the classification head)."""
        if mode not in ("features", "logits", "probabilities"):
            raise ValueError(f"Unknown mode {mode!r}")
        return self.builder(
            self, mode=mode, dtype=dtype, weights_file=weights_file, seed=seed
        )


#: name -> eval_shape'd param bytes (tracing ResNet50's init is cheap but
#: not free; supported_models(with_memory=True) asks for every entry).
_ESTIMATE_CACHE: Dict[str, int] = {}


@dataclass(frozen=True)
class NamedTextModel:
    """A registered text model: the :class:`NamedImageModel` sibling the
    serving residency/HBM machinery needs to treat LLM-shaped workloads
    as first-class registry entries. ``model_function`` returns a
    ModelFunction over int32 token-id batches ``[B, L]`` (the attention
    mask is derived ON DEVICE as ``ids != 0``, so zero-padding a row —
    to a bucket edge or the serving router's seq bucket — never changes
    its pooled embedding) producing ``[B, feature_dim]`` embeddings."""

    name: str
    max_length: int  # position-table capacity == the hard seq ceiling
    feature_dim: int
    backend: str  # 'flax'
    builder: Callable[..., "ModelFunction"]
    vocab_size: int = 30522
    #: () -> flax module, for eval_shape sizing without init compute.
    module_factory: Optional[Callable[[], Any]] = None
    #: seq_len -> analytic forward FLOPs per example (utils/flops.py).
    flops_fn: Optional[Callable[[int], float]] = None
    #: Serving mesh election — same contract as the image spec's field.
    mesh: str = "dp"

    @property
    def input_dtype(self) -> str:
        return "int32"

    def param_bytes_estimate(self) -> Optional[int]:
        """float32 param-pytree bytes via ``jax.eval_shape`` over the
        module's init (trace only, no weights) — same contract as the
        image spec's, so residency capacity planning covers both."""
        if self.module_factory is None:
            return None
        cached = _ESTIMATE_CACHE.get(self.name)
        if cached is not None:
            return cached
        module = self.module_factory()
        shaped = jax.eval_shape(
            module.init,
            jax.random.PRNGKey(0),
            jnp.zeros((1, min(self.max_length, 16)), jnp.int32),
        )
        total = param_bytes(shaped)
        _ESTIMATE_CACHE[self.name] = total
        return total

    def flops_per_item(self, seq_len: Optional[int] = None) -> Optional[float]:
        """Analytic forward FLOPs for one example at ``seq_len``
        (default: the full ``max_length`` geometry)."""
        if self.flops_fn is None:
            return None
        return self.flops_fn(seq_len if seq_len else self.max_length)

    def model_function(
        self,
        mode: str = "embed",
        dtype: Any = jnp.float32,
        weights_file: Optional[str] = None,
        seed: int = 0,
    ) -> "ModelFunction":
        """mode: 'embed' (masked-mean pooled embedding vector) —
        'features' is accepted as an alias so text models serve through
        the router's default mode unchanged."""
        if mode not in ("embed", "features"):
            raise ValueError(
                f"Unknown text-model mode {mode!r}; supported: embed "
                "(alias: features)"
            )
        return self.builder(
            self, mode=mode, dtype=dtype, weights_file=weights_file,
            seed=seed,
        )

    def supports_generate(self) -> bool:
        """Whether this entry can build the autoregressive generate
        surface (prefill + decode programs need the flax module's param
        tree exposed — a ``module_factory``)."""
        return self.module_factory is not None and self.backend == "flax"

    def kv_bytes_per_token(self) -> Optional[int]:
        """Per-token K/V cache footprint (bytes, float32 cache): the
        number the admission-time KV budget and ``/v1/models`` rows
        carry — 2 x layers x hidden x 4. None when the entry cannot
        generate."""
        if not self.supports_generate():
            return None
        c = self.module_factory().config
        return 2 * int(c.num_layers) * int(c.hidden_size) * 4

    def generate_function(
        self,
        dtype: Any = jnp.float32,
        weights_file: Optional[str] = None,
        seed: int = 0,
    ):
        """Build the ``mode='generate'`` surface: a
        :class:`~sparkdl_tpu.models.bert.BertGenerator` whose prefill /
        single-token decode programs share the EXACT param tree the
        embed path initializes (same module, same seed, same init
        geometry — the attention fn carries no parameters), so one
        registry entry serves both modes off one set of weights."""
        if not self.supports_generate():
            raise ValueError(
                f"{self.name!r} has no generate surface (needs a flax "
                "module_factory exposing its param tree)"
            )
        from sparkdl_tpu.models import bert as bert_mod

        module = self.module_factory()
        if weights_file:
            variables = _load_flax_weights(weights_file)
        else:
            variables = module.init(
                jax.random.PRNGKey(seed),
                jnp.zeros((1, min(self.max_length, 16)), jnp.int32),
            )
        return bert_mod.BertGenerator(
            module.config, variables, max_length=self.max_length
        )


def _bert_text_builder(size: str, attention: str = "flash"):
    """Builder over models/bert.py presets. ``attention``: 'flash' (the
    Pallas kernel on TPU, the dense einsum off it — chosen at build
    time and recorded as ``mf.attention``) or 'dense'.
    The returned ModelFunction takes a bare ids batch and derives its
    mask on device — serving payloads are one int array, not a tuple."""

    def build(
        spec: NamedTextModel, mode: str, dtype, weights_file, seed
    ) -> ModelFunction:
        from sparkdl_tpu.models import bert as bert_mod

        config = bert_mod._SIZES[size](dtype=dtype).config
        if attention == "dense":
            attention_fn = bert_mod.dense_attention
        else:
            attention_fn = bert_mod.flash_attention_for(config)
        module = bert_mod.BertEncoder(config, attention_fn=attention_fn)
        if weights_file:
            variables = _load_flax_weights(weights_file)
        else:
            variables = module.init(
                jax.random.PRNGKey(seed),
                jnp.zeros((1, min(spec.max_length, 16)), jnp.int32),
            )

        def fn(p, x):
            # Serving payloads are one bare int array; TextEmbedder
            # feeds (ids, mask) tuples — accept both. A missing mask is
            # derived ON DEVICE as ids != 0: pad id 0 never attends and
            # never pools, so a row zero-padded to ANY geometry embeds
            # identically — the invariant seq bucketing relies on.
            ids, mask = x if isinstance(x, (tuple, list)) else (x, None)
            # Shapes are static at trace time, so this raises on the
            # first call of an over-wide geometry instead of letting
            # JAX clamp the position gather into a silently wrong
            # embedding (same refusal as bert_model_function's guard).
            if ids.shape[1] > module.config.max_position_embeddings:
                raise ValueError(
                    f"sequence length {ids.shape[1]} exceeds "
                    f"{spec.name}'s position table "
                    f"({module.config.max_position_embeddings})"
                )
            if mask is None:
                mask = (ids != 0).astype(jnp.int32)
            return module.apply(p, ids, mask, pooled=True)

        return bert_mod.encoder_model_function(
            module, fn, variables, f"{spec.name}[{mode}]"
        )

    return build


def _bert_module_factory(size: str):
    def factory():
        from sparkdl_tpu.models import bert as bert_mod

        return bert_mod._SIZES[size](dtype=jnp.float32)

    return factory


def param_bytes(tree: Any) -> int:
    """Total bytes of a params pytree — the device-memory footprint the
    residency manager budgets against (``sparkdl_tpu/serving/``).

    Accepts a :class:`ModelFunction` (sizes its ``params``), a raw
    pytree, or an ``eval_shape`` result: any leaf exposing ``nbytes``
    counts exactly; leaves with only ``shape``/``dtype`` (ShapeDtypeStruct)
    count as ``prod(shape) * itemsize``; anything else counts zero."""
    if hasattr(tree, "params") and hasattr(tree, "fn"):
        tree = tree.params
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
        elif hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(
                np.prod(leaf.shape, dtype=np.int64)
                * np.dtype(leaf.dtype).itemsize
            )
    return total


def _load_flax_weights(
    weights_file: str, spec=None, module=None, allow_missing_head=True
):
    from sparkdl_tpu.models.keras_weights import is_keras_weights_file

    if is_keras_weights_file(weights_file):
        # Stock keras.applications weights convert onto the flax perf-path
        # architectures exactly (see keras_weights._CONVERTERS).
        from sparkdl_tpu.models import keras_weights

        if spec is None:
            raise ValueError(
                "Keras weight files need a registry spec for conversion"
            )
        return keras_weights.load_keras_weights(
            spec.name,
            weights_file,
            module=module,
            input_shape=spec.input_shape,
            num_classes=spec.num_classes,
            allow_missing_head=allow_missing_head,
        )
    if weights_file.endswith(".npz"):
        blob = dict(np.load(weights_file, allow_pickle=False))
        tree: Dict[str, Any] = {}
        for flat_key, arr in blob.items():
            node = tree
            parts = flat_key.split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = jnp.asarray(arr)
        return tree
    with open(weights_file, "rb") as f:
        return jax.tree_util.tree_map(jnp.asarray, pickle.load(f))


def save_flax_weights(params, path: str) -> None:
    """Save a flax params pytree as a flat .npz (keys joined by '/')."""
    flat = {}

    def visit(node, prefix):
        if isinstance(node, dict):
            for k, v in node.items():
                visit(v, f"{prefix}/{k}" if prefix else k)
        else:
            flat[prefix] = np.asarray(node)

    visit(params, "")
    np.savez(path, **flat)


def _flax_cnn_builder(module_factory: Callable[..., Any]):
    """Builder for flax CNNs exposing __call__(x, features_only=...)."""

    def build(
        spec: NamedImageModel, mode: str, dtype, weights_file, seed
    ) -> ModelFunction:
        module = module_factory(dtype=dtype, num_classes=spec.num_classes)
        if weights_file:
            # logits/probabilities need the classification head; catch a
            # headless (include_top=False) weights file at LOAD time with
            # the converter's purpose-built message, not at first apply.
            variables = _load_flax_weights(
                weights_file,
                spec,
                module,
                allow_missing_head=(mode == "features"),
            )
        else:
            variables = module.init(
                jax.random.PRNGKey(seed),
                jnp.zeros((1, spec.height, spec.width, 3), jnp.float32),
            )

        if mode == "features":
            fn = lambda p, x: module.apply(p, x, features_only=True)
        elif mode == "logits":
            fn = lambda p, x: module.apply(p, x)
        else:
            fn = lambda p, x: jax.nn.softmax(module.apply(p, x), axis=-1)
        return ModelFunction(
            fn,
            variables,
            input_shape=spec.input_shape,
            input_dtype=jnp.float32,
            name=f"{spec.name}[{mode}]",
        )

    return build


def keras_app_builder(app_name: str, feature_pooling: str = "avg"):
    """Builder over keras.applications (JAX backend, weights=None offline;
    pass weights_file=.keras/.h5 to load saved weights)."""

    def build(
        spec: NamedImageModel, mode: str, dtype, weights_file, seed
    ) -> ModelFunction:
        import keras

        app = getattr(keras.applications, app_name)
        keras.utils.set_random_seed(seed)
        if mode == "features":
            model = app(
                weights=None,
                include_top=False,
                pooling=feature_pooling,
                input_shape=spec.input_shape,
            )
        else:
            model = app(
                weights=None,
                include_top=True,
                classifier_activation="softmax"
                if mode == "probabilities"
                else None,
                input_shape=spec.input_shape,
            )
        if weights_file:
            model.load_weights(weights_file)
        mf = ModelIngest.from_keras(model, input_shape=spec.input_shape)
        return ModelFunction(
            mf.fn,
            mf.params,
            input_shape=spec.input_shape,
            input_dtype=jnp.float32,
            name=f"{spec.name}[{mode}]",
        )

    return build


def _resnet50_factory(dtype, num_classes):
    from sparkdl_tpu.models.resnet import ResNet50

    return ResNet50(dtype=dtype, num_classes=num_classes)


def _mobilenetv2_factory(dtype, num_classes):
    from sparkdl_tpu.models.mobilenet import MobileNetV2

    return MobileNetV2(dtype=dtype, num_classes=num_classes)


def _inceptionv3_factory(dtype, num_classes):
    from sparkdl_tpu.models.inception import InceptionV3

    return InceptionV3(dtype=dtype, num_classes=num_classes)


def _xception_factory(dtype, num_classes):
    from sparkdl_tpu.models.xception import Xception

    return Xception(dtype=dtype, num_classes=num_classes)


def _vgg16_factory(dtype, num_classes):
    from sparkdl_tpu.models.vgg import VGG16

    return VGG16(dtype=dtype, num_classes=num_classes)


def _vgg19_factory(dtype, num_classes):
    from sparkdl_tpu.models.vgg import VGG19

    return VGG19(dtype=dtype, num_classes=num_classes)


_REGISTRY: Dict[str, NamedImageModel] = {}


def _register(spec: NamedImageModel) -> None:
    _REGISTRY[spec.name.lower()] = spec


# Flax-native flagship(s). Geometries match the upstream registry so
# pipelines are drop-in compatible (ResNet50: 224², caffe-mode, 2048-d).
_register(
    NamedImageModel(
        "ResNet50", 224, 224, "caffe", 2048, "flax",
        _flax_cnn_builder(_resnet50_factory),
        module_factory=_resnet50_factory,
    )
)

# Flax-native (in-tree, models/inception.py) — the perf path for the
# BASELINE config[0] transfer-learning flagship.
_register(
    NamedImageModel(
        "InceptionV3", 299, 299, "tf", 2048, "flax",
        _flax_cnn_builder(_inceptionv3_factory),
        module_factory=_inceptionv3_factory,
    )
)
# Flax-native (in-tree, models/xception.py).
_register(
    NamedImageModel(
        "Xception", 299, 299, "tf", 2048, "flax",
        _flax_cnn_builder(_xception_factory),
        module_factory=_xception_factory,
    )
)
# Flax-native (in-tree, models/vgg.py) — with these, every upstream
# named model (SURVEY.md §3 #8b) runs flax-native on the TPU perf path.
_register(
    NamedImageModel(
        "VGG16", 224, 224, "caffe", 512, "flax",
        _flax_cnn_builder(_vgg16_factory),
        module_factory=_vgg16_factory,
    )
)
_register(
    NamedImageModel(
        "VGG19", 224, 224, "caffe", 512, "flax",
        _flax_cnn_builder(_vgg19_factory),
        module_factory=_vgg19_factory,
    )
)
# Flax-native (in-tree, models/mobilenet.py) — the perf path for the
# BASELINE config[2] SQL-UDF scoring model.
_register(
    NamedImageModel(
        "MobileNetV2", 224, 224, "tf", 1280, "flax",
        _flax_cnn_builder(_mobilenetv2_factory),
        module_factory=_mobilenetv2_factory,
    )
)

# -- text models (models/bert.py): the LLM-shaped serving workloads ----------
# BASELINE config[3]'s BERT-base embedder as a first-class registry
# entry; bert-tiny for tests/smokes; bert-long-2048 is the long-context
# geometry the ops/ flash kernel carries past one dense [L, L] score
# block per head (seq >= 2048 through POST /v1/predict).


def _bert_text_flops(size: str):
    def flops(seq_len: int) -> float:
        from sparkdl_tpu.utils.flops import bert_flops_per_example

        from sparkdl_tpu.models import bert as bert_mod

        c = bert_mod._SIZES[size](dtype=jnp.float32).config
        return bert_flops_per_example(
            seq_len,
            hidden=c.hidden_size,
            num_layers=c.num_layers,
            intermediate=c.intermediate_size,
        )

    return flops


_register(
    NamedTextModel(
        "bert-base", 512, 768, "flax", _bert_text_builder("base"),
        vocab_size=30522,
        module_factory=_bert_module_factory("base"),
        flops_fn=_bert_text_flops("base"),
    )
)
_register(
    NamedTextModel(
        "bert-tiny", 128, 128, "flax", _bert_text_builder("tiny"),
        vocab_size=1000,
        module_factory=_bert_module_factory("tiny"),
        flops_fn=_bert_text_flops("tiny"),
    )
)
_register(
    NamedTextModel(
        "bert-long-2048", 2048, 128, "flax", _bert_text_builder("long"),
        vocab_size=8192,
        module_factory=_bert_module_factory("long"),
        flops_fn=_bert_text_flops("long"),
    )
)


def _jamba_text_builder(size: str):
    """Builder over models/jamba.py presets: a causal hybrid of Mamba-1
    and attention layers whose ``embed`` is the final state of a row's
    last real token. The ModelFunction is marked ``weights_as_arguments``
    and reports ``attention`` ('flash' | 'dense') and ``scan``
    ('pallas' | 'jnp'), both chosen at build time."""

    def build(
        spec: NamedTextModel, mode: str, dtype, weights_file, seed
    ) -> ModelFunction:
        from sparkdl_tpu.models import jamba as jamba_mod

        return jamba_mod.jamba_model_function(
            size,
            dtype=dtype,
            seed=seed,
            weights_file=weights_file,
            name=f"{spec.name}[{mode}]",
        )

    return build


# AI21-Jamba2-3B at its published widths (no position table: the length
# is the model's declared context), and the same family at test size.
_register(
    NamedTextModel(
        "jamba2-3b", 262144, 2560, "jax", _jamba_text_builder("jamba2-3b"),
        vocab_size=65536,
    )
)
_register(
    NamedTextModel(
        "jamba-tiny", 4096, 64, "jax", _jamba_text_builder("jamba-tiny"),
        vocab_size=512,
    )
)


def _deepseek_v2_text_builder(size: str):
    """Builder over models/deepseek_v2.py presets: latent attention and
    shared plus routed experts, of which this chip holds a share;
    ``embed`` is the mean final state of a row's real tokens (and a
    column of counts, ``mf.row_counters``). The ModelFunction is marked
    ``weights_as_arguments`` and reports
    ``attention`` ('flash' | 'dense') and ``experts`` ('pallas' |
    'ragged_dot'), both chosen at build time."""

    def build(
        spec: NamedTextModel, mode: str, dtype, weights_file, seed
    ) -> ModelFunction:
        from sparkdl_tpu.models import deepseek_v2 as deepseek_mod

        return deepseek_mod.deepseek_v2_model_function(
            size,
            dtype=dtype,
            seed=seed,
            weights_file=weights_file,
            name=f"{spec.name}[{mode}]",
        )

    return build


# One chip's share of DeepSeek-V2 at its published widths (5 of 60
# layers, experts 0-39 of 160, a quarter of the vocabulary: the cut of
# benchmarks/configs/deepseek-v2.json), and the family at test size.
_register(
    NamedTextModel(
        "deepseek-v2", 163840, 5120, "jax",
        _deepseek_v2_text_builder("deepseek-v2"), vocab_size=25600,
    )
)
_register(
    NamedTextModel(
        "deepseek-v2-tiny", 4096, 64, "jax",
        _deepseek_v2_text_builder("deepseek-v2-tiny"), vocab_size=512,
    )
)


def _deepseek_v32_text_builder(size: str):
    """Builder over models/deepseek_v32.py presets: DeepSeek-V2's stack
    with a lightning indexer in every layer that selects each query's
    keys (``mf.indexer``: 'pallas' | 'jnp', chosen at build time like
    ``attention`` and ``experts``) and the sigmoid gate with a correction
    bias; two more row counters ride back with each row."""

    def build(
        spec: NamedTextModel, mode: str, dtype, weights_file, seed
    ) -> ModelFunction:
        from sparkdl_tpu.models import deepseek_v32 as deepseek_mod

        return deepseek_mod.deepseek_v32_model_function(
            size,
            dtype=dtype,
            seed=seed,
            weights_file=weights_file,
            name=f"{spec.name}[{mode}]",
        )

    return build


# One chip's share of DeepSeek-V3.2-Exp at its published widths (5 of 61
# layers, experts 0-7 of 256, an eighth of the vocabulary: the cut of
# benchmarks/configs/deepseek-v3.2-exp.json), and the family at test size.
_register(
    NamedTextModel(
        "deepseek-v3.2-exp", 163840, 7168, "jax",
        _deepseek_v32_text_builder("deepseek-v3.2-exp"), vocab_size=16160,
    )
)
_register(
    NamedTextModel(
        "deepseek-v3.2-exp-tiny", 4096, 64, "jax",
        _deepseek_v32_text_builder("deepseek-v3.2-exp-tiny"), vocab_size=512,
    )
)



def _afmoe_text_builder(size: str):
    """Builder over models/afmoe.py presets: sliding-window and full
    attention layers in one stack, gated GQA with QK-norm, and shared plus
    routed experts, every expert held; ``embed`` is the mean final state
    of a row's real tokens (and a column of counts, ``mf.row_counters``).
    The ModelFunction is marked ``weights_as_arguments`` and reports
    ``attention`` and ``window_attention`` ('flash' | 'dense') and
    ``experts`` ('pallas' | 'ragged_dot'), all chosen at build time."""

    def build(
        spec: NamedTextModel, mode: str, dtype, weights_file, seed
    ) -> ModelFunction:
        from sparkdl_tpu.models import afmoe

        return afmoe.afmoe_model_function(
            size,
            dtype=dtype,
            seed=seed,
            weights_file=weights_file,
            name=f"{spec.name}[{mode}]",
        )

    return build


# One chip's share of Trinity-Mini at its published widths (published
# layers 1-5 of 32, all 128 experts, the whole vocabulary: the cut of
# benchmarks/configs/trinity-mini.json), and the family at test size.
_register(
    NamedTextModel(
        "trinity-mini", 131072, 2048, "jax",
        _afmoe_text_builder("trinity-mini"), vocab_size=200192,
    )
)
_register(
    NamedTextModel(
        "trinity-mini-tiny", 4096, 64, "jax",
        _afmoe_text_builder("trinity-mini-tiny"), vocab_size=512,
    )
)


def _xing4_0_text_builder(size: str):
    """Builder over models/xing4_0.py presets: DeepSeek's latent attention
    and shared plus routed experts, every expert held, with a residual of
    four streams mixed by manifold-constrained hyper-connections; ``embed``
    is the mean final state of a row's real tokens (and a column of
    counts, ``mf.row_counters``). The ModelFunction is marked
    ``weights_as_arguments`` and reports ``attention`` ('flash' | 'dense'),
    ``experts`` ('pallas' | 'ragged_dot') and ``residual`` ('pallas' |
    'xla'), all chosen at build time."""

    def build(
        spec: NamedTextModel, mode: str, dtype, weights_file, seed
    ) -> ModelFunction:
        from sparkdl_tpu.models import xing4_0

        return xing4_0.xing4_0_model_function(
            size,
            dtype=dtype,
            seed=seed,
            weights_file=weights_file,
            name=f"{spec.name}[{mode}]",
        )

    return build


# Xing4.0-29B-A4B at its published widths (1 dense and 4 expert layers of
# 40, all 64 experts, the whole vocabulary: the cut of
# benchmarks/configs/xing4.0-29b-a4b.json), and the family at test size.
_register(
    NamedTextModel(
        "xing4.0-29b-a4b", 262144, 3584, "jax",
        _xing4_0_text_builder("xing4.0-29b-a4b"), vocab_size=131072,
    )
)
_register(
    NamedTextModel(
        "xing4.0-tiny", 4096, 64, "jax",
        _xing4_0_text_builder("xing4.0-tiny"), vocab_size=512,
    )
)

def get_model(name: str):
    """The registered spec for ``name`` — a :class:`NamedImageModel` or
    :class:`NamedTextModel`; both expose ``model_function(mode=...)``
    and ``param_bytes_estimate()``, which is all the serving residency
    loader needs (text and image models share one namespace)."""
    key = name.lower()
    if key not in _REGISTRY:
        raise ValueError(
            f"Unknown model {name!r}; supported: {supported_models()}"
        )
    return _REGISTRY[key]


def get_image_model(name: str) -> NamedImageModel:
    """`get_model` restricted to image specs — the resolver for the
    image-only surfaces (DeepImageFeaturizer, image UDFs), whose
    geometry/preprocessing fields text specs don't have. A text name
    fails HERE with a pointer to the right surface, not downstream
    with an AttributeError on ``spec.height``."""
    spec = get_model(name)
    if isinstance(spec, NamedTextModel):
        raise ValueError(
            f"{spec.name!r} is a text model; this API needs an image "
            "model — embed text with TextEmbedder or serve it in mode "
            f"'embed'. Image models: {supported_models(kind='image')}"
        )
    return spec


def register_model(spec) -> None:
    """Extend the registry (user-defined named image OR text models).
    Re-registering a name drops its cached memory estimate — the new
    spec may be a different architecture."""
    _ESTIMATE_CACHE.pop(spec.name, None)
    _register(spec)


def supported_models(
    with_memory: bool = False,
    kind: Optional[str] = None,
    estimates: bool = True,
) -> list:
    """Registered model names, sorted. ``with_memory=True`` returns one
    dict per model instead, carrying the geometry and the float32
    param-pytree device-memory estimate (``param_bytes`` /
    ``param_mb``; None where the backend needs a real build to size) —
    what the serving residency manager budgets against before loading.
    Text entries carry ``max_length`` where image entries carry
    ``input_shape``; ``kind='image'|'text'`` filters (the image-only
    surfaces advertise ``kind='image'`` so they never list a name they
    would then reject). ``estimates=False`` skips the per-spec
    eval_shape sizing (``param_bytes``/``param_mb`` come back None on
    a cold cache): the first full-estimate pass costs SECONDS of
    tracing per process, which a scrape-path caller — the worker's
    ``GET /v1/models``, pulled by the gateway's fleet loop on a short
    timeout — must never pay."""
    specs = [
        m
        for m in _REGISTRY.values()
        if kind is None
        or ("text" if isinstance(m, NamedTextModel) else "image") == kind
    ]
    if not with_memory:
        return sorted(m.name for m in specs)
    out = []
    for spec in sorted(specs, key=lambda m: m.name):
        est = (
            spec.param_bytes_estimate()
            if estimates
            else _ESTIMATE_CACHE.get(spec.name)
        )
        row = {
            "name": spec.name,
            "backend": spec.backend,
            "feature_dim": spec.feature_dim,
            "param_bytes": est,
            "param_mb": round(est / 2**20, 2) if est is not None else None,
        }
        if isinstance(spec, NamedTextModel):
            row["kind"] = "text"
            row["max_length"] = spec.max_length
            # generate capability is advertised, not probed: clients and
            # the fleet scraper read `modes` + `kv_bytes_per_token` off
            # GET /v1/models instead of risking a 400 to find out
            row["modes"] = (
                ["embed", "generate"]
                if spec.supports_generate()
                else ["embed"]
            )
            kv = spec.kv_bytes_per_token()
            if kv is not None:
                row["kv_bytes_per_token"] = kv
        else:
            row["kind"] = "image"
            row["input_shape"] = spec.input_shape
            row["modes"] = ["features", "logits", "probabilities"]
        out.append(row)
    return out
