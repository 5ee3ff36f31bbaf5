"""ModelFunction — the framework's unit of executable model.

Reference analogue: ``GraphFunction`` / frozen TF GraphDefs produced by
``strip_and_freeze_until`` (python/sparkdl/graph/builder.py + utils.py,
SURVEY.md §3 #3/#6). The reference froze TF variables into graph constants
and shipped serialized GraphDefs to executors. The TPU-native equivalent is
a **pure function + params pytree**:

    fn(params, batch) -> output          # traceable, jit-compatible

"Freezing" is closing over params and jitting; "serializing the frozen
graph" is ``jax.export`` StableHLO bytes (hardware-portable, version-stable)
plus the params saved via orbax. Composition of graph pieces (converter ∘
model ∘ flattener) is plain function composition, which XLA then fuses into
one program — the fusion the reference had to assemble manually by splicing
GraphDefs.
"""

from __future__ import annotations

import os
import pickle
import warnings
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.runtime import knobs


def input_donation_enabled() -> bool:
    """SPARKDL_DONATE_INPUT gates flat-input buffer donation in
    ``jitted_flat`` / ``jitted_flat_parts`` (default on; 0/off = the
    plain A/B arm)."""
    return knobs.get_flag("SPARKDL_DONATE_INPUT")


def _donation_supported() -> bool:
    """XLA implements input buffer donation on TPU/GPU; the CPU client
    ignores it (with a warning), AND the CPU client may alias a numpy
    batch zero-copy — donating an aliased host buffer the feeder's ring
    is about to refill would be memory corruption, so CPU stays on the
    plain build. Tests monkeypatch this to exercise the donated build
    shape on CPU (where jax safely ignores the donation)."""
    return jax.default_backend() in ("tpu", "gpu", "cuda", "rocm")


def input_donation_engaged() -> bool:
    """Whether flat-input donation actually engages right now (gate on
    AND a backend that implements it) — the single source bench.py
    records the ``donation`` arm from, per house style (record
    engagement, never a knob the runtime silently ignored)."""
    return input_donation_enabled() and _donation_supported()


_donation_warning_filtered = False


def _donate_kwargs(donate: bool, n_args: int = 1) -> dict:
    global _donation_warning_filtered
    if not donate:
        return {}
    # The flat input is donated to the program. When input and compute
    # dtypes match, XLA aliases it straight into an output/intermediate;
    # the uint8 image case is donatable too because the uint8->f32 cast
    # is FUSED into the program (the converter piece runs first), so the
    # staged uint8 buffer frees at its last use inside the program
    # instead of surviving all of it — that is what lets a device
    # staging slot turn over without a second allocation. A donation
    # XLA can't use is released early and warned about; filter that one
    # message rather than spamming it once per geometry. Installed ONCE:
    # warnings.filters is a process-global list, and re-installing per
    # donated build would pile up duplicates and invalidate the warning
    # registry every time.
    if not _donation_warning_filtered:
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        _donation_warning_filtered = True
    return {"donate_argnums": tuple(range(n_args))}


def param_placement_engaged() -> bool:
    """Whether chunked param placement CAN engage right now: exactly one
    local device, it is a TPU, and chunking isn't disabled
    (SPARKDL_H2D_CHUNK_MB=0). The single source for this gate —
    ModelFunction._capture_params enforces it and bench.py records
    engagement from it, so an A/B record can never claim the treatment
    arm while the baseline ran."""
    devs = jax.devices()
    if len(devs) != 1 or devs[0].platform != "tpu":
        return False
    return knobs.get_int("SPARKDL_H2D_CHUNK_MB") > 0


def _flat_unpacker(shape: Tuple[int, ...], layout: str):
    """flat 1-D buffer -> logical NHWC batch, shared by jitted_flat and
    jitted_flat_parts so the two feed paths can never diverge.

    ``nchw`` means the flat buffer holds CHANNEL-MAJOR pixels: reshape
    to (B, C, H, W) then transpose — see jitted_flat's docstring for
    why that ordering keeps every device intermediate small."""
    if layout == "nchw":
        if len(shape) != 4:
            raise ValueError(
                f"layout='nchw' needs a rank-4 NHWC batch_shape, "
                f"got {shape}"
            )
        b, h, w, c = shape

        def unpack(flat):
            x = jnp.reshape(flat, (b, c, h, w))
            return jnp.transpose(x, (0, 2, 3, 1))

    elif layout == "nhwc":

        def unpack(flat):
            return jnp.reshape(flat, shape)

    else:
        raise ValueError(f"Unknown flat layout {layout!r}")
    return unpack


@dataclass
class ModelFunction:
    """A pure model function with its parameters.

    Attributes:
        fn: pure callable ``fn(params, x) -> y``; must be jax-traceable.
        params: pytree of arrays (may be None for param-less pieces).
        input_shape: per-example input shape (no batch dim), if known.
        input_dtype: expected input dtype, if known.
        name: diagnostic name.
    """

    fn: Callable[[Any, Any], Any]
    params: Any = None
    input_shape: Optional[Tuple[int, ...]] = None
    input_dtype: Any = None
    name: str = "model_fn"
    _jitted: Any = field(default=None, repr=False, compare=False)
    #: Set by a model's builder (as ``attention`` and ``vocab_size`` are):
    #: ``jitted()`` compiles ``fn`` itself and hands it the parameter
    #: tree at every call, placed once on each execution device, instead
    #: of closing over it. A compiled shape then holds no copy of the
    #: weights: the only way to run a model whose weights are a large
    #: share of the device's memory at more than one shape.
    weights_as_arguments: bool = field(default=False, repr=False, compare=False)

    # -- execution ------------------------------------------------------------

    def __call__(self, x):
        return self.fn(self.params, x)

    def _capture_params(self):
        """Params as the jit closures will capture them.

        Default (``closure``): the raw pytree — XLA transfers each leaf
        whole on first execution. ``SPARKDL_PARAM_PLACEMENT=chunked``
        pre-places the tree on the single local TPU device before the
        first batch ships, every transfer at most SPARKDL_H2D_CHUNK_MB
        (runtime/transfer.py). Opt-in; whether it helps is not measured
        on the attached chip."""
        placement = knobs.get_str("SPARKDL_PARAM_PLACEMENT")
        if placement not in ("", "closure", "chunked"):
            raise ValueError(
                f"SPARKDL_PARAM_PLACEMENT={placement!r}: expected "
                "'closure' (default) or 'chunked'"
            )
        if placement != "chunked" or not param_placement_engaged():
            return self.params
        cache = self.__dict__.setdefault("_placed_params", {})
        key = self._placement_key()
        if key not in cache:
            from ..obs import span
            from ..runtime.transfer import put_pytree_chunked

            chunk_mb = knobs.get_int("SPARKDL_H2D_CHUNK_MB")
            with span(
                "param_capture",
                model=self.name,
                placement=placement,
                chunk_mb=chunk_mb,
            ):
                cache[key] = put_pytree_chunked(
                    self.params, jax.devices()[0], chunk_mb << 20
                )
        return cache[key]

    @staticmethod
    def _placement_key() -> tuple:
        """Param-capture environment: jit caches must key on it, or
        toggling SPARKDL_PARAM_PLACEMENT / SPARKDL_H2D_CHUNK_MB
        mid-session silently reuses executables built with the old
        capture (the transformer-level dispatch_env_key gives the same
        guarantee one level up)."""
        return (
            knobs.get_raw("SPARKDL_PARAM_PLACEMENT"),
            knobs.get_raw("SPARKDL_H2D_CHUNK_MB"),
        )

    def jitted(self) -> Callable[[Any], Any]:
        """Jit with params captured as constants — the 'frozen' form. The
        params pytree is closed over (transferred to each execution device
        once, when that device's executable is built); every batch
        thereafter only ships the batch. A model marked
        ``weights_as_arguments`` gets :meth:`_jitted_with_arguments`
        instead. Either way one callable per model and placement
        environment: the shared feeder keys its streams by it."""
        cache = self.__dict__.setdefault("_jitted_cache", {})
        key = self._placement_key()
        if key not in cache:
            from ..runtime import compile_cache

            compile_cache.note_build("jitted", self.name, key)
            if self.weights_as_arguments:
                cache[key] = self._jitted_with_arguments()
            else:
                fn, params = self.fn, self._capture_params()
                cache[key] = jax.jit(lambda x: fn(params, x))
        return cache[key]

    def _jitted_with_arguments(self) -> Callable[[Any], Any]:
        """``call(x)`` = ``jit(fn)(placed params, x)``. The tree is placed
        on a device the first time ``call.place(device)`` names it (the
        ``param_place`` span; ``model_device_fn`` names every device it
        will dispatch to, before the first batch) and never donated. A
        batch runs on the device its arrays are committed to, else on
        the first device placed."""
        import threading

        from ..obs import span
        from ..runtime.transfer import put_pytree_chunked

        program = jax.jit(self.fn)
        placed: dict = {}
        lock = threading.Lock()

        def place(device):
            with lock:
                if device not in placed:
                    leaves = jax.tree_util.tree_leaves(self.params)
                    chunk = knobs.get_int("SPARKDL_H2D_CHUNK_MB") << 20
                    with span(
                        "param_place",
                        model=self.name,
                        device=str(device),
                        bytes=sum(int(a.nbytes) for a in leaves),
                    ):
                        tree = (
                            put_pytree_chunked(self.params, device, chunk)
                            if chunk > 0
                            else jax.device_put(self.params, device)
                        )
                        placed[device] = jax.block_until_ready(tree)
                return placed[device]

        def call(x):
            device = None
            for leaf in jax.tree_util.tree_leaves(x):
                if isinstance(leaf, jax.Array) and leaf.committed:
                    (device,) = leaf.devices()
                    break
            if device is None:
                device = next(iter(placed), None) or jax.devices()[0]
            return program(place(device), x)

        call.place = place
        call.program = program  # the one jit, for tests that count shapes
        return call

    def frozen(self) -> Callable[[Any], Any]:
        fn, params = self.fn, self.params
        return lambda x: fn(params, x)

    def jitted_flat(
        self,
        batch_shape: Tuple[int, ...],
        layout: str = "nhwc",
        donate: Optional[bool] = None,
    ) -> Callable[[Any], Any]:
        """Jit a variant whose argument is the batch's FLAT 1-D buffer,
        unpacked to ``batch_shape`` inside the program.

        TPU feed-path details:

        - A 1-D buffer has one device layout, so host->HBM needs no
          host-side relayout; an N-D array (especially uint8 NHWC with a
          3-wide minor dim) can be assigned a tiled device layout that
          the host must produce first.
        - ``layout='nchw'``: the flat buffer holds CHANNEL-MAJOR pixels and
          the program reshapes to (B, C, H, W) then transposes to NHWC.
          Unpacking flat->NHWC directly materializes an (8,128)-tiled
          array whose 3-wide minor dim pads to 128 lanes — a 42x memory
          blowup (3.3GB for a 128x224x224x3 f32 batch). Channel-major
          keeps W minor (pads 224->256, 1.14x).

        What either costs on the attached chip is not measured.

        ``batch_shape`` is always the logical NHWC shape; ``layout`` only
        changes how the flat buffer is packed. One compiled program per
        (batch_shape, layout, donation arm), cached.

        ``donate``: donate the flat input buffer to the program
        (default: :func:`input_donation_engaged` — on wherever the
        backend implements donation). The donated buffer — in the
        staged-feed path, a device staging slot — is aliased into the
        program's outputs/intermediates (dtypes matching) or freed at
        its last use inside the program (the fused uint8->f32 cast
        consumes it first), so staging slots turn over without a second
        allocation. Pass ``donate=False`` when the SAME input array is
        dispatched repeatedly (the resident bench loop) — a donated
        array is dead after the call."""
        cache = self.__dict__.setdefault("_jitted_flat_cache", {})
        if donate is None:
            donate = input_donation_engaged()
        key = (tuple(batch_shape), layout, bool(donate), self._placement_key())
        if key not in cache:
            from ..runtime import compile_cache

            compile_cache.note_build("jitted_flat", self.name, key)
            fn, params = self.fn, self._capture_params()
            shape = tuple(batch_shape)
            unpack = _flat_unpacker(shape, layout)
            cache[key] = jax.jit(
                lambda flat: fn(params, unpack(flat)),
                **_donate_kwargs(donate),
            )
        return cache[key]

    def jitted_flat_parts(
        self,
        batch_shape: Tuple[int, ...],
        n_parts: int,
        part_elems: int,
        layout: str = "nhwc",
    ) -> Callable[..., Any]:
        """Like ``jitted_flat`` but the flat buffer arrives as ``n_parts``
        equal-length chunks, concatenated INSIDE the compiled program.

        Folding the concatenate into the model program makes a chunked
        batch cost ONE put call (list form) + ONE dispatch — or, when
        the chunks are passed as numpy views, a single dispatch that
        transfers every argument itself — instead of N_chunks puts plus
        a separate on-device ``concatenate`` dispatch plus the model
        dispatch. Whether fewer client calls pays on the attached chip
        is not measured.

        Chunks must all be ``part_elems`` long (pad the last one); the
        program slices the concatenation back to the true element count
        before unpacking, so padding never reaches the model. Every part
        is donated under the same policy as ``jitted_flat`` — each chunk
        is consumed by the in-program concatenate, so donation frees the
        per-chunk buffers as the program starts instead of holding
        N_parts staging allocations to the end."""
        cache = self.__dict__.setdefault("_jitted_parts_cache", {})
        donate = input_donation_engaged()
        key = (
            tuple(batch_shape),
            int(n_parts),
            int(part_elems),
            layout,
            bool(donate),
            self._placement_key(),
        )
        if key not in cache:
            from ..runtime import compile_cache

            compile_cache.note_build("jitted_flat_parts", self.name, key)
            fn, params = self.fn, self._capture_params()
            shape = tuple(batch_shape)
            total = int(np.prod(shape))
            unpack = _flat_unpacker(shape, layout)
            cache[key] = jax.jit(
                lambda *parts: fn(
                    params, unpack(jnp.concatenate(parts)[:total])
                ),
                **_donate_kwargs(donate, n_args=int(n_parts)),
            )
        return cache[key]

    # -- composition ----------------------------------------------------------

    def and_then(self, g: "ModelFunction | Callable") -> "ModelFunction":
        """self ∘-then g: output of self feeds g. Graph-splicing analogue."""
        g_mf = g if isinstance(g, ModelFunction) else ModelFunction(
            lambda p, x, _g=g: _g(x), None, name=getattr(g, "__name__", "fn")
        )
        f_fn, g_fn = self.fn, g_mf.fn

        def composed(params, x):
            fp, gp = params
            return g_fn(gp, f_fn(fp, x))

        return ModelFunction(
            fn=composed,
            params=(self.params, g_mf.params),
            input_shape=self.input_shape,
            input_dtype=self.input_dtype,
            name=f"{self.name}>>{g_mf.name}",
        )

    def before(self, pre: "ModelFunction | Callable") -> "ModelFunction":
        pre_mf = pre if isinstance(pre, ModelFunction) else ModelFunction(
            lambda p, x, _f=pre: _f(x), None, name=getattr(pre, "__name__", "fn")
        )
        return pre_mf.and_then(self)

    def with_params(self, params) -> "ModelFunction":
        return replace(self, params=params, _jitted=None)

    # -- example inputs / signature -------------------------------------------

    def example_input(self, batch_size: int = 1):
        if self.input_shape is None:
            raise ValueError(
                f"ModelFunction {self.name!r} has no input_shape recorded"
            )
        dtype = self.input_dtype or jnp.float32
        return jnp.zeros((batch_size, *self.input_shape), dtype=dtype)

    # -- serialization --------------------------------------------------------
    # Two artifacts, mirroring frozen-GraphDef + weights-on-disk:
    #   <path>/program.stablehlo : jax.export serialization of the frozen fn
    #   <path>/params.pkl        : params pytree (numpy), for re-freezing /
    #                              fine-tuning on load

    def export(self, path: str, batch_size: Optional[int] = None) -> None:
        """Serialize the frozen fn. The batch dimension is exported
        SYMBOLIC by default (shape polymorphism), so the loaded program
        accepts any batch size; pass an explicit ``batch_size`` to pin it
        (some programs don't support polymorphic shapes)."""
        from jax import export as jax_export

        os.makedirs(path, exist_ok=True)
        if batch_size is None:
            (b,) = jax_export.symbolic_shape("b")
            lead = b
        else:
            lead = batch_size
        x_spec = jax.ShapeDtypeStruct(
            (lead, *(self.input_shape or ())),
            self.input_dtype or jnp.float32,
        )
        exported = jax_export.export(jax.jit(self.frozen()))(x_spec)
        with open(os.path.join(path, "program.stablehlo"), "wb") as f:
            f.write(exported.serialize())
        host_params = jax.tree_util.tree_map(np.asarray, self.params)
        with open(os.path.join(path, "params.pkl"), "wb") as f:
            pickle.dump(
                {
                    "params": host_params,
                    "input_shape": self.input_shape,
                    "input_dtype": str(np.dtype(self.input_dtype))
                    if self.input_dtype
                    else None,
                    "name": self.name,
                },
                f,
            )

    @staticmethod
    def load(path: str) -> "ModelFunction":
        """Load an exported ModelFunction. The StableHLO program is the
        executable unit (params already baked in as constants)."""
        from jax import export as jax_export

        with open(os.path.join(path, "program.stablehlo"), "rb") as f:
            exported = jax_export.deserialize(f.read())
        with open(os.path.join(path, "params.pkl"), "rb") as f:
            meta = pickle.load(f)

        def fn(params, x):
            return exported.call(x)

        mf = ModelFunction(
            fn=fn,
            params=None,
            input_shape=tuple(meta["input_shape"]) if meta["input_shape"] else None,
            input_dtype=np.dtype(meta["input_dtype"])
            if meta["input_dtype"]
            else None,
            name=meta.get("name", "loaded"),
        )
        mf.raw_params = meta["params"]  # available for re-freezing/fine-tune
        return mf


def piece(fn: Callable[[Any], Any], name: str = "piece") -> ModelFunction:
    """Wrap a param-less traceable function as a ModelFunction piece."""
    return ModelFunction(lambda p, x: fn(x), None, name=name)
