"""Analytic FLOPs and MFU accounting for bench records.

Reference analogue: the upstream benchmarks report raw images/sec only
(SURVEY.md §7); a throughput number alone cannot distinguish "the device
program is slow" from "the host→device link is slow".  Every bench record
therefore carries the analytic FLOPs of one work item and — on a known
accelerator — the implied model-FLOPs-utilization (MFU), so a plateau can
be attributed before anyone reaches for a profiler.

MACs below are the published forward-pass multiply-accumulate counts for
the registry geometries (torchvision/keras model cards); FLOPs = 2 x MACs.
``tests/test_flops.py`` cross-checks them against XLA's own
``cost_analysis()`` on the in-tree flax models so the constants cannot
drift from the programs we actually run.
"""

from __future__ import annotations

from typing import Optional

# Forward GMACs per image at the registry input geometry.
MODEL_GMACS = {
    "ResNet50": 4.09,  # 224x224
    "MobileNetV2": 0.314,  # 224x224
    "InceptionV3": 5.71,  # 299x299
    "Xception": 8.37,  # 299x299
    "VGG16": 15.47,  # 224x224
    "VGG19": 19.63,  # 224x224
}

# Dense bf16 peak FLOP/s per chip, keyed by substrings of
# ``jax.devices()[0].device_kind``. Order matters: more specific first.
_DEVICE_PEAKS = (
    ("v6", 918e12),  # Trillium / v6e
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5litepod", 197e12),
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
)


def model_flops_per_image(name: str, height: int = 0, width: int = 0) -> float:
    """Forward FLOPs for one image through a registry model.

    ``height``/``width``: actual input geometry if it differs from the
    registry default (conv FLOPs scale with spatial area — the train bench
    shrinks images on the CPU fallback)."""
    from sparkdl_tpu.models.registry import get_model

    flops = MODEL_GMACS[name] * 2e9
    if height and width:
        spec = get_model(name)
        flops *= (height * width) / float(spec.height * spec.width)
    return flops


def bert_flops_per_example(
    seq_len: int,
    hidden: int = 768,
    num_layers: int = 12,
    intermediate: int = 3072,
) -> float:
    """Forward FLOPs for one sequence through a BERT encoder.

    Per layer (MACs): QKV+output projections ``4*T*d^2``, attention
    scores+mix ``2*T^2*d``, FFN ``2*T*d*f``; embeddings/pooler omitted
    (<1%). FLOPs = 2 x MACs."""
    t, d, f = seq_len, hidden, intermediate
    macs_per_layer = 4 * t * d * d + 2 * t * t * d + 2 * t * d * f
    return 2.0 * num_layers * macs_per_layer


def bert_size_flops_per_example(size: str, seq_len: int) -> float:
    """FLOPs by the bench's BENCH_SIZE ladder (models/bert.py configs)."""
    if size == "tiny":
        return bert_flops_per_example(
            seq_len, hidden=128, num_layers=4, intermediate=256
        )
    return bert_flops_per_example(seq_len)


def local_device_kind() -> str:
    """``jax.devices()[0].device_kind`` without paying backend init at
    import time — the shared probe behind the live-MFU gauge and the
    bench's device tagging. A backend that fails to come up raises: an
    accelerator that is not there is an error, not an unknown kind."""
    import jax

    return jax.devices()[0].device_kind


def device_peak_flops(device_kind: str) -> Optional[float]:
    """Dense bf16 peak FLOP/s for one chip, or None when unknown (CPU,
    unrecognized TPU generation) — callers emit ``mfu: null`` then rather
    than a fictitious utilization."""
    kind = (device_kind or "").lower()
    if "tpu" not in kind:
        return None
    for sub, peak in _DEVICE_PEAKS:
        if sub in kind:
            return peak
    return None


def mfu(
    flops_per_item: float,
    items_per_sec: float,
    device_kind: str,
    devices: int = 1,
) -> Optional[float]:
    """Model-FLOPs-utilization in [0, 1]; None when the device peak is
    unknown (CPU, unrecognized TPU generation) — callers bank
    ``mfu: null`` then rather than a fictitious utilization.

    ``items_per_sec`` is the ACHIEVED rate over ``devices`` chips:
    ``flops_per_item * items_per_sec / (peak * devices)``. Pass a
    per-chip rate with the default ``devices=1`` (the per-chip bench
    metrics), or an aggregate rate with the mesh width (the serving
    bench's rows/sec over a ``mesh_width`` fan-out) — the two forms
    are algebraically identical."""
    peak = device_peak_flops(device_kind)
    if not peak or not items_per_sec:
        return None
    return flops_per_item * items_per_sec / (peak * max(1, devices))
