"""Device time of the latent-attention flash kernel per thousand tokens
it attended over: the summed time of the kernel's events in the traced
window over the program's counter `mla.attention_tokens` (dispatched
rows x bucket edge x layers, pad rows and pad tokens included: what the
kernel ran over, not what was asked for)."""

from benchmarks.layer_metrics.mla_attention_roofline import kernel_seconds


def read(ctx):
    trace = ctx["trace"]
    tokens = ctx["counters"].get("mla.attention_tokens", 0)
    if trace is None or tokens <= 0:
        return None
    seconds = kernel_seconds(trace)
    if seconds <= 0:
        return None
    return 1e3 * seconds * ctx["chips"] / (tokens / 1e3)
