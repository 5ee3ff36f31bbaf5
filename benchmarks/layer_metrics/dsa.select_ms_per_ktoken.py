"""Device time of choosing each query's keys per thousand tokens the
indexer ran over: the summed time of the index-scores kernel's events
and of the selection kernel's (`dsa_select`: the search for each query's
`index_topk`-th score and the selection it writes), each by its own name
in the traced window, over the program's counter `dsa.index_tokens`
(dispatched rows x bucket edge x layers, for the buckets that run the
indexer; pad tokens too). Says the two parts."""

from benchmarks.layer_metrics.dsa_indexer_roofline import own_kernel_seconds


def read(ctx):
    trace = ctx["trace"]
    tokens = ctx["counters"].get("dsa.index_tokens", 0)
    if trace is None or tokens <= 0:
        return None
    scores = own_kernel_seconds(trace, "dsa_index_scores")
    select = own_kernel_seconds(trace, "dsa_select")
    if scores + select <= 0:
        return None
    return {
        "value": 1e3 * (scores + select) * ctx["chips"] / (tokens / 1e3),
        "scores_s": scores,
        "select_s": select,
    }
