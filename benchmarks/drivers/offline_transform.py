"""Offline batch jobs, closed loop: one DataFrame built in set-up and
transformed again, `transform(df).collect()`, one job after another until
the window's seconds have passed. Rows per second over the whole window
is what a batch user pays for.

The entry is the program's own transformer, named in the configuration's
`entry`; weights are the benchmark's, written once as the `.npz` weights
file that the entry loads. After the window, the rows that the timed jobs
returned at the sampled positions are compared with the plain reference's
answers for the same inputs (`check`).
"""

from __future__ import annotations

import copy
import gc
import importlib
import os
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from benchmarks import compare, traffic_gen

OUT_COL = "out"


@dataclass
class Job:
    start_s: float
    end_s: float
    rows: int


@dataclass
class Window:
    jobs: list = field(default_factory=list)
    #: per job, the answers at the sampled positions (None for a null)
    samples: list = field(default_factory=list)
    attempted: int = 0
    #: rows whose null-ness or count is not what was sent
    failed: int = 0

    @property
    def rows(self) -> int:
        return sum(j.rows for j in self.jobs)

    @property
    def seconds(self) -> float:
        return self.jobs[-1].end_s - self.jobs[0].start_s

    def end_to_end(self) -> dict:
        return {"rows_per_s": self.rows / self.seconds}


@dataclass
class State:
    cell: object
    transformer: object
    df: object
    #: the head of every partition: what the warm job transforms
    warm_df: object
    n_rows: int
    null_at: frozenset
    sample_at: list
    sample_inputs: list
    weights: dict
    #: stored row -> the tokens of its own that the program will find in
    #: it, where the entry's kind can say (`entries/<kind>.py:row_length`),
    #: and then the job's rows as stored, for `work` to measure
    row_length: object = None
    rows: list = None


def _reference(cell):
    return importlib.import_module(
        f"benchmarks.reference.{cell.config['family']}"
    )


def weights_file(cell) -> tuple:
    """The benchmark's weights for this configuration: (path, weights).
    The file is written once in a checkout and found again by later runs;
    it is the same from run to run because the program compiles its
    weights into its executables, and only an unchanged program is found
    in the compile cache."""
    seed = cell.config["weights_seed"]
    weights = _reference(cell).make_weights(cell.config, seed)
    directory = os.path.join(cell.work_dir, "weights")
    path = os.path.join(directory, f"{cell.config['name']}-{seed}.npz")
    if not os.path.exists(path):
        os.makedirs(directory, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp.npz"
        np.savez(tmp, **weights)
        os.replace(tmp, path)
    return path, weights


def build_entry(cell) -> tuple:
    """(the program's transformer, the benchmark's weights it loaded). The
    builder is `entries/<kind>.py`, by the configuration's `entry.kind`."""
    path, weights = weights_file(cell)
    return _entry_kind(cell).build(cell, path, OUT_COL), weights


def _entry_kind(cell):
    return importlib.import_module(
        f"benchmarks.entries.{cell.config['entry']['kind']}"
    )


def load_job(cell, transformer, weights) -> State:
    """The job's DataFrame from the cell's seed, and which of its rows the
    check will look at: the ends of every partition and a draw from the
    seed."""
    traffic = cell.traffic
    row_length = None
    if transformer is not None:
        tells = getattr(_entry_kind(cell), "row_length", None)
        row_length = tells and tells(cell, transformer)
    data, parts = traffic["data"], traffic["partitions"]
    n = data["rows"]
    with jax.profiler.TraceAnnotation("bench:datagen"):
        rng = np.random.default_rng([cell.seed, 0x5A3B1E])
        edges = {0, n - 1}
        for p in range(1, parts):
            edges.update((p * n // parts - 1, p * n // parts))
        draw = rng.choice(n, size=min(n, traffic["check_rows"]), replace=False)
        sample_at = sorted(edges | set(draw.tolist()))
        wanted, raw_at = set(sample_at), {}
        stored, null_at = [], set()
        # a kind whose DataFrame holds rows in another form says how
        store = getattr(traffic_gen.kind_of(data), "stored", lambda row: row)
        for i, row in enumerate(traffic_gen.make_rows(data, cell.seed)):
            if row is None:
                null_at.add(i)
                stored.append(None)
                continue
            if i in wanted:
                raw_at[i] = copy.copy(row)
            stored.append(store(row))
        from sparkdl_tpu.dataframe import DataFrame

        df = DataFrame.fromColumns({"in": stored}, numPartitions=parts)
        # the warm job's rows: the whole job, or where the traffic names
        # `warm_rows`, that many, taken from the head of every partition
        warm_df = df
        if "warm_rows" in traffic:
            head = traffic["warm_rows"] // parts
            warm = [
                row
                for p in range(parts)
                for row in stored[p * n // parts :][:head]
            ]
            warm_df = DataFrame.fromColumns({"in": warm}, numPartitions=parts)
    return State(
        cell=cell,
        transformer=transformer,
        df=df,
        warm_df=warm_df,
        n_rows=n,
        null_at=frozenset(null_at),
        sample_at=sample_at,
        sample_inputs=[raw_at.get(i) for i in sample_at],
        weights=weights,
        row_length=row_length,
        rows=stored if row_length is not None else None,
    )


def setup(cell) -> State:
    state = load_job(cell, *build_entry(cell))
    # one warm job, in the job's partitions at the job's batch size: every
    # shape the window will use is compiled (or fetched) here, every thread
    # and buffer exists. A traffic mix whose job is long warms on the head
    # of every partition (`warm_rows`), enough rows to fill a batch of
    # every shape. What it returns is not judged: the timed jobs' answers are
    with jax.profiler.TraceAnnotation("bench:warm"):
        state.transformer.transform(state.warm_df).collect()
    return state


def _job(state: State, window: Window) -> None:
    with jax.profiler.TraceAnnotation("bench:job"):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:transform"):
            out = state.transformer.transform(state.df)
        with jax.profiler.TraceAnnotation("bench:collect"):
            rows = out.collect()
        t1 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench:sample"):
        window.attempted += state.n_rows
        answers = [r[OUT_COL] for r in rows]
        bad = abs(len(answers) - state.n_rows)
        for i, a in enumerate(answers[: state.n_rows]):
            bad += (a is None) != (i in state.null_at)
        window.failed += bad
        window.jobs.append(Job(t0, t1, len(answers) - bad))
        window.samples.append(
            [
                None if i >= len(answers) or answers[i] is None
                else np.array(answers[i], np.float32)
                for i in state.sample_at
            ]
        )


def window(state: State, seconds: float) -> Window:
    w = Window()
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline:
        _job(state, w)
    return w


def counters(state: State) -> dict:
    from sparkdl_tpu.utils.metrics import metrics

    return dict(metrics.scalar_snapshot()["counters"])


def real_lengths(state: State, delta: dict, jobs: int, by_length: dict) -> dict:
    """The real lengths of the rows the window completed, by the edge
    each was dispatched at: `{"lengths_by_edge": {edge: {real: rows}}}`.
    From the job's own rows as the benchmark made them, measured by the
    entry's `row_length`, times the whole jobs completed; a row goes to
    the least edge the program counted that holds it, the top one cut.
    Held against what the program counted: the real tokens have to sum
    to the delta of `text.tokens` and each edge's rows to that of
    `text.bucket_rows.<edge>`. Where they do not, no real length is
    given and `pairs_unknown` says why: the counts then have no pair
    term (`benchmarks/counts/__init__.py`). `lengths_check` is the
    comparison itself, for the result line."""
    edges = sorted(int(e) for e in by_length)
    if not edges:
        return _pairs_unknown("the program counted no text.bucket_rows.<edge>")
    try:
        lengths = [state.row_length(r) for r in state.rows if r is not None]
    except Exception as e:  # a silent metric, and the run still prints its line
        return _pairs_unknown(f"row_length raised {e!r}")
    mine = {e: {} for e in edges}
    for n in lengths:
        edge = next((e for e in edges if n <= e), edges[-1])
        n = min(n, edge)
        mine[edge][n] = mine[edge].get(n, 0) + jobs
    tokens = sum(n * rows for of in mine.values() for n, rows in of.items())
    check = {
        "jobs": jobs,
        "tokens": [tokens, int(delta.get("text.tokens", 0))],
        "rows_by_edge": {
            str(e): [sum(mine[e].values()), by_length[str(e)]] for e in edges
        },
    }
    if any(a != b for a, b in [check["tokens"], *check["rows_by_edge"].values()]):
        return _pairs_unknown(
            "the job's rows as made do not square with the program's counters "
            "([mine, counted]): text.tokens %s, text.bucket_rows %s"
            % (check["tokens"], check["rows_by_edge"]),
            **check,
        )
    return {
        "lengths_by_edge": {str(e): mine[e] for e in edges},
        "lengths_check": dict(check, ok=True),
    }


def _pairs_unknown(why: str, **check) -> dict:
    return {"pairs_unknown": why, "lengths_check": dict(check, ok=False, why=why)}


def work(state: State, delta: dict, w: Window) -> dict:
    """What the window completed, as the counts modules take it: the rows
    by the edge they were dispatched at (the program's counters), the
    routed slots that fell on held experts where the program measures
    them (counter `moe.slots_held`), and for an entry that can tell a
    row's own length the real lengths (`real_lengths`)."""
    by_length = {
        name.rsplit(".", 1)[1]: int(v)
        for name, v in delta.items()
        if name.startswith("text.bucket_rows.") and v
    }
    out = {"rows": w.rows, "rows_by_length": by_length}
    if delta.get("moe.slots_held", 0) > 0:
        out["slots_held"] = int(delta["moe.slots_held"])
    if state.row_length is not None:
        out.update(real_lengths(state, delta, len(w.jobs), by_length))
    return out


def release(state: State) -> None:
    """Drop what the program holds on the device, so that the reference
    has the chip to itself."""
    from sparkdl_tpu.runtime.feeder import shutdown_feeders

    shutdown_feeders()
    state.transformer = None
    state.df = state.warm_df = None
    gc.collect()
    jax.clear_caches()
    gc.collect()


def sampled_answers(state: State, w: Window, shape) -> list:
    """Per timed job, the answers at the sampled positions that hold an
    input, stacked; NaN where an answer is missing or of another shape
    (the window has counted a missing one as misplaced already)."""
    live = [k for k, x in enumerate(state.sample_inputs) if x is not None]
    return [
        np.stack(
            [
                answers[k]
                if answers[k] is not None and answers[k].shape == shape
                else np.full(shape, np.nan, np.float32)
                for k in live
            ]
        )
        for answers in w.samples
    ]


def sample_errors(answers: list, ref: np.ndarray) -> np.ndarray:
    """Errors of every sampled answer of every timed job, as
    `compare.row_errors` measures them; an answer that is missing where
    one was due counts as another row's."""
    return np.stack(
        [np.nan_to_num(compare.row_errors(got, ref), nan=2.0) for got in answers]
    )


def reference_answers(state: State, precision: str = "reference") -> np.ndarray:
    """The plain reference's answers for the sampled inputs, at the
    precision the configuration states. A CPU has no MXU and computes the
    program's float32 products in float32, so a rehearsal is held against
    `highest`."""
    if precision == "reference" and state.cell.rehearsal:
        precision = "highest"
    inputs = [x for x in state.sample_inputs if x is not None]
    return _reference(state.cell).outputs(
        state.cell.config, state.weights, inputs, precision=precision
    )


def control_numbers(state: State) -> dict:
    """The control: the reference in the nearest precision under the one
    the configuration states, put in the program's place."""
    module = _reference(state.cell)
    lower = module.CONTROL_PRECISION[state.cell.config["compute_dtype"]]
    ref = reference_answers(state)
    low = reference_answers(state, lower)
    errs = compare.row_errors(low, ref)
    truth = reference_answers(state, "highest")
    return {
        "precision": lower,
        "rows_mismatched": compare.rows_mismatched(low, ref),
        **compare.error_numbers(errs),
        # the look: how far the reference at the stated precision lies
        # from every product in float32
        "reference_vs_highest_max": float(compare.row_errors(ref, truth).max()),
    }


def check(cell, state: State, w: Window, look: bool = False) -> dict:
    """The numbers that decide `correct`. `look` adds, for the record of
    how a limit was set, the same errors against every product in float32."""
    ref = reference_answers(state)
    answers = sampled_answers(state, w, ref[0].shape)
    errs = sample_errors(answers, ref)
    extra = {}
    if look:
        truth = sample_errors(answers, reference_answers(state, "highest"))
        extra = {
            "vs_highest_max": float(truth.max()),
            "vs_highest_median": float(np.median(truth)),
        }
    if look or "rows_mismatched" in cell.limits:
        # only a cell whose reference rows lie far apart holds this to 0
        extra["rows_mismatched"] = sum(
            compare.rows_mismatched(np.nan_to_num(got), ref) for got in answers
        )
        extra["ref_rows_nearest_pair"] = compare.nearest_pair(ref)
    return {
        **extra,
        "rows_misplaced": int(w.failed),
        **compare.error_numbers(errs),
        "rows_compared": int(errs.size),
    }
