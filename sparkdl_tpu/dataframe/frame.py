"""Partitioned, Arrow-interoperable DataFrame.

The reference keeps all data in Spark DataFrames and expresses work as
column transforms executed per partition on executors (SURVEY.md §2, §4).
This module supplies that substrate without a JVM:

- A ``DataFrame`` is an ordered list of *partitions*; each partition is a
  column-dict ``{col_name: list_of_values}``. Cell values are plain Python
  scalars, dicts (image structs), or numpy arrays (tensor columns).
- Transformations (``withColumn``, ``select``, ``filter`` …) are **lazy**:
  they append per-partition ops to a plan. Actions (``collect``, ``count``,
  ``toArrow`` …) execute the plan over all partitions on the runtime
  Executor (thread pool + per-partition retry) — the moral equivalent of
  Spark's narrow-transformation pipelining into one task per partition.
- Arrow is the interchange format: ``toArrow``/``fromArrow`` and parquet
  read/write, so data plugs into the wider Arrow ecosystem the way Spark
  DataFrames plug into theirs. Image structs map to Arrow struct columns.

There is deliberately no shuffle: nothing in the reference's featurization /
inference / training paths requires one (SURVEY.md §6 "featurization path
needs no shuffle at all"); ``repartition`` is a driver-side re-chunking.
"""

from __future__ import annotations

import copy
import math
import os
from collections.abc import Mapping
from typing import (
    Any,
    Callable,
    Dict,
    Iterable,
    List,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from sparkdl_tpu.dataframe.columns import (
    TensorColumn,
    from_arrow_array,
    to_arrow_array,
)
from sparkdl_tpu.obs import span
from sparkdl_tpu.runtime import knobs
from sparkdl_tpu.runtime.executor import default_executor

# A partition column chunk is either a plain list of cells or a contiguous
# TensorColumn block (fixed-shape tensor columns — the columnar fast path).
Partition = Dict[str, "list | TensorColumn"]


def _part_num_rows(part: Partition) -> int:
    if not part:
        return 0
    return len(next(iter(part.values())))


def _maybe_columnar(values):
    """Store uniformly-shaped ndarray sequences as one contiguous block."""
    tc = TensorColumn.maybe_pack(values)
    return tc if tc is not None else list(values)


def _take(values, indices):
    if isinstance(values, TensorColumn):
        return values.take(indices)
    return [values[i] for i in indices]


class LazyPartition(Mapping):
    """A partition backed by on-disk data: columns load on first access and
    can be released after a streaming pass, so file-backed DataFrames never
    hold every partition in memory at once. A Mapping (not a dict subclass)
    so ``dict(part)`` in op bodies goes through ``keys``/``__getitem__``
    and triggers the load instead of C-fast-pathing an empty dict.

    Subclasses implement ``_load_table() -> pyarrow.Table``."""

    def __init__(self, columns: Sequence[str]):
        self._lazy_columns = list(columns)
        self._data: Optional[Dict[str, Any]] = None
        self._table = None

    def _load_table(self):
        raise NotImplementedError

    def _ensure_table(self):
        if self._table is None:
            self._table = self._load_table()
        return self._table

    def release(self) -> None:
        """Drop the loaded columns; the next access re-reads the file."""
        self._data = None
        self._table = None

    def __getitem__(self, key):
        # convert columns one at a time: select('label') on a gathered
        # frame must not pay the features column's decode
        if self._data is None:
            self._data = {}
        if key not in self._data:
            if key not in self._lazy_columns:
                raise KeyError(key)
            self._data[key] = from_arrow_array(
                self._read_column_arrow(key)
            )
        return self._data[key]

    def _read_column_arrow(self, key):
        """One column as an Arrow array/chunked array; subclasses with
        columnar storage override to avoid touching other columns."""
        return self._ensure_table().column(key)

    def __iter__(self):
        return iter(self._lazy_columns)

    def __len__(self) -> int:
        return len(self._lazy_columns)

    def __contains__(self, key) -> bool:
        return key in self._lazy_columns

    @property
    def num_rows(self) -> int:
        """Row count without pinning: if the table isn't already cached,
        read it transiently (memory-mapped, no column conversion) and let
        it drop — a metadata-only count must not leave N file mappings
        alive."""
        if self._table is not None:
            return int(self._table.num_rows)
        return int(self._load_table().num_rows)


class LazyArrowPartition(LazyPartition):
    """One partition = one Arrow IPC file (the multi-worker gather layout)."""

    def __init__(self, path: str, columns: Sequence[str]):
        super().__init__(columns)
        self._path = path

    def _load_table(self):
        import pyarrow as pa

        # memory_map: column buffers page in on use, so a projection
        # that never touches the wide tensor column never reads it
        with pa.memory_map(self._path, "rb") as src:
            return pa.ipc.open_file(src).read_all()


class LazyParquetPartition(LazyPartition):
    """One partition = one row span of a parquet file, read row-group-wise
    (only the groups intersecting the span are ever decoded — the worker's
    bounded-memory reader discipline, as a DataFrame partition)."""

    def __init__(
        self, path: str, span: Tuple[int, int], columns: Sequence[str]
    ):
        super().__init__(columns)
        self._path = path
        self._span = (int(span[0]), int(span[1]))
        self._pf = None

    @property
    def num_rows(self) -> int:
        lo, hi = self._span
        return hi - lo

    def _load_table(self):
        return self._read_columns(self._lazy_columns)

    def _read_column_arrow(self, key):
        # parquet is columnar at rest: read ONE column's row groups per
        # access, so a select(in_col, label_col) stream never decodes a
        # wide features column riding in the same file
        return self._read_columns([key]).column(key)

    def release(self) -> None:
        super().release()
        self._pf = None  # also drop the cached file handle

    def _parquet_file(self):
        if self._pf is None:
            import pyarrow.parquet as pq

            self._pf = pq.ParquetFile(self._path)
        return self._pf

    def _read_columns(self, columns):
        import pyarrow as pa

        pf = self._parquet_file()
        lo, hi = self._span
        row = 0
        tables = []
        for r in range(pf.metadata.num_row_groups):
            nr = pf.metadata.row_group(r).num_rows
            lo_r, hi_r = max(lo, row), min(hi, row + nr)
            if lo_r < hi_r:
                tables.append(
                    pf.read_row_group(r, columns=list(columns)).slice(
                        lo_r - row, hi_r - lo_r
                    )
                )
            row += nr
            if row >= hi:
                break
        if not tables:
            return pf.schema_arrow.empty_table().select(list(columns))
        return pa.concat_tables(tables)


# Driver-side relational actions (orderBy / join) collect the frame; this
# cap fails FAST — from source-row metadata, before any decode — when the
# collect cannot be driver-sized. Raise it, or set 0 to disable, via env.
DRIVER_COLLECT_MAX_ROWS = knobs.get_int("SPARKDL_DRIVER_COLLECT_MAX_ROWS")


def _guard_driver_collect(df: "DataFrame", action: str) -> None:
    # env read LIVE (not just at import) so the error message's own advice
    # — set the var and retry — works inside a running session
    env = knobs.get_raw("SPARKDL_DRIVER_COLLECT_MAX_ROWS")
    limit = (
        knobs.get_int("SPARKDL_DRIVER_COLLECT_MAX_ROWS")
        if env is not None
        else DRIVER_COLLECT_MAX_ROWS
    )
    if not limit:
        return
    if df._ops:
        # a planned frame (filter/select/...) must decode anyway, and its
        # post-plan size is unknowable from metadata — filter-then-sort on
        # a huge file legitimately produces a driver-sized result, so the
        # fail-fast-from-metadata rationale doesn't apply
        return
    rows = sum(df.partitionRowCounts())
    if rows > limit:
        raise ValueError(
            f"{action} is a driver-side action and this frame has "
            f"{rows:,} source rows "
            f"(> SPARKDL_DRIVER_COLLECT_MAX_ROWS={limit:,}). At this scale "
            "use the streaming surfaces instead: filter/select/withColumn "
            "+ iterPartitions/writeParquet stay bounded, and groupBy/SQL "
            "aggregation streams partition-wise. Set "
            "SPARKDL_DRIVER_COLLECT_MAX_ROWS=0 to disable this guard."
        )


def _cell_key(v):
    """Hashable key for an arbitrary cell value: tensors hash by
    shape/dtype/bytes, image structs and lists recursively. Shared by
    distinct() and groupBy() so tensor/struct key columns work in both."""
    if isinstance(v, np.ndarray):
        return (v.shape, v.dtype.str, v.tobytes())
    if isinstance(v, dict):  # image structs and friends
        return tuple((k, _cell_key(v[k])) for k in sorted(v))
    if isinstance(v, (list, tuple)):
        return tuple(_cell_key(x) for x in v)
    return v


def partition_row_spans(total_rows: int, num_partitions: int):
    """(start, end) row span of each partition in the canonical balanced
    split (sizes differ by at most 1). THE single source of truth for how
    N rows map onto partitions — fromColumns slices by it, and the
    multi-host worker (sparkdl_tpu.worker) derives ownership from it, so
    driver and gang always agree without coordination."""
    num_partitions = (
        max(1, min(num_partitions, total_rows)) if total_rows else 1
    )
    base, rem = divmod(total_rows, num_partitions)
    spans = []
    start = 0
    for k in range(num_partitions):
        size = base + (1 if k < rem else 0)
        spans.append((start, start + size))
        start += size
    return spans


def _pandas_cells(series) -> list:
    """Bring a pandas column back to engine cells: scalar NaN/NaT/NA
    becomes None (pandas cannot hold None in numeric columns, so null
    round-trips through NaN — like pyspark's nullable-column
    conversion). Container cells (lists/arrays/dicts) pass through."""
    import pandas as pd

    out = []
    for v in series:
        if not isinstance(v, (list, tuple, dict, np.ndarray)) and pd.isna(v):
            out.append(None)
        else:
            out.append(v)
    return out


def _split_ddl_fields(s: str) -> List[str]:
    """Split a DDL schema string on TOP-LEVEL commas only, so
    parameterized/nested types (map<string,int>, decimal(10,2),
    array<struct<...>>) stay attached to their field."""
    parts: List[str] = []
    depth = 0
    cur: List[str] = []
    for ch in s:
        if ch in "<(":
            depth += 1
        elif ch in ">)":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    parts.append("".join(cur))
    return parts


def _schema_names(schema) -> List[str]:
    """Output column names from a pyspark-style schema argument: a
    list/tuple of names, or a DDL string ("id long, name string") whose
    type words — including parameterized/nested types — are accepted
    and ignored (dynamically-typed engine)."""
    if isinstance(schema, (list, tuple)):
        names = [str(c) for c in schema]
    elif isinstance(schema, str):
        import re as _re

        # name ends at whitespace OR colon: "a int", "a: int", "a:int"
        # are all accepted pyspark DDL spellings
        names = [
            _re.split(r"[:\s]", piece.strip(), maxsplit=1)[0]
            for piece in _split_ddl_fields(schema)
            if piece.strip()
        ]
    else:
        raise TypeError(
            "schema must be a list of column names or a DDL string "
            f"('id long, name string'), got {type(schema).__name__}"
        )
    if not names:
        raise ValueError("schema declares no columns")
    dups = {n for n in names if names.count(n) > 1}
    if dups:
        raise ValueError(f"Duplicate schema columns: {sorted(dups)}")
    return names


def _gen_nondet(node, index: int, n: int) -> list:
    """Values for one partition of a partition-seeded generator
    (Column API NondetNode): pyspark's monotonically_increasing_id
    layout (partition index << 33 + row offset), and seed+partition
    deterministic uniform/normal draws for rand/randn."""
    if node.kind == "mono_id":
        return [(index << 33) + j for j in range(n)]
    if node.kind == "spark_partition_id":
        return [index] * n
    # mask: SeedSequence rejects negative entropy, and hash-derived
    # seeds are frequently negative
    seed = (0 if node.seed is None else int(node.seed)) & (2 ** 64 - 1)
    rng = np.random.default_rng(np.random.SeedSequence([seed, index]))
    if node.kind == "rand":
        return [float(v) for v in rng.random(n)]
    if node.kind == "randn":
        return [float(v) for v in rng.standard_normal(n)]
    raise ValueError(f"Unknown generator kind {node.kind!r}")


def _run_plan(
    ops: Sequence[Callable[[Partition], Partition]],
    cols: Sequence[str],
    part: Partition,
    index: int = 0,
) -> Partition:
    """Run the pending op chain over one partition and project to ``cols``
    — the single shared execution body for pooled, streaming, and take
    paths. Ops marked ``_indexed`` also receive the partition's index
    (monotonically_increasing_id / rand / stratified sampling need
    partition identity to be unique and seed-deterministic)."""
    cur = part
    for op in ops:
        cur = op(cur, index) if getattr(op, "_indexed", False) else op(cur)
    return {c: cur[c] for c in cols if c in cur}


class _CoalescedPartition(Mapping):
    """Several source partitions presented as ONE, with the parent
    frame's pending ops applied per child at first access — the lazy
    half of :meth:`DataFrame.coalesce`. Children release as they are
    consumed; release() drops the merged cache (lazy children reload)."""

    def __init__(self, children, ops, cols, base_index: int = 0):
        self._children = list(children)
        self._child_ops = list(ops)
        self._cols = list(cols)
        self._base_index = base_index  # first child's ORIGINAL index
        self._data: Optional[Dict[str, list]] = None

    def _ensure(self) -> None:
        if self._data is not None:
            return
        merged: Dict[str, list] = {c: [] for c in self._cols}
        for off, child in enumerate(self._children):
            cur = _run_plan(
                self._child_ops, self._cols, child,
                index=self._base_index + off,
            )
            for c in self._cols:
                if c in cur:
                    merged[c].extend(list(cur[c]))
            if isinstance(child, LazyPartition):
                child.release()
        self._data = merged

    def __getitem__(self, key):
        self._ensure()
        return self._data[key]

    def __iter__(self):
        return iter(self._cols)

    def __len__(self) -> int:
        return len(self._cols)

    def release(self) -> None:
        self._data = None


class Row(dict):
    """A result row; attribute access mirrors pyspark Row ergonomics."""

    def __getattr__(self, name: str) -> Any:
        try:
            return self[name]
        except KeyError as e:
            raise AttributeError(name) from e

    def asDict(self, recursive: bool = False) -> dict:
        """Plain-dict copy (pyspark Row.asDict); ``recursive`` converts
        nested Rows too, including Rows inside list/dict cells."""
        if not recursive:
            return dict(self)

        def conv(v):
            if isinstance(v, Row):
                return v.asDict(True)
            if isinstance(v, list):
                return [conv(x) for x in v]
            if isinstance(v, dict):
                return {k: conv(x) for k, x in v.items()}
            return v

        return {k: conv(v) for k, v in self.items()}


class DataFrame:
    def __init__(
        self,
        partitions: Sequence[Partition],
        columns: Sequence[str],
        ops: Optional[List[Callable[[Partition], Partition]]] = None,
    ):
        self._source: List[Partition] = list(partitions)
        self._columns: List[str] = list(columns)
        self._ops: List[Callable[[Partition], Partition]] = list(ops or [])

    # correlation name from .alias(); read only by the join paths, and
    # deliberately NOT propagated through transformations — alias right
    # before joining, like the idiom it exists for
    _alias_name: Optional[str] = None

    def alias(self, name: str) -> "DataFrame":
        """Attach a correlation name for joins (pyspark ``alias``):
        ``df.alias("x").join(df.alias("y"), on="k")``. On a
        name-colliding join of two ALIASED frames, colliding non-key
        columns surface qualified as ``<alias>.<col>`` — the SQL
        layer's self-join spelling (this engine cannot represent
        Spark's duplicate flat output names, so it qualifies instead
        of refusing)."""
        if not name or not isinstance(name, str):
            raise ValueError(f"alias needs a non-empty name, got {name!r}")
        out = DataFrame(self._source, self._columns, list(self._ops))
        out._alias_name = name
        return out

    def colRegex(self, colName: str) -> list:
        """Columns whose name fully matches the regex (pyspark
        ``colRegex``; backticks optional): returns the matching columns
        as a list usable directly in select —
        ``df.select(df.colRegex("`^v.*`"))``."""
        import re as _re

        from sparkdl_tpu.dataframe.column import Column

        pat = colName.strip()
        if pat.startswith("`") and pat.endswith("`"):
            pat = pat[1:-1]
        rx = _re.compile(pat)
        from sparkdl_tpu import sql as _sql

        return [
            Column(_sql.Col(c))
            for c in self._columns
            if rx.fullmatch(c)
        ]

    # -- construction ---------------------------------------------------------

    @staticmethod
    def fromColumns(
        columns: Dict[str, Sequence[Any]], numPartitions: int = 1
    ) -> "DataFrame":
        names = list(columns)
        if not names:
            return DataFrame([], [])
        n = len(columns[names[0]])
        for c in names:
            if len(columns[c]) != n:
                raise ValueError("All columns must have the same length")
        # Balanced split via the canonical partition_row_spans (shared
        # with the multi-host worker's ownership math), so partition->
        # device mappings never leave a device without work.
        # Columnar decision is made ONCE per column over the whole input
        # (then sliced), so every partition of a column shares one storage
        # kind — per-partition divergence would mean divergent Arrow
        # schemas downstream.
        packed = {c: _maybe_columnar(columns[c]) for c in names}
        parts: List[Partition] = [
            {c: packed[c][start:end] for c in names}
            for start, end in partition_row_spans(n, numPartitions)
        ]
        if not parts:
            parts = [{c: [] for c in names}]
        return DataFrame(parts, names)

    @staticmethod
    def fromRows(
        rows: Sequence[Dict[str, Any]], numPartitions: int = 1
    ) -> "DataFrame":
        if not rows:
            return DataFrame([], [])
        names = list(rows[0])
        cols = {c: [r[c] for r in rows] for c in names}
        return DataFrame.fromColumns(cols, numPartitions)

    @staticmethod
    def fromArrow(table, numPartitions: int = 1) -> "DataFrame":
        """Build from a pyarrow Table; struct columns become dict cells and
        FixedShapeTensor columns become contiguous TensorColumn blocks
        (zero-copy where Arrow allows)."""
        cols = {
            name: from_arrow_array(table.column(name))
            for name in table.column_names
        }
        return DataFrame.fromColumns(cols, numPartitions)

    @staticmethod
    def fromArrowFiles(paths: Sequence[str]) -> "DataFrame":
        """Partition-per-file DataFrame over Arrow IPC files, loaded
        lazily (only the first file's schema is read here). Streaming
        actions (``iterPartitions``/``writeParquet``) hold one file's
        columns at a time; collect-style actions materialize all."""
        import pyarrow as pa

        paths = list(paths)
        if not paths:
            return DataFrame([], [])
        with pa.OSFile(paths[0], "rb") as src:
            schema = pa.ipc.open_file(src).schema
        cols = list(schema.names)
        return DataFrame(
            [LazyArrowPartition(p, cols) for p in paths], cols
        )

    @staticmethod
    def readParquet(path: str, numPartitions: int = 1) -> "DataFrame":
        import pyarrow.parquet as pq

        return DataFrame.fromArrow(pq.read_table(path), numPartitions)

    @staticmethod
    def scanParquet(path: str, numPartitions: int = 1) -> "DataFrame":
        """LAZY parquet scan: a partition-per-row-span DataFrame where each
        partition reads only its intersecting row groups on first access
        (and releases them after streaming passes). The bounded-memory
        alternative to :meth:`readParquet` for ImageNet-scale frames —
        streaming actions and the streaming trainer hold O(partition), not
        O(dataset). Only the footer is read here."""
        import pyarrow.parquet as pq

        pf = pq.ParquetFile(path)
        cols = list(pf.schema_arrow.names)
        spans = partition_row_spans(pf.metadata.num_rows, numPartitions)
        return DataFrame(
            [LazyParquetPartition(path, span, cols) for span in spans], cols
        )

    # -- metadata -------------------------------------------------------------

    @property
    def columns(self) -> List[str]:
        return list(self._columns)

    def __getattr__(self, name: str):
        """pyspark's attribute column access: ``df.x`` is a Column
        reference usable in expressions (``df.filter(df.x > 3)``).
        Only reached when no real attribute matches; non-column names
        raise AttributeError as usual."""
        if name.startswith("_"):
            raise AttributeError(name)
        # self._columns via __dict__ to avoid recursing through
        # __getattr__ during unpickling/copy before init
        cols = self.__dict__.get("_columns")
        if cols is not None and name in cols:
            from sparkdl_tpu.dataframe.column import Column
            from sparkdl_tpu import sql as _sql

            return Column(_sql.Col(name))
        if name == "writeStream":
            # AttributeError (not TypeError) so hasattr/getattr
            # capability probes get False/None; a real column named
            # writeStream resolved above
            raise AttributeError(
                "There is no structured-streaming engine in "
                "sparkdl_tpu (df.isStreaming is always False); for "
                "incremental processing, stream partitions with "
                "foreachPartition / toLocalIterator or write "
                "per-batch with writeParquet"
            )
        raise AttributeError(
            f"'DataFrame' object has no attribute {name!r} (and no "
            "such column)"
        )

    def __getitem__(self, key):
        """``df["x"]`` is a Column (pyspark); ``df[["a", "b"]]`` is a
        projection."""
        if isinstance(key, str):
            if key not in self._columns:
                raise KeyError(f"No such column {key!r}")
            from sparkdl_tpu.dataframe.column import Column
            from sparkdl_tpu import sql as _sql

            return Column(_sql.Col(key))
        if isinstance(key, (list, tuple)):
            return self.select(*key)
        raise TypeError(
            f"DataFrame indices are column names or lists, got "
            f"{type(key).__name__}"
        )

    @property
    def numPartitions(self) -> int:
        return len(self._source)

    def partitionRowCounts(self) -> List[int]:
        """Per-partition SOURCE row counts, from metadata where the
        partition is file-backed — no decode, no plan execution. Counts
        are pre-plan: pending filter ops are not applied (callers needing
        lockstep step-count agreement across a gang want exactly this —
        an identical, cheaply-computable upper bound on every rank)."""
        return [
            p.num_rows if isinstance(p, LazyPartition) else _part_num_rows(p)
            for p in self._source
        ]

    def __repr__(self) -> str:
        return (
            f"DataFrame(columns={self._columns}, "
            f"partitions={len(self._source)}, pending_ops={len(self._ops)})"
        )

    # -- lazy transformations -------------------------------------------------

    def _with_op(
        self, op: Callable[[Partition], Partition], columns: List[str]
    ) -> "DataFrame":
        return DataFrame(self._source, columns, self._ops + [op])

    def _apply_window_cols(self, cols: list) -> Tuple["DataFrame", list]:
        """Column-API windows (``F.row_number().over(Window...)``):
        compute every window-bearing Column through the SQL window
        engine (ONE engine for sql() text and .over — semantics cannot
        drift), widening the frame with hidden ``__win``/operand
        columns and rewriting those Columns to plain references. The
        caller's final projection drops the hidden columns. Returns
        (frame, cols) unchanged when nothing carries a window."""
        from sparkdl_tpu import sql as _sql
        from sparkdl_tpu.dataframe.column import Column

        items: list = []
        positions: list = []
        for i, c in enumerate(cols):
            if not (isinstance(c, Column) and c._has_window()):
                continue
            if c._is_pred():
                raise TypeError(
                    f"Window condition {c._output_name()!r} is not "
                    "supported directly; compute the window value with "
                    "withColumn first and compare that, or wrap the "
                    "comparison in F.when(...)"
                )
            # deepcopy: the engine materializes operand expressions IN
            # PLACE on the Window nodes; user-held Columns stay pure so
            # re-using one against another frame re-resolves cleanly
            expr = copy.deepcopy(c._expr)
            for w in _sql._iter_windows(expr):
                if _sql._window_needs_order(w.fn) and not w.order_by:
                    raise TypeError(
                        f"Window function {w.fn}() needs a bound, "
                        "ordered window: call .over(Window"
                        ".partitionBy(...).orderBy(...))"
                    )
            items.append(_sql.SelectItem(expr, c._output_name()))
            positions.append(i)
        if not items:
            return self, list(cols)
        df = _sql.SQLContext._apply_window_items(self, items)
        out = list(cols)
        for item, i in zip(items, positions):
            out[i] = Column(item.expr, item.alias)
        return df, out

    def select(self, *cols) -> "DataFrame":
        """Project by name, or by Column expression
        (``df.select("a", (F.col("v") * 2).alias("d"))``). A single
        list argument expands (pyspark: ``select(["a", "b"])``, and
        the ``select(df.colRegex("`v.*`"))`` idiom)."""
        if len(cols) == 1 and isinstance(cols[0], (list, tuple)):
            cols = tuple(cols[0])
        if any(not isinstance(c, str) for c in cols):
            from sparkdl_tpu.dataframe.column import (
                Column,
                ExplodeNode,
                JsonTupleNode,
                StackNode,
            )

            n_explodes = sum(
                1
                for c in cols
                if isinstance(c, Column)
                and isinstance(
                    c._expr, (ExplodeNode, StackNode, JsonTupleNode)
                )
            )
            if n_explodes > 1:
                raise ValueError(
                    "Only one generator (explode/stack/json_tuple) is "
                    "allowed per select"
                )
            if n_explodes:
                if any(
                    isinstance(c, Column) and c._has_window()
                    for c in cols
                ):
                    raise ValueError(
                        "A generator (explode) and a window function "
                        "cannot share one select; split into two selects"
                    )
                return self._select_with_explode(list(cols))

            base, wcols = self._apply_window_cols(list(cols))
            if base is not self:
                return base.select(*wcols)

            # every item resolves against the ORIGINAL frame (Spark):
            # computed items land under collision-proof temp names and
            # rename at the end, so an alias shadowing an input column
            # cannot corrupt later items that read the original
            df = self
            names: List[str] = []
            rename: List[Tuple[str, str]] = []
            for i, c in enumerate(cols):
                if isinstance(c, str):
                    names.append(c)
                    continue
                if not isinstance(c, Column):
                    raise TypeError(
                        "select() takes column names or Columns, got "
                        f"{type(c).__name__}"
                    )
                plain = c._plain_name()
                if plain is not None and c._alias in (None, plain):
                    names.append(plain)  # bare reference: no recompute
                    continue
                tmp = f"__sel_{i}"
                df = df.withColumn(tmp, c)
                names.append(tmp)
                rename.append((tmp, c._output_name()))
            out = df.select(*names)
            for tmp, final in rename:
                out = out.withColumnRenamed(tmp, final)
            return out
        wanted = list(cols)
        missing = [c for c in wanted if c not in self._columns]
        if missing:
            raise KeyError(f"No such columns: {missing}")

        def op(part: Partition) -> Partition:
            return {c: part[c] for c in wanted}

        return self._with_op(op, wanted)

    def _select_with_explode(self, cols: list) -> "DataFrame":
        """select with ONE generator item (F.explode/explode_outer/
        posexplode/stack/json_tuple): every non-generator item resolves
        against the input frame as in plain select; each input row then
        emits the generator's rows (a tuple of output cells per row),
        with plain items repeated alongside. Lazy — a per-partition op
        like every projection."""
        from sparkdl_tpu import sql as _sqlmod
        from sparkdl_tpu.dataframe.column import (
            Column,
            ExplodeNode,
            JsonTupleNode,
            StackNode,
        )

        df = self
        # (src cols, output names, kind): kind 'plain' carries the
        # source cell; generator kinds emit tuples via gen_rows below
        items: List[Tuple[List[str], List[str], str]] = []
        outer = False
        gen_node = None
        for i, c in enumerate(cols):
            if isinstance(c, str):
                if c not in self._columns:
                    raise KeyError(f"No such column {c!r}")
                items.append(([c], [c], "plain"))
                continue
            if not isinstance(c, Column):
                raise TypeError(
                    "select() takes column names or Columns, got "
                    f"{type(c).__name__}"
                )
            if isinstance(c._expr, ExplodeNode):
                tmp = f"__exp_{i}"
                df = df.withColumn(tmp, Column(c._expr.inner))
                node = gen_node = c._expr
                if node.with_pos:
                    if isinstance(c._alias, tuple):
                        fnames = list(c._alias)
                    elif c._alias is not None:
                        raise ValueError(
                            "posexplode produces two columns; alias "
                            "both: .alias('pos', 'col')"
                        )
                    else:
                        fnames = ["pos", "col"]
                    items.append(([tmp], fnames, "posex"))
                else:
                    items.append(([tmp], [c._output_name()], "ex"))
                outer = node.outer
                continue
            if isinstance(c._expr, StackNode):
                node = gen_node = c._expr
                srcs = []
                for j, arg in enumerate(node.args):
                    tmp = f"__stk_{i}_{j}"
                    df = df.withColumn(tmp, Column(arg))
                    srcs.append(tmp)
                if isinstance(c._alias, tuple):
                    fnames = list(c._alias)
                elif c._alias is not None:
                    fnames = [c._alias]  # width-1 stack, single alias
                else:
                    fnames = [f"col{j}" for j in range(node.width)]
                if len(fnames) != node.width:
                    raise ValueError(
                        f"stack produces {node.width} columns; got "
                        f"{len(fnames)} alias name(s)"
                    )
                items.append((srcs, fnames, "stack"))
                continue
            if isinstance(c._expr, JsonTupleNode):
                node = gen_node = c._expr
                tmp = f"__jt_{i}"
                df = df.withColumn(tmp, Column(node.src))
                if isinstance(c._alias, tuple):
                    fnames = list(c._alias)
                elif c._alias is not None:
                    fnames = [c._alias]
                else:
                    fnames = [f"c{j}" for j in range(len(node.fields))]
                if len(fnames) != len(node.fields):
                    raise ValueError(
                        f"json_tuple produces {len(node.fields)} "
                        f"columns; got {len(fnames)} alias name(s)"
                    )
                items.append(([tmp], fnames, "jt"))
                continue
            plain = c._plain_name()
            if plain is not None and c._alias in (None, plain):
                items.append(([plain], [plain], "plain"))
                continue
            tmp = f"__sel_{i}"
            df = df.withColumn(tmp, c)
            items.append(([tmp], [c._output_name()], "plain"))
        finals = [f for _, fs, _ in items for f in fs]
        dups = {f for f in finals if finals.count(f) > 1}
        if dups:
            raise ValueError(
                f"Duplicate output column(s) in select: {sorted(dups)}"
            )
        gen_srcs, gen_fs, gen_kind = next(
            (s, fs, k) for s, fs, k in items if k != "plain"
        )

        def gen_rows(part, i) -> Optional[List[tuple]]:
            """The generator's output tuples for input row i; None
            drops the row (non-outer explode of null/empty)."""
            if gen_kind in ("ex", "posex"):
                arr = part[gen_srcs[0]][i]
                if isinstance(arr, np.ndarray):
                    # tensor-block rows explode too (a uniform-length
                    # list column may be stored columnar)
                    arr = list(arr)
                if arr is None or (
                    isinstance(arr, (list, tuple)) and len(arr) == 0
                ):
                    if not outer:
                        return None  # explode drops null/empty rows
                    return [(None, None)] if gen_kind == "posex" else [
                        (None,)
                    ]
                if not isinstance(arr, (list, tuple)):
                    raise TypeError(
                        f"explode needs list cells; column "
                        f"{gen_srcs[0]!r} holds {type(arr).__name__}"
                    )
                if gen_kind == "posex":
                    return list(enumerate(arr))
                return [(e,) for e in arr]
            if gen_kind == "stack":
                vals = [part[s][i] for s in gen_srcs]
                w = gen_node.width
                rows = []
                for r in range(gen_node.n):
                    rows.append(tuple(
                        vals[r * w + j] if r * w + j < len(vals) else None
                        for j in range(w)
                    ))
                return rows
            # json_tuple: one output row, k LITERAL top-level key
            # lookups off a single json.loads (Spark: 'a.b' is the
            # literal key, never a path)
            js = part[gen_srcs[0]][i]
            if js is None:
                return [(None,) * len(gen_node.fields)]
            return [_sqlmod._json_tuple_row(js, gen_node.fields)]

        def op(part: Partition) -> Partition:
            n = _part_num_rows(part)
            out: Dict[str, list] = {f: [] for f in finals}
            for i in range(n):
                rows = gen_rows(part, i)
                if rows is None:
                    continue
                for tup in rows:
                    for srcs, fs, kind in items:
                        if kind == "plain":
                            out[fs[0]].append(part[srcs[0]][i])
                        else:
                            for f, v in zip(fs, tup):
                                out[f].append(v)
            return out

        return df._with_op(op, finals)

    def drop(self, *cols: str) -> "DataFrame":
        keep = [c for c in self._columns if c not in cols]
        return self.select(*keep)

    def withColumn(self, name: str, fn) -> "DataFrame":
        """Row-wise UDF column (reference: DataFrame.withColumn(udf(col))).
        ``fn`` is a row-callable or a Column expression; a condition
        Column produces a True/False/None cell per row (Spark)."""
        if not callable(fn):
            from sparkdl_tpu.dataframe.column import (
                Column,
                ExplodeNode,
                JsonTupleNode,
                NondetNode,
                StackNode,
            )

            if not isinstance(fn, Column):
                raise TypeError(
                    "withColumn() takes a row-callable or a Column, got "
                    f"{type(fn).__name__}"
                )
            if isinstance(
                fn._expr, (ExplodeNode, StackNode, JsonTupleNode)
            ):
                raise TypeError(
                    "generators (explode/stack/json_tuple) change the "
                    "row/column shape and only work as select items, "
                    "not withColumn"
                )
            if isinstance(fn._expr, NondetNode):
                node = fn._expr

                def nop(part: Partition, index: int) -> Partition:
                    out = dict(part)
                    out[name] = _gen_nondet(node, index, _part_num_rows(part))
                    return out

                nop._indexed = True
                cols = self._columns + (
                    [name] if name not in self._columns else []
                )
                return self._with_op(nop, cols)
            if fn._has_window():
                base, (c2,) = self._apply_window_cols([fn])
                out = base.withColumn(name, c2)
                keep = self._columns + (
                    [name] if name not in self._columns else []
                )
                return out.select(*keep)  # drop the hidden window cols
            if fn._has_catalog_call():
                if fn._is_pred():
                    raise TypeError(
                        "A UDF inside a condition is not supported "
                        "directly; compute the UDF value with "
                        "withColumn first, then compare that"
                    )
                from sparkdl_tpu import sql as _sql

                out = _sql._apply_expr(self, fn._expr, name)
                keep = self._columns + (
                    [name] if name not in self._columns else []
                )
                return out.select(*keep)
            fn = fn._row_fn()

        def op(part: Partition) -> Partition:
            n = _part_num_rows(part)
            rows = (Row({c: part[c][i] for c in part}) for i in range(n))
            out = dict(part)
            out[name] = [fn(r) for r in rows]
            return out

        cols = self._columns + ([name] if name not in self._columns else [])
        return self._with_op(op, cols)

    def withColumnPartition(
        self, name: str, fn: Callable[[Partition], Dict[str, list]]
    ) -> "DataFrame":
        """Partition-wise (vectorized) column producer: ``fn`` sees the whole
        partition column-dict and returns ``{name: values}``. This is the
        batched path every model transformer uses — one device call per batch,
        not per row (the TensorFrames map_blocks analogue)."""

        def op(part: Partition) -> Partition:
            out = dict(part)
            produced = fn(part)
            n = _part_num_rows(part)
            for k, v in produced.items():
                if len(v) != n:
                    raise ValueError(
                        f"withColumnPartition fn returned {len(v)} values for "
                        f"column {k!r}, expected {n}"
                    )
                # Storage kind follows the TYPE the producer returns —
                # TensorColumn/ndarray means columnar, a list stays a
                # list — so the kind is a property of the fn, identical
                # in every partition (per-partition content sniffing
                # could diverge on a ragged partition and split the
                # frame's Arrow schema).
                if isinstance(v, TensorColumn):
                    out[k] = v
                elif isinstance(v, np.ndarray) and v.ndim >= 2:
                    out[k] = TensorColumn(v)
                else:
                    out[k] = list(v)
            return out

        cols = self._columns + ([name] if name not in self._columns else [])
        return self._with_op(op, cols)

    def filter(self, fn) -> "DataFrame":
        """Keep rows where ``fn`` holds: a row-callable, or a condition
        Column (``df.filter(F.col("x") > 3)``) with SQL three-valued
        semantics — unknown (null comparison) never keeps a row."""
        if not callable(fn):
            from sparkdl_tpu.dataframe.column import Column

            if not isinstance(fn, Column):
                raise TypeError(
                    "filter() takes a row-callable or a Column "
                    f"condition, got {type(fn).__name__}"
                )
            if fn._is_pred() and fn._has_catalog_call():
                # UDF calls inside the condition: materialize batched
                # (same planner path as SQL WHERE), filter on the
                # rewritten tree, drop the temp columns. Windows must
                # still get their pointed construction-time error, not
                # a lazy partition failure
                fn._reject_window(
                    "filter (compute it with withColumn first, then "
                    "filter on the result, as in Spark)"
                )
                from sparkdl_tpu import sql as _sql

                tmp: List[str] = []
                pred, df = _sql._materialize_pred_calls(
                    copy.deepcopy(fn._expr), self, tmp
                )
                out = df.filter(
                    lambda r, node=pred: _sql._eval_pred3(node, r)
                    is True
                )
                return out.drop(*tmp) if tmp else out
            fn = fn._filter_fn()

        def op(part: Partition) -> Partition:
            n = _part_num_rows(part)
            keep = [
                i
                for i in range(n)
                if fn(Row({c: part[c][i] for c in part}))
            ]
            return {c: _take(part[c], keep) for c in part}

        return self._with_op(op, self._columns)

    def filterOnColumns(
        self,
        fn,
        cols: Sequence[str],
        on_skipped: Optional[Callable[[int], None]] = None,
    ) -> "DataFrame":
        """Pushdown filter: evaluate ``fn`` over Rows holding ONLY
        ``cols``, then take survivors across every column. Unlike
        :meth:`filter` — whose per-row Rows touch every column, forcing
        element-lazy cells (image decodes) to materialize for rows the
        predicate is about to drop — the untouched columns here pay
        only the per-survivor ``_take``. This is the SQL planner's
        cheap-predicate-first arm; ``on_skipped`` receives the dropped
        row count per partition (it feeds the pushdown counters)."""
        missing = [c for c in cols if c not in self._columns]
        if missing:
            raise KeyError(f"No such columns: {missing}")
        pred_cols = list(cols)

        def op(part: Partition) -> Partition:
            n = _part_num_rows(part)
            keep = [
                i
                for i in range(n)
                if fn(Row({c: part[c][i] for c in pred_cols}))
            ]
            if len(keep) == n:
                return part  # nothing dropped: no copies, no takes
            if on_skipped is not None:
                on_skipped(n - len(keep))
            return {c: _take(part[c], keep) for c in part}

        return self._with_op(op, self._columns)

    def dropna(
        self,
        how: str = "any",
        thresh: Optional[int] = None,
        subset: Optional[Sequence[str]] = None,
    ) -> "DataFrame":
        """Drop null rows (pyspark ``dropna``): ``how='any'`` drops a
        row with ANY null among ``subset`` (default all columns),
        ``how='all'`` only when every one is null; ``thresh=k`` keeps
        rows with at least k non-nulls and overrides ``how``."""
        if isinstance(how, (list, tuple)):
            # legacy positional form dropna([cols]) from before the
            # pyspark (how, thresh, subset) signature
            subset, how = how, "any"
        elif isinstance(how, str) and how not in ("any", "all"):
            if how in self._columns:
                # legacy dropna('col'); a column literally named
                # any/all takes the pyspark how-interpretation
                subset, how = [how], "any"
            else:
                raise ValueError(
                    f"dropna how must be 'any' or 'all' (or a column "
                    f"name for the legacy positional form), got {how!r}"
                )
        if isinstance(subset, str):  # single column name, pyspark-style
            subset = [subset]
        cols = list(subset) if subset is not None else list(self._columns)
        missing = [c for c in cols if c not in self._columns]
        if missing:
            raise KeyError(f"dropna: no such column(s) {missing}")
        if thresh is not None:
            k = int(thresh)
            return self.filter(
                lambda r: sum(r[c] is not None for c in cols) >= k
            )
        if how == "any":
            return self.filter(
                lambda r: all(r[c] is not None for c in cols)
            )
        if how == "all":
            return self.filter(
                lambda r: any(r[c] is not None for c in cols)
            )
        raise ValueError(f"dropna how must be 'any' or 'all', got {how!r}")

    def fillna(
        self, value, subset: Optional[Sequence[str]] = None
    ) -> "DataFrame":
        """Replace nulls (Spark ``fillna``): ``value`` may be a scalar
        (applied to every column in ``subset``, default all) or a
        ``{column: value}`` dict (``subset`` ignored, as in pyspark).
        Schema-light divergence from Spark: a scalar fills nulls in the
        chosen columns regardless of column type — there is no schema
        to type-scope the fill against. Lazy (per-partition map)."""
        if isinstance(value, dict):
            fills = dict(value)
        else:
            if isinstance(subset, str):
                subset = [subset]
            cols = list(subset) if subset is not None else list(self._columns)
            fills = {c: value for c in cols}
        missing = [c for c in fills if c not in self._columns]
        if missing:
            raise KeyError(f"fillna: no such column(s) {missing}")

        def fill(part: Partition) -> Partition:
            out = dict(part)
            for c, v in fills.items():
                cells = part[c]
                if any(x is None for x in cells):
                    out[c] = [v if x is None else x for x in cells]
            return out

        return self._with_op(fill, self._columns)

    def mapPartitions(
        self, fn: Callable[[Partition], Partition], columns: List[str]
    ) -> "DataFrame":
        return self._with_op(fn, columns)

    def unionAll(self, other: "DataFrame") -> "DataFrame":
        """Alias of :meth:`union` (pyspark keeps both; neither dedups)."""
        return self.union(other)

    @property
    def na(self) -> "_NAFunctions":
        """pyspark's ``df.na`` accessor: ``df.na.drop(...)`` /
        ``df.na.fill(...)`` delegate to :meth:`dropna` / :meth:`fillna`."""
        return _NAFunctions(self)

    def withColumnsRenamed(self, colsMap: Dict[str, str]) -> "DataFrame":
        """Rename several columns at once, SIMULTANEOUSLY (pyspark 3.4:
        {'a': 'b', 'b': 'c'} maps the original a->b and the original
        b->c; swaps work); missing names are ignored."""
        mapping = {
            old: new
            for old, new in colsMap.items()
            if old in self._columns and old != new
        }
        if not mapping:
            return self
        new_cols = [mapping.get(c, c) for c in self._columns]
        dups = {c for c in new_cols if new_cols.count(c) > 1}
        if dups:
            raise ValueError(
                f"withColumnsRenamed produces duplicate columns "
                f"{sorted(dups)}"
            )

        def op(part: Partition) -> Partition:
            return {mapping.get(c, c): part[c] for c in part}

        return self._with_op(op, new_cols)

    def union(self, other: "DataFrame") -> "DataFrame":
        """Row-union of two DataFrames with identical column sets; partitions
        of both sides are preserved (Spark ``DataFrame.union`` semantics)."""
        if set(self._columns) != set(other._columns):
            raise ValueError(
                f"union requires matching columns: {self._columns} vs "
                f"{other._columns}"
            )
        left = self._execute()
        right = [
            {c: p[c] for c in self._columns} for p in other._execute()
        ]
        return DataFrame(left + right, list(self._columns))

    def unionByName(
        self, other: "DataFrame", allowMissingColumns: bool = False
    ) -> "DataFrame":
        """Union matching columns BY NAME (Spark ``unionByName``);
        with ``allowMissingColumns`` either side's absent columns fill
        with nulls instead of erroring."""
        mine, theirs = set(self._columns), set(other._columns)
        if mine != theirs and not allowMissingColumns:
            raise ValueError(
                f"unionByName requires the same column names: "
                f"{sorted(mine ^ theirs)} differ (pass "
                "allowMissingColumns=True to null-fill)"
            )
        all_cols = list(self._columns) + [
            c for c in other._columns if c not in mine
        ]

        def widen(df: "DataFrame") -> "DataFrame":
            for c in all_cols:
                if c not in df.columns:
                    df = df.withColumn(c, lambda r: None)
            return df.select(*all_cols)

        return widen(self).union(widen(other))

    def intersect(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows present in BOTH frames (Spark ``intersect``)."""
        return self._set_op(other, keep_present=True)

    def subtract(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows of this frame NOT in ``other`` (Spark
        ``subtract`` / SQL EXCEPT)."""
        return self._set_op(other, keep_present=False)

    def exceptAll(self, other: "DataFrame") -> "DataFrame":
        """Multiset difference (Spark ``exceptAll`` / EXCEPT ALL): each
        left row survives max(left_count - right_count, 0) times, in
        left order — duplicates are data here, unlike subtract."""
        return self._multiset_op(other, keep_matched=False)

    def intersectAll(self, other: "DataFrame") -> "DataFrame":
        """Multiset intersection (Spark ``intersectAll`` / INTERSECT
        ALL): each row survives min(left_count, right_count) times."""
        return self._multiset_op(other, keep_matched=True)

    def _set_op_prologue(self, other: "DataFrame", what: str):
        """Shared validation + collection for the set/multiset ops:
        returns (cols, mine, n_mine, theirs, n_theirs)."""
        if set(self._columns) != set(other._columns):
            raise ValueError(
                f"set operation requires matching columns: "
                f"{self._columns} vs {other._columns}"
            )
        _guard_driver_collect(self, what)
        _guard_driver_collect(other, what)
        cols = self._columns
        mine = self.collectColumns()
        theirs = other.collectColumns()
        n_mine = len(mine[cols[0]]) if cols else 0
        n_theirs = len(theirs[cols[0]]) if cols else 0
        return cols, mine, n_mine, theirs, n_theirs

    def _multiset_op(
        self, other: "DataFrame", keep_matched: bool
    ) -> "DataFrame":
        from collections import Counter

        cols, mine, n, theirs, n_other = self._set_op_prologue(
            other, "exceptAll/intersectAll"
        )
        budget = Counter(
            tuple(_cell_key(theirs[c][i]) for c in cols)
            for i in range(n_other)
        )
        keep: List[int] = []
        for i in range(n):
            k = tuple(_cell_key(mine[c][i]) for c in cols)
            matched = budget[k] > 0
            if matched:
                budget[k] -= 1
            if matched == keep_matched:
                keep.append(i)
        out = {c: _take(mine[c], keep) for c in cols}
        return DataFrame.fromColumns(
            out, numPartitions=max(1, self.numPartitions)
        )

    def _set_op(self, other: "DataFrame", keep_present: bool) -> "DataFrame":
        cols, mine, n, theirs, n_other = self._set_op_prologue(
            other, "intersect/subtract"
        )
        other_keys = {
            tuple(_cell_key(theirs[c][i]) for c in cols)
            for i in range(n_other)
        }
        seen = set()
        keep: List[int] = []
        for i in range(n):
            k = tuple(_cell_key(mine[c][i]) for c in cols)
            if k in seen:
                continue
            seen.add(k)
            if (k in other_keys) == keep_present:
                keep.append(i)
        return DataFrame.fromColumns(
            {c: _take(mine[c], keep) for c in cols},
            numPartitions=max(1, self.numPartitions),
        )

    def withColumns(self, colsMap: Dict[str, Callable]) -> "DataFrame":
        """Add/replace several columns at once (Spark ``withColumns``):
        every fn sees the ORIGINAL row, so new columns cannot observe
        each other (Spark semantics)."""
        names = list(colsMap)
        tmps = {c: f"__wc_{i}" for i, c in enumerate(names)}
        df = self
        for c, fn in colsMap.items():
            df = df.withColumn(tmps[c], fn)
        # replaced columns keep their schema POSITION (Spark, and this
        # file's own withColumn); genuinely new columns append in order
        order = [tmps.get(c, c) for c in self._columns]
        order += [tmps[c] for c in names if c not in self._columns]
        df = df.select(*order)
        for c in names:
            df = df.withColumnRenamed(tmps[c], c)
        return df

    def randomSplit(
        self, weights: Sequence[float], seed: int = 0
    ) -> List["DataFrame"]:
        """Split rows randomly by normalized ``weights`` (Spark
        ``randomSplit``). Deterministic for a given seed: each row draws a
        uniform sample from a seeded stream ordered by (partition, row)."""
        import numpy as _np

        if any(w < 0 for w in weights) or sum(weights) <= 0:
            raise ValueError(f"Invalid split weights: {weights}")
        total = float(sum(weights))
        bounds = _np.cumsum([w / total for w in weights])
        parts = self._execute()
        rng = _np.random.default_rng(seed)
        out_parts: List[List[Partition]] = [[] for _ in weights]
        for part in parts:
            n = _part_num_rows(part)
            draws = rng.random(n)
            # bucket index of each row: first bound >= draw (clipped — a
            # draw one ulp past bounds[-1] must not drop the row)
            buckets = _np.minimum(
                _np.searchsorted(bounds, draws, side="left"), len(weights) - 1
            )
            for b in range(len(weights)):
                idx = _np.nonzero(buckets == b)[0]
                out_parts[b].append(
                    {c: _take(part[c], idx) for c in self._columns}
                )
        return [
            DataFrame(ps, list(self._columns)) for ps in out_parts
        ]

    def distinct(self) -> "DataFrame":
        """Deduplicate rows (driver-side; keys must be hashable — rows
        with tensor cells are compared by their tuple of bytes)."""
        return self._drop_duplicates(self._columns, "distinct")

    def _drop_duplicates(self, key_cols, action: str) -> "DataFrame":
        """Shared dedup core (first occurrence wins) for distinct /
        dropDuplicates — one place for the collect guard and key logic."""
        _guard_driver_collect(self, action)
        merged = self.collectColumns()
        cols = self._columns
        n = len(merged[cols[0]]) if cols else 0
        seen = set()
        keep: List[int] = []
        for i in range(n):
            k = tuple(_cell_key(merged[c][i]) for c in key_cols)
            if k not in seen:
                seen.add(k)
                keep.append(i)
        return DataFrame.fromColumns(
            {c: _take(merged[c], keep) for c in cols},
            numPartitions=max(1, self.numPartitions),
        )

    def dropDuplicates(self, subset: Optional[List[str]] = None) -> "DataFrame":
        """Deduplicate rows, optionally keying on a column subset —
        first occurrence wins (Spark ``dropDuplicates``)."""
        if subset is None:
            return self.distinct()
        for c in subset:
            if c not in self._columns:
                raise KeyError(f"Unknown column {c!r} in dropDuplicates")
        return self._drop_duplicates(list(subset), "dropDuplicates")

    drop_duplicates = dropDuplicates  # pyspark offers both spellings

    def where(self, fn: Callable[[Row], bool]) -> "DataFrame":
        """Alias of :meth:`filter` (Spark ``where``)."""
        return self.filter(fn)

    def sort(self, *cols: str, ascending=True) -> "DataFrame":
        """Alias of :meth:`orderBy` (Spark ``sort``)."""
        return self.orderBy(*cols, ascending=ascending)

    def take(self, n: int) -> List[Row]:
        """First ``n`` rows as a list (Spark ``take``)."""
        return self.head(n)

    def foreach(self, fn: Callable[[Row], Any]) -> None:
        """Apply ``fn`` to every row for its side effects (Spark
        ``foreach``); runs partition-at-a-time on the executor pool."""

        def per_part(part):
            n = _part_num_rows(part)
            for i in range(n):
                fn(Row({c: part[c][i] for c in part}))

        self.foreachPartition(lambda part: per_part(part))

    def replace(self, to_replace, value=None, subset=None) -> "DataFrame":
        """Replace cell values (Spark ``replace``): scalar->scalar,
        list->list (positional pairing), or a {old: new} dict. Nulls are
        untouched (that is :meth:`fillna`'s job)."""
        if isinstance(to_replace, dict):
            if value is not None:
                raise ValueError(
                    "value must be omitted when to_replace is a dict"
                )
            pairs = list(to_replace.items())
        elif isinstance(to_replace, (list, tuple)):
            if not isinstance(value, (list, tuple)) or len(value) != len(
                to_replace
            ):
                raise ValueError(
                    "list to_replace needs a value list of equal length"
                )
            pairs = list(zip(to_replace, value))
        else:
            if value is None:
                # a forgotten value must not silently null cells out
                raise ValueError(
                    "value argument is required for scalar/list "
                    "to_replace (use fillna/dropna for nulls)"
                )
            pairs = [(to_replace, value)]
        # Key by (is-bool, value): hash(False)==hash(0) and False==0 in
        # Python, so a plain dict would let replace(0, x) silently
        # rewrite boolean cells.
        mapping = {
            (isinstance(old, bool), old): new for old, new in pairs
        }
        cols = list(subset) if subset else list(self._columns)
        for c in cols:
            if c not in self._columns:
                raise KeyError(f"Unknown column {c!r} in replace")
        col_set = set(cols)

        def swap(v):
            if v is None:
                return None
            try:
                return mapping.get((isinstance(v, bool), v), v)
            except TypeError:  # unhashable cell (arrays/structs): keep
                return v

        def op(part: Partition) -> Partition:
            return {
                c: (
                    [swap(v) for v in part[c]] if c in col_set else part[c]
                )
                for c in part
            }

        return self._with_op(op, list(self._columns))

    def _co_moments(self, col1: str, col2: str, action: str):
        """One streamed pass over the (col1, col2) pairs: null pairs
        skip, sums SHIFTED by the first pair (corr/cov are
        shift-invariant; the naive sum-of-squares form catastrophically
        cancels on large-mean data). Returns (n, sx, sy, sxx, syy, sxy)."""
        for c in (col1, col2):
            if c not in self._columns:
                raise KeyError(f"Unknown column {c!r} in {action}")
        sx = sy = sxx = syy = sxy = 0.0
        n = 0
        ox = oy = None
        for part in self.iterPartitions():
            a, b = part[col1], part[col2]
            for i in range(_part_num_rows(part)):
                x, y = a[i], b[i]
                if x is None or y is None:
                    continue
                if ox is None:
                    ox, oy = x, y
                dx, dy = x - ox, y - oy
                n += 1
                sx += dx
                sy += dy
                sxx += dx * dx
                syy += dy * dy
                sxy += dx * dy
        return n, sx, sy, sxx, syy, sxy

    def corr(self, col1: str, col2: str) -> Optional[float]:
        """Pearson correlation of two numeric columns (pyspark
        ``df.corr``), streamed in one pass; null pairs skip; fewer than
        two pairs or zero variance -> None."""
        n, sx, sy, sxx, syy, sxy = self._co_moments(col1, col2, "corr")
        if n < 2:
            return None
        vx = sxx - sx * sx / n
        vy = syy - sy * sy / n
        if vx <= 0 or vy <= 0:
            return None
        return (sxy - sx * sy / n) / math.sqrt(vx * vy)

    def cov(self, col1: str, col2: str) -> Optional[float]:
        """Sample covariance of two numeric columns (pyspark
        ``df.cov``), streamed; null pairs skip; n < 2 -> None."""
        n, sx, sy, _, _, sxy = self._co_moments(col1, col2, "cov")
        if n < 2:
            return None
        return (sxy - sx * sy / n) / (n - 1)

    def _qualify_overlap(self, other: "DataFrame", overlap: set):
        """When BOTH frames carry distinct .alias() names, resolve a
        column collision by renaming each colliding column to
        ``<alias>.<col>`` on its side (the SQL layer's self-join
        spelling); returns None when aliases cannot disambiguate."""
        la, ra = self._alias_name, other._alias_name
        if not la or not ra or la == ra:
            return None
        targets = [(f"{la}.{c}", f"{ra}.{c}") for c in sorted(overlap)]
        if any(
            lt in self._columns or rt in other._columns
            for lt, rt in targets
        ):
            # a qualified name is already taken (e.g. the output of a
            # previous aliased join): fall through to the ambiguity
            # error rather than raising a baffling rename failure
            return None
        left2, right2 = self, other
        for c, (lt, rt) in zip(sorted(overlap), targets):
            left2 = left2.withColumnRenamed(c, lt)
            right2 = right2.withColumnRenamed(c, rt)
        return left2, right2

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        """Cartesian product (Spark ``crossJoin``); column names must
        not collide, as with :meth:`join` — unless both frames are
        aliased, which qualifies the collisions instead."""
        overlap = set(self._columns) & set(other._columns)
        if overlap:
            qualified = self._qualify_overlap(other, overlap)
            if qualified is not None:
                left2, right2 = qualified
                return left2.crossJoin(right2)
            raise ValueError(
                f"crossJoin column name collision: {sorted(overlap)}; "
                "rename with withColumnRenamed first, or alias both "
                "frames (df.alias('x'))"
            )
        _guard_driver_collect(self, "crossJoin")
        _guard_driver_collect(other, "crossJoin")
        left = self.collectColumns()
        right = other.collectColumns()
        ln = len(left[self._columns[0]]) if self._columns else 0
        rn = len(right[other._columns[0]]) if other._columns else 0
        out: Dict[str, list] = {}
        for c in self._columns:
            out[c] = [left[c][i] for i in range(ln) for _ in range(rn)]
        for c in other._columns:
            out[c] = [right[c][j] for _ in range(ln) for j in range(rn)]
        return DataFrame.fromColumns(
            out, numPartitions=max(1, self.numPartitions)
        )

    def _schema_samples(self) -> Dict[str, Any]:
        """First non-null cell per column (the shared schema-inference
        sampling for printSchema / dtypes / schema): streams partitions
        and stops as soon as every column has a sample — O(one
        partition) for dense data, never a full collect."""
        samples: Dict[str, Any] = {}
        for part in self.iterPartitions():
            n = _part_num_rows(part)
            for c in self._columns:
                if c in samples:
                    continue
                col = part[c]
                for i in range(n):
                    if col[i] is not None:
                        samples[c] = col[i]
                        break
            if len(samples) == len(self._columns):
                break
        return samples

    @property
    def dtypes(self) -> List[Tuple[str, str]]:
        """Inferred (name, type-name) pairs (pyspark ``dtypes``),
        Spark's type vocabulary for scalar cells: bigint / double /
        string / boolean / binary / date / timestamp; array for list
        cells, struct for dict cells, tensor<dtype>[shape] for ndarray
        columns, unknown when a column has no non-null cell to sample."""
        import datetime

        samples = self._schema_samples()

        def tname(v) -> str:
            if v is None:
                return "unknown"
            if isinstance(v, (bool, np.bool_)):  # before int checks
                return "boolean"
            if isinstance(v, (int, np.integer)):
                return "bigint"
            if isinstance(v, (float, np.floating)):
                return "double"
            if isinstance(v, str):
                return "string"
            if isinstance(v, bytes):
                return "binary"
            if isinstance(v, datetime.datetime):
                return "timestamp"
            if isinstance(v, datetime.date):
                return "date"
            if isinstance(v, np.ndarray):
                return f"tensor<{v.dtype}>{list(v.shape)}"
            if isinstance(v, (list, tuple)):
                return "array"
            if isinstance(v, dict):
                return "struct"
            return type(v).__name__

        return [(c, tname(samples.get(c))) for c in self._columns]

    @property
    def schema(self):
        """Inferred schema as a StructType-shaped object (pyspark
        ``schema``): fields carry the :attr:`dtypes` type names; every
        field is nullable by construction."""
        from sparkdl_tpu.dataframe.types import StructField, StructType

        return StructType(
            [StructField(c, t, True) for c, t in self.dtypes]
        )

    def printSchema(self) -> None:
        """Print an inferred schema tree (Spark ``printSchema``): the
        type of each column's first non-null cell; every column is
        nullable by construction. Streams partitions and stops as soon
        as every column has a sample — O(one partition) for dense data,
        never a full collect."""
        samples = self._schema_samples()
        lines = ["root"]
        for c in self._columns:
            sample = samples.get(c)
            if sample is None:
                tname = "unknown"
            elif isinstance(sample, np.ndarray):
                tname = f"tensor<{sample.dtype}>{list(sample.shape)}"
            else:
                tname = type(sample).__name__
            lines.append(f" |-- {c}: {tname} (nullable = true)")
        print("\n".join(lines))

    def selectExpr(self, *exprs: str) -> "DataFrame":
        """Project SQL expression strings (Spark ``selectExpr``):
        ``df.selectExpr("price * qty AS total", "label")``. Uses the SQL
        dialect's expression grammar — UDF calls from the process-global
        catalog included; aggregates are not allowed here (use
        ``agg``/``groupBy`` or a SQL query)."""
        from sparkdl_tpu import sql as _sql

        # Every expression evaluates against the INPUT frame (Spark
        # semantics): materialize into collision-proof temp names first,
        # so an alias shadowing a source column ("price * 2 AS price")
        # cannot corrupt later items, then rename into place.
        df = self
        # parse pass: every expression is validated before anything
        # executes, and window-bearing items are gathered so the window
        # engine runs ONCE for the whole select (one driver collect,
        # shared-spec dedup across items), like sql()'s item planning
        parsed: List[tuple] = []  # (item|None, final_name) output order
        witems: List[Any] = []
        for text in exprs:
            if text.strip() == "*":
                parsed.extend((None, c) for c in self._columns)
                continue
            parser = _sql._Parser(_sql._tokenize(text))
            item = parser.select_item()
            if parser.peek()[0] != "eof":
                raise ValueError(
                    f"Trailing tokens in selectExpr item {text!r}"
                )
            if item.expr == "*" or _sql._contains_aggregate(item.expr):
                raise ValueError(
                    f"selectExpr does not support aggregates ({text!r}); "
                    "use agg()/groupBy() or sql()"
                )
            name = item.alias or _sql._expr_name(item.expr)
            if _sql._contains_window(item.expr):
                witems.append(item)
            parsed.append((item, name))
        if witems:
            # same engine as sql() OVER(...) and Column.over; items are
            # rewritten in place to plain references over the widened df
            df = _sql.SQLContext._apply_window_items(df, witems)
        items: List[tuple] = []  # (tmp_name, final_name) in output order
        for i, (item, name) in enumerate(parsed):
            if item is None:  # a "*" passthrough column
                items.append((name, name))
                continue
            tmp = f"__selexpr_{i}"
            df = _sql._apply_expr(df, item.expr, tmp)
            items.append((tmp, name))
        finals = [n for _, n in items]
        dups = {n for n in finals if finals.count(n) > 1}
        if dups:
            raise ValueError(
                f"Duplicate output column(s) in selectExpr: {sorted(dups)}"
            )
        df = df.select(*[t for t, _ in items])
        for tmp, name in items:
            df = df.withColumnRenamed(tmp, name)
        return df

    def summary(self, *stats: str) -> "DataFrame":
        """Extended describe (Spark ``summary``): default statistics are
        count, mean, stddev, min, 25%, 50%, 75%, max over the numeric
        columns; pass stat names (incl. any 'N%') to customize."""
        import numbers

        wanted_stats = list(stats) or [
            "count", "mean", "stddev", "min", "25%", "50%", "75%", "max"
        ]
        known = {"count", "mean", "stddev", "min", "max"}
        for s in wanted_stats:  # validate before any execution
            if s not in known and not s.endswith("%"):
                raise ValueError(f"Unknown summary statistic {s!r}")
        # ONE execution of the plan: percentiles and moments both come
        # from this collection (describe would re-execute it).
        merged = self.collectColumns()

        def is_num(v):
            return isinstance(v, numbers.Number) and not isinstance(v, bool)

        num_cols = [
            c
            for c in self._columns
            if (vals := [v for v in merged[c] if v is not None])
            and all(is_num(v) for v in vals)
        ]
        out: Dict[str, List[Any]] = {"summary": wanted_stats}
        for c in num_cols:
            vals = np.asarray(
                [v for v in merged[c] if v is not None], dtype=float
            )
            n = int(vals.size)
            col_out: List[Any] = []
            for s in wanted_stats:
                if s.endswith("%"):
                    col_out.append(
                        float(np.percentile(vals, float(s[:-1])))
                        if n
                        else None
                    )
                elif s == "count":
                    col_out.append(n)
                elif s == "mean":
                    col_out.append(float(vals.mean()) if n else None)
                elif s == "stddev":
                    col_out.append(
                        float(vals.std(ddof=1)) if n > 1 else None
                    )
                elif s == "min":
                    col_out.append(float(vals.min()) if n else None)
                else:  # max
                    col_out.append(float(vals.max()) if n else None)
            out[c] = col_out
        return DataFrame.fromColumns(out)

    def createOrReplaceTempView(self, name: str) -> None:
        """Register this frame in the process-default SQL context under
        ``name`` (pyspark ``createOrReplaceTempView``), queryable via
        ``sparkdl_tpu.sql.sql(...)``."""
        from sparkdl_tpu import sql as _sqlmod

        _sqlmod.registerDataFrameAsTable(self, name)

    def createTempView(self, name: str) -> None:
        """Like :meth:`createOrReplaceTempView` but refuses to replace
        an existing view (pyspark semantics); the check-and-register is
        atomic under the context lock."""
        from sparkdl_tpu import sql as _sqlmod

        if not _sqlmod._default._register_if_absent(self, name):
            raise ValueError(
                f"Temp view {name!r} already exists; use "
                "createOrReplaceTempView to overwrite"
            )

    def createGlobalTempView(self, name: str) -> None:
        """pyspark ``createGlobalTempView``: registered under the
        ``global_temp`` database prefix — query as
        ``SELECT ... FROM global_temp.<name>``. One process = one
        "global" scope here (no cross-session catalog)."""
        from sparkdl_tpu import sql as _sqlmod

        if not _sqlmod._default._register_if_absent(
            self, f"global_temp.{name}"
        ):
            raise ValueError(
                f"Global temp view {name!r} already exists; use "
                "createOrReplaceGlobalTempView to overwrite"
            )

    def createOrReplaceGlobalTempView(self, name: str) -> None:
        from sparkdl_tpu import sql as _sqlmod

        _sqlmod.registerDataFrameAsTable(self, f"global_temp.{name}")

    def _grouping_keys(self, cols, what: str):
        """Resolve groupBy/rollup/cube keys: names stay names;
        expression Columns (``F.window(...)``, ``F.col("v") % 2``)
        materialize under their output name first (Spark groups by
        the expression)."""
        from sparkdl_tpu.dataframe.column import Column

        df = self
        names: List[str] = []
        for c in cols:
            if isinstance(c, str):
                if c not in df._columns:
                    raise KeyError(f"Unknown column {c!r} in {what}")
                names.append(c)
                continue
            if not isinstance(c, Column):
                raise TypeError(
                    f"{what} keys are names or Columns, got "
                    f"{type(c).__name__}"
                )
            plain = c._plain_name()
            if plain is not None and c._alias in (None, plain):
                if plain not in df._columns:
                    raise KeyError(f"Unknown column {plain!r} in {what}")
                names.append(plain)
                continue
            name = c._output_name()
            if name in df._columns:
                # materializing the key would silently SHADOW the
                # existing column — aggregates over that name would
                # read the key, not the data (wrong results, no error)
                raise ValueError(
                    f"{what} expression key {name!r} collides with an "
                    "existing column; alias the key to a fresh name"
                )
            df = df.withColumn(name, c)
            names.append(name)
        return df, names

    def groupBy(self, *cols) -> "GroupedData":
        """Group rows by key columns for aggregation (Spark ``groupBy``).
        Keys may be names or expression Columns —
        ``groupBy(F.window("ts", "10 minutes"))`` buckets by tumbling
        time windows (struct keys group by content). Returns a
        :class:`GroupedData`; see its ``agg``/``count``."""
        df, names = self._grouping_keys(cols, "groupBy")
        return GroupedData(df, names)

    groupby = groupBy  # pyspark offers both spellings

    def rollup(self, *cols) -> "GroupedData":
        """Hierarchical subtotals (Spark ``rollup``): aggregates over
        (k1..kn), (k1..kn-1), ..., (), with null-filled key columns on
        the subtotal rows — the SQL GROUP BY ROLLUP surface on the
        DataFrame API."""
        df, names = self._grouping_keys(cols, "rollup")
        return GroupedData(df, names, mode="rollup")

    def cube(self, *cols) -> "GroupedData":
        """All grouping-set combinations of the keys (Spark ``cube``)."""
        df, names = self._grouping_keys(cols, "cube")
        return GroupedData(df, names, mode="cube")

    def groupingSets(self, groupingSets, *cols) -> "GroupedData":
        """Explicit grouping sets (pyspark 3.4 ``groupingSets``):
        ``df.groupingSets([["a", "b"], ["a"], []], "a", "b")`` — each
        set must use keys from ``cols``; keys absent from a set emit
        null, exactly the SQL GROUP BY GROUPING SETS surface."""
        df, names = self._grouping_keys(cols, "groupingSets")
        if not names:
            raise ValueError("groupingSets needs at least one key column")
        from sparkdl_tpu.dataframe.column import Column

        def member_name(m) -> str:
            if isinstance(m, Column):
                # `m not in names` would force Column.__eq__ into bool
                plain = m._plain_name()
                if plain is None:
                    raise ValueError(
                        "groupingSets members must be plain column "
                        "references (expressions go in the key list)"
                    )
                return plain
            return m

        sets: List[Tuple[str, ...]] = []
        for s in groupingSets:
            members = [
                member_name(m)
                for m in ([s] if isinstance(s, (str, Column)) else list(s))
            ]
            bad = [m for m in members if m not in names]
            if bad:
                raise ValueError(
                    f"groupingSets members {bad} are not among the "
                    f"key columns {names}"
                )
            sets.append(tuple(members))
        if not sets:
            raise ValueError("groupingSets needs at least one set")
        return GroupedData(df, names, mode="sets", explicit_sets=sets)

    def agg(self, *exprs) -> "DataFrame":
        """Global aggregation without grouping (Spark ``df.agg``):
        ``df.agg({"score": "avg", "*": "count"})`` or the Column form
        ``df.agg(F.sum("v").alias("s"))`` yields one row."""
        return GroupedData(self, []).agg(*exprs)

    def first(self) -> Optional[Row]:
        """First row, or None on an empty frame (Spark ``first``)."""
        rows = self.head(1)
        return rows[0] if rows else None

    def _join_on_columns(
        self, conds: list, other: "DataFrame", how: str
    ) -> "DataFrame":
        """Equi-join from Column conditions: each must be
        F.col('a') == F.col('b') (or a bare F.col('k') meaning a
        same-named key); '&'-combined conditions expand. Differing key
        names rename the right key onto the left's, so the output keeps
        one merged key column under the left name (the SQL JOIN rule)."""
        from sparkdl_tpu import sql as _sql
        from sparkdl_tpu.dataframe.column import Column

        pairs: List[Tuple[str, str]] = []

        def add_pred(node) -> None:
            if isinstance(node, _sql.BoolOp) and node.op == "and":
                for p in node.parts:
                    add_pred(p)
                return
            if (
                isinstance(node, _sql.Predicate)
                and node.op == "="
                and isinstance(node.col, _sql.Col)
                and isinstance(node.value, _sql.Col)
            ):
                pairs.append((node.col.name, node.value.name))
                return
            raise ValueError(
                "join(on=Column) takes equality conditions between "
                "column references — F.col('a') == F.col('b'), several "
                "combined with & — not arbitrary predicates"
            )

        for c in conds:
            if isinstance(c, str):
                pairs.append((c, c))
                continue
            if not isinstance(c, Column):
                raise TypeError(
                    f"join key must be a name or Column, got "
                    f"{type(c).__name__}"
                )
            if c._is_pred():
                add_pred(c._expr)
                continue
            plain = c._plain_name()
            if plain is None:
                raise ValueError(
                    "A non-condition join Column must be a bare column "
                    "reference (same-named key on both sides)"
                )
            pairs.append((plain, plain))

        right = other
        keys: List[str] = []
        for ln, rn in pairs:
            if ln not in self._columns and rn in self._columns:
                ln, rn = rn, ln  # condition written right == left
            if ln not in self._columns:
                raise KeyError(
                    f"Join key {ln!r} not found on the left side"
                )
            if rn not in other._columns:
                raise KeyError(
                    f"Join key {rn!r} not found on the right side"
                )
            if ln != rn:
                if ln in right._columns:
                    raise ValueError(
                        f"Cannot join on {ln!r} == {rn!r}: the right "
                        f"side also has a column named {ln!r}; rename "
                        "it with withColumnRenamed first"
                    )
                right = right.withColumnRenamed(rn, ln)
            keys.append(ln)
        return self.join(right, on=keys, how=how)

    def withColumnRenamed(self, existing: str, new: str) -> "DataFrame":
        """Rename a column (Spark ``withColumnRenamed``). No-op if the
        source column does not exist, matching Spark."""
        if existing not in self._columns or existing == new:
            return self
        if new in self._columns:
            raise ValueError(f"Column {new!r} already exists")

        def op(part: Partition) -> Partition:
            return {(new if c == existing else c): part[c] for c in part}

        cols = [new if c == existing else c for c in self._columns]
        return self._with_op(op, cols)

    def tail(self, num: int) -> List[Row]:
        """The LAST ``num`` rows (pyspark ``tail``): rows stream
        through a ``num``-deep window — O(num) memory, no full driver
        collect."""
        if num <= 0:
            return []
        from collections import deque

        return list(deque(self.toLocalIterator(), maxlen=num))

    def toLocalIterator(self) -> Iterable[Row]:
        """Row iterator streaming partition-at-a-time (pyspark
        ``toLocalIterator``): O(partition) memory, rows in frame
        order."""
        for part in self.iterPartitions():
            n = _part_num_rows(part)
            for i in range(n):
                yield Row({c: part[c][i] for c in self._columns})

    def transform(self, func, *args, **kwargs) -> "DataFrame":
        """Chain a frame-to-frame function fluently (pyspark
        ``transform``): ``df.transform(clean).transform(featurize)``."""
        out = func(self, *args, **kwargs)
        if not isinstance(out, DataFrame):
            raise TypeError(
                f"transform function must return a DataFrame, got "
                f"{type(out).__name__}"
            )
        return out

    def sortWithinPartitions(
        self, *cols, ascending: Any = True
    ) -> "DataFrame":
        """Per-partition sort (Spark ``sortWithinPartitions``): the
        same key and null-ordering rules as :meth:`orderBy` (nulls
        first ascending, last descending) but LAZY and partition-local
        — no driver collect, no repartitioning. Keys are column names
        or plain/asc()/desc()-marked Columns; computed keys need a
        withColumn first."""
        if not cols:
            raise ValueError("sortWithinPartitions needs a column")
        from sparkdl_tpu.dataframe.column import Column

        asc_in = (
            list(ascending)
            if isinstance(ascending, (list, tuple))
            else [ascending] * len(cols)
        )
        if len(asc_in) != len(cols):
            raise ValueError(
                f"ascending has {len(asc_in)} entries for "
                f"{len(cols)} columns"
            )
        keys: List[Tuple[str, bool]] = []
        for c, a in zip(cols, asc_in):
            if isinstance(c, Column):
                if c._sort is not None:
                    a = c._sort
                    if c._sort_nulls is not None:
                        from sparkdl_tpu import sql as _sql

                        a = _sql.SortDir(c._sort, c._sort_nulls)
                plain = c._plain_name()
                if plain is None:
                    raise TypeError(
                        "sortWithinPartitions keys must be plain "
                        "columns; compute expressions with withColumn "
                        "first"
                    )
                c = plain
            if c not in self._columns:
                raise KeyError(f"No such column {c!r}")
            # resolve the null rank HERE so the partition op carries
            # plain (name, asc, rank) triples — same algebra as orderBy
            asc_b = bool(a)
            nf = getattr(a, "nulls_first", None)
            if nf is None:
                nf = asc_b
            rank = (0 if nf else 2) if asc_b else (2 if nf else 0)
            keys.append((c, asc_b, rank))

        def op(part: Partition) -> Partition:
            n = _part_num_rows(part)
            order = list(range(n))
            for name, asc, rank in reversed(keys):  # stable multi-key
                col = part[name]
                order.sort(
                    key=lambda i, c=col, r=rank: (
                        (r, 0) if c[i] is None else (1, c[i])
                    ),
                    reverse=not asc,
                )
            return {c: _take(part[c], order) for c in part}

        return self._with_op(op, self._columns)

    @property
    def stat(self) -> "DataFrameStatFunctions":
        """Statistics namespace (pyspark ``df.stat``): approxQuantile,
        corr, cov, crosstab, freqItems, sampleBy."""
        return DataFrameStatFunctions(self)

    def approxQuantile(
        self, col, probabilities, relativeError: float = 0.0
    ):
        """Quantiles of numeric column(s) as actual data points (Spark
        ``approxQuantile``). Computed EXACTLY regardless of
        ``relativeError`` (driver-side sort, collect-guarded) — exact
        satisfies any requested error. Nulls are ignored; a column of
        all nulls yields an empty list. A list of columns returns a
        list of per-column results."""
        probs = list(probabilities)
        for p in probs:
            if not 0.0 <= float(p) <= 1.0:
                raise ValueError(f"probability {p} outside [0, 1]")
        if float(relativeError) < 0:
            raise ValueError("relativeError must be >= 0")
        cols = [col] if isinstance(col, str) else list(col)
        for c in cols:
            if c not in self._columns:
                raise KeyError(f"No such column {c!r}")
        _guard_driver_collect(self, "approxQuantile")
        merged = self.select(*cols).collectColumns()
        out = []
        for c in cols:
            vals = sorted(v for v in merged[c] if v is not None)
            if not vals:
                out.append([])
                continue
            n = len(vals)
            # exact rank: ceil(p*n)-1 (p=0.5, n=4 -> element 1, like
            # Spark's relativeError=0); int(p*n) would sit one too high
            out.append([
                float(vals[min(n - 1, max(0, math.ceil(float(p) * n) - 1))])
                for p in probs
            ])
        return out[0] if isinstance(col, str) else out

    def crosstab(self, col1: str, col2: str) -> "DataFrame":
        """Pairwise frequency table (Spark ``crosstab``): one row per
        distinct ``col1`` value, one count column per distinct ``col2``
        value (stringified, sorted), first column named
        ``<col1>_<col2>``. Memory O(distinct1 x distinct2)."""
        for c in (col1, col2):
            if c not in self._columns:
                raise KeyError(f"No such column {c!r}")
        _guard_driver_collect(self, "crosstab")
        merged = self.select(col1, col2).collectColumns()
        n = len(merged[col1])
        counts: Dict[Tuple[str, str], int] = {}
        for i in range(n):
            k = (str(merged[col1][i]), str(merged[col2][i]))
            counts[k] = counts.get(k, 0) + 1
        rows = sorted({a for a, _ in counts})
        col_vals = sorted({b for _, b in counts})
        label = f"{col1}_{col2}"
        if label in col_vals:
            # a col2 VALUE stringifying to the label name would silently
            # clobber the row-label column (dup names are unrepresentable)
            raise ValueError(
                f"crosstab: a {col2!r} value equals the label column "
                f"name {label!r}; rename a column first"
            )
        out: Dict[str, list] = {label: rows}
        for b in col_vals:
            out[b] = [counts.get((a, b), 0) for a in rows]
        return DataFrame.fromColumns(
            out, numPartitions=max(1, self.numPartitions)
        )

    def freqItems(self, cols, support: float = 0.01) -> "DataFrame":
        """Values occurring in more than ``support`` fraction of rows,
        per column, as one row of list cells named ``<col>_freqItems``
        (Spark ``freqItems``; computed exactly, which satisfies the
        approximate contract). Null cells never count."""
        if not 0.0 < float(support) <= 1.0:
            raise ValueError(f"support must be in (0, 1], got {support}")
        cols = list(cols)
        for c in cols:
            if c not in self._columns:
                raise KeyError(f"No such column {c!r}")
        _guard_driver_collect(self, "freqItems")
        merged = self.select(*cols).collectColumns()
        n = len(merged[cols[0]]) if cols else 0
        out: Dict[str, list] = {}
        for c in cols:
            counts: Dict[Any, int] = {}
            order: List[Any] = []
            for v in merged[c]:
                if v is None:
                    continue
                k = _cell_key(v)
                if k not in counts:
                    order.append((k, v))
                counts[k] = counts.get(k, 0) + 1
            out[f"{c}_freqItems"] = [[
                v for k, v in order if counts[k] > support * n
            ]]
        return DataFrame.fromColumns(out, numPartitions=1)

    def sampleBy(
        self, col: str, fractions: Dict[Any, float], seed: Any = None
    ) -> "DataFrame":
        """Stratified sample without replacement (Spark ``sampleBy``):
        each row kept with its stratum's fraction (absent strata keep
        nothing). Lazy, seed + partition deterministic."""
        if col not in self._columns:
            raise KeyError(f"No such column {col!r}")
        fr = {}
        for k, f in fractions.items():
            f = float(f)
            if not 0.0 <= f <= 1.0:
                raise ValueError(
                    f"fraction for stratum {k!r} outside [0, 1]: {f}"
                )
            fr[k] = f
        base_seed = (0 if seed is None else int(seed)) & (2 ** 64 - 1)

        def op(part: Partition, index: int) -> Partition:
            n = _part_num_rows(part)
            rng = np.random.default_rng(
                np.random.SeedSequence([base_seed, index])
            )
            u = rng.random(n)
            keys = part[col]
            keep = [
                i for i in range(n) if fr.get(keys[i], 0.0) > u[i]
            ]
            return {c: _take(part[c], keep) for c in part}

        op._indexed = True
        return self._with_op(op, self._columns)

    def _semi_join(
        self, other: "DataFrame", keys: List[str], anti: bool
    ) -> "DataFrame":
        """LEFT SEMI / LEFT ANTI join (Spark ``left_semi``/``left_anti``):
        keep left rows with at least one key match (semi) or none
        (anti); output = LEFT columns only, never duplicated by multiple
        matches. Null keys never match (SQL), so null-keyed left rows
        drop under semi and survive under anti, like Spark. Right-side
        non-key name collisions are irrelevant — no right column ever
        surfaces."""
        for k in keys:
            if k not in self._columns or k not in other._columns:
                raise KeyError(f"Join key {k!r} missing from a side")
        _guard_driver_collect(self, "join")
        _guard_driver_collect(other, "join")
        left = self.collectColumns()
        right = other.select(*keys).collectColumns()
        n_left = len(left[self._columns[0]]) if self._columns else 0
        n_right = len(right[keys[0]]) if keys else 0
        rkeys = [right[k] for k in keys]
        table = set()
        for j in range(n_right):
            # null-keyed right tuples may enter the set: a left tuple
            # with any null is excluded below, so they can never match
            table.add(tuple(_cell_key(col[j]) for col in rkeys))
        lkeys = [left[k] for k in keys]
        keep: List[int] = []
        for i in range(n_left):
            raw = [col[i] for col in lkeys]
            matched = not any(v is None for v in raw) and (
                tuple(_cell_key(v) for v in raw) in table
            )
            if matched != anti:
                keep.append(i)
        out = {c: _take(left[c], keep) for c in self._columns}
        return DataFrame.fromColumns(
            out, numPartitions=max(1, self.numPartitions)
        )

    def join(
        self,
        other: "DataFrame",
        on,
        how: str = "inner",
    ) -> "DataFrame":
        """Equi-join on key column(s) (Spark ``join``): ``how`` is
        'inner', 'left', 'right', or 'outer'/'full' (full outer). Null
        keys never match (SQL semantics). Non-key column names must not
        collide — rename with withColumnRenamed first (Spark would emit
        ambiguous duplicate columns; this engine refuses instead).

        Like orderBy, a join is a driver-side action: both sides'
        referenced columns are collected (TensorColumn blocks stay
        whole on the matched inner path).

        ``on`` may also be Column equality conditions
        (``df.join(d2, on=F.col("a") == F.col("b"))``, several combined
        with ``&`` or passed as a list): differing key names join by
        renaming the right key onto the left's, like the SQL layer.
        """
        if not isinstance(on, str):
            cand = list(on) if isinstance(on, (list, tuple)) else [on]
            if any(not isinstance(x, str) for x in cand):
                return self._join_on_columns(cand, other, how)
        keys = [on] if isinstance(on, str) else list(on)
        if not keys:
            raise ValueError("join needs at least one key column")
        aliases = {
            "left_outer": "left", "leftouter": "left",
            "right_outer": "right", "rightouter": "right",
            "full_outer": "outer", "fullouter": "outer", "full": "outer",
            "cross": "cross",
            "semi": "left_semi", "leftsemi": "left_semi",
            "anti": "left_anti", "leftanti": "left_anti",
        }
        how = aliases.get(how, how)
        if how == "cross":
            raise ValueError("Use crossJoin() for cross joins")
        if how in ("left_semi", "left_anti"):
            return self._semi_join(other, keys, anti=how == "left_anti")
        overlap_pre = (
            set(self._columns) & set(other._columns) - set(keys)
        )
        if overlap_pre:
            # BEFORE the right-join swap: qualification renames columns,
            # and the swap's reordering select must see the final names
            qualified = self._qualify_overlap(other, overlap_pre)
            if qualified is not None:
                left2, right2 = qualified
                return left2.join(right2, on=keys, how=how)
        if how == "right":
            # right join = left join with sides swapped, columns
            # reordered back to (left cols, right non-key cols)
            swapped = other.join(self, on=keys, how="left")
            order = list(self._columns) + [
                c for c in other._columns if c not in keys
            ]
            return swapped.select(*order)
        if how not in ("inner", "left", "outer"):
            raise ValueError(f"Unsupported join type {how!r}")
        for k in keys:
            if k not in self._columns or k not in other._columns:
                raise KeyError(f"Join key {k!r} missing from a side")
        overlap = (
            set(self._columns) & set(other._columns) - set(keys)
        )
        if overlap:
            raise ValueError(
                f"Ambiguous non-key columns on both sides: "
                f"{sorted(overlap)}; rename with withColumnRenamed "
                "first, or alias both frames (df.alias('x'))"
            )

        _guard_driver_collect(self, "join")
        _guard_driver_collect(other, "join")
        left = self.collectColumns()
        right = other.collectColumns()
        n_left = len(left[self._columns[0]]) if self._columns else 0
        n_right = len(right[other._columns[0]]) if other._columns else 0

        # hash the right side on the key tuple (None keys never match)
        table: Dict[Tuple, List[int]] = {}
        rkeys = [right[k] for k in keys]
        for j in range(n_right):
            kt = tuple(col[j] for col in rkeys)
            if any(v is None for v in kt):
                continue
            table.setdefault(kt, []).append(j)

        lkeys = [left[k] for k in keys]
        li: List[Optional[int]] = []
        ri: List[Optional[int]] = []
        matched_right: set = set()
        for i in range(n_left):
            kt = tuple(col[i] for col in lkeys)
            matches = (
                table.get(kt, []) if not any(v is None for v in kt) else []
            )
            if matches:
                for j in matches:
                    li.append(i)
                    ri.append(j)
                    matched_right.add(j)
            elif how in ("left", "outer"):
                li.append(i)
                ri.append(None)
        if how == "outer":
            # right rows nobody matched (incl. null-keyed ones) append
            # with a null left side, in right-side order (SQL FULL OUTER)
            for j in range(n_right):
                if j not in matched_right:
                    li.append(None)
                    ri.append(j)

        right_cols = [c for c in other._columns if c not in keys]
        out: Dict[str, Any] = {}
        if any(i is None for i in li):
            rkeys_by_col = {k: right[k] for k in keys}
            for c in self._columns:
                col = left[c]
                if c in rkeys_by_col:
                    # key columns surface the RIGHT key for right-only
                    # rows (one merged key column, Spark's using-join)
                    out[c] = [
                        rkeys_by_col[c][j] if i is None else col[i]
                        for i, j in zip(li, ri)
                    ]
                else:
                    out[c] = [
                        None if i is None else col[i] for i in li
                    ]
        else:
            idx = [i for i in li if i is not None]
            for c in self._columns:
                out[c] = _take(left[c], idx)
        if any(j is None for j in ri):
            # unmatched left rows pad the right side with None — boxed
            # lists, since a TensorColumn cannot hold nulls
            for c in right_cols:
                col = right[c]
                out[c] = [None if j is None else col[j] for j in ri]
        else:
            idx = [j for j in ri if j is not None]
            for c in right_cols:
                out[c] = _take(right[c], idx)
        return DataFrame.fromColumns(
            out, numPartitions=max(1, self.numPartitions)
        )

    def orderBy(
        self,
        *cols: str,
        ascending: Any = True,
    ) -> "DataFrame":
        """Globally sort rows by scalar key columns (Spark ``orderBy``).

        ``ascending``: bool or per-column list. Null ordering follows
        Spark: nulls first ascending, nulls last descending. A global
        sort necessarily materializes the keys on the driver; rows are
        re-partitioned into the same partition count afterwards.

        Keys may also be Columns: ``orderBy(F.col("x").desc(),
        (F.col("p") * F.col("q")).asc())`` — asc()/desc() markers win
        over ``ascending``; expression keys sort on hidden materialized
        columns, dropped afterwards.
        """
        if not cols:
            raise ValueError("orderBy needs at least one column")
        if any(not isinstance(c, str) for c in cols):
            from sparkdl_tpu.dataframe.column import Column

            asc_in = (
                list(ascending)
                if isinstance(ascending, (list, tuple))
                else [ascending] * len(cols)
            )
            if len(asc_in) != len(cols):
                raise ValueError(
                    f"ascending has {len(asc_in)} entries for "
                    f"{len(cols)} columns"
                )
            df = self
            names: List[str] = []
            asc_out: List[bool] = []
            tmp: List[str] = []
            for c, a in zip(cols, asc_in):
                if isinstance(c, str):
                    names.append(c)
                    asc_out.append(a)
                    continue
                if not isinstance(c, Column):
                    raise TypeError(
                        "orderBy keys are names or Columns, got "
                        f"{type(c).__name__}"
                    )
                if c._sort is not None:
                    a = c._sort
                    if c._sort_nulls is not None:
                        from sparkdl_tpu import sql as _sql

                        a = _sql.SortDir(c._sort, c._sort_nulls)
                plain = c._plain_name()
                if plain is not None:
                    names.append(plain)
                    asc_out.append(a)
                    continue
                # computed keys ALWAYS use a collision-proof temp name:
                # an expression whose canonical/alias name matches an
                # existing column must not silently sort by that column
                name = f"__ordcol_{len(tmp)}"
                df = df.withColumn(name, c)
                tmp.append(name)
                names.append(name)
                asc_out.append(a)
            out = df.orderBy(*names, ascending=asc_out)
            return out.drop(*tmp) if tmp else out
        asc = (
            list(ascending)
            if isinstance(ascending, (list, tuple))
            else [ascending] * len(cols)
        )
        if len(asc) != len(cols):
            raise ValueError(
                f"ascending has {len(asc)} entries for {len(cols)} columns"
            )
        for c in cols:
            if c not in self._columns:
                raise KeyError(f"Unknown column {c!r} in orderBy")
        # collectColumns keeps TensorColumn blocks whole, and _take
        # reorders them as one fancy-index — no per-row boxing for
        # non-key tensor columns (keys must be scalar columns).
        _guard_driver_collect(self, "orderBy")
        merged = self.collectColumns()
        n = len(merged[self._columns[0]]) if self._columns else 0
        order = list(range(n))
        # Stable multi-key sort: one pass per key, minor key first. The
        # (rank, value) tuple keeps None out of comparisons; the null
        # rank places nulls below (0) or above (2) every value, which
        # after `reverse` yields all four ASC/DESC x FIRST/LAST
        # combinations. Defaults are Spark's: first ascending, last
        # descending. An entry in `asc` may be a bool or a
        # sql.SortDir carrying an explicit NULLS FIRST/LAST.
        for c, a in list(zip(cols, asc))[::-1]:
            vals = merged[c]
            asc_b = bool(a)
            nulls_first = getattr(a, "nulls_first", None)
            if nulls_first is None:
                nulls_first = asc_b
            if asc_b:
                null_rank = 0 if nulls_first else 2
            else:  # reversed comparison flips the rank's effect
                null_rank = 2 if nulls_first else 0
            order.sort(
                key=lambda i: (
                    (null_rank, 0) if vals[i] is None else (1, vals[i])
                ),
                reverse=not asc_b,
            )
        sorted_cols = {c: _take(merged[c], order) for c in self._columns}
        return DataFrame.fromColumns(
            sorted_cols, numPartitions=max(1, self.numPartitions)
        )

    # -- execution ------------------------------------------------------------

    def _execute(self) -> List[Partition]:
        ops, cols = self._ops, self._columns

        def run(i, part):
            out = _run_plan(ops, cols, part, index=i)
            if isinstance(part, LazyPartition):
                # the result holds what it needs by reference; don't also
                # pin every decoded column in the source partition's cache
                part.release()
            return out

        return default_executor().map_partitions(
            run, self._source, count_rows=_part_num_rows
        )

    def cache(self) -> "DataFrame":
        """Execute the pending plan now; return a DataFrame over materialized
        partitions (Spark ``cache()`` + action semantics)."""
        return DataFrame(self._execute(), self._columns)

    def persist(self, storageLevel: Any = None) -> "DataFrame":
        """Spark ``persist``: one storage tier here (driver memory), so
        every level maps to :meth:`cache`; the argument is accepted for
        source compatibility."""
        del storageLevel
        return self.cache()

    def unpersist(self, blocking: bool = False) -> "DataFrame":
        """Spark ``unpersist``: materialized partitions are ordinary
        Python objects freed by refcounting, so this is a no-op that
        returns self (source compatibility)."""
        del blocking
        return self

    def checkpoint(self, eager: bool = True) -> "DataFrame":
        """Spark ``checkpoint``: truncate the pending-op lineage by
        materializing now. There is no lineage-recompute engine to
        protect against here, so eager/lazy both materialize."""
        del eager
        return self.cache()

    localCheckpoint = checkpoint

    def isLocal(self) -> bool:
        """True — every action runs in this process (Spark isLocal)."""
        return True

    @property
    def isStreaming(self) -> bool:
        """False — there is no structured-streaming engine here."""
        return False

    @property
    def sparkSession(self):
        """The active session (pyspark ``df.sparkSession``) — sessions
        are process-global here, so every frame shares the one active
        SparkSession (created on demand)."""
        from sparkdl_tpu.session import SparkSession

        # getOrCreate IS the singleton rule (returns the active
        # session when one exists) — no second spelling here
        return SparkSession.builder.getOrCreate()

    def inputFiles(self) -> List[str]:
        """Source file paths when the frame is file-backed (lazy
        parquet/Arrow scans record their paths); [] otherwise, like
        pyspark on a non-file source."""
        out: List[str] = []
        for p in self._source:
            path = getattr(p, "_path", None)  # Lazy*Partition attribute
            if path is not None:
                out.append(str(path))
        return out

    def to(self, schema) -> "DataFrame":
        """Conform to a schema's COLUMN LIST (pyspark 3.4 ``to``):
        reorder to the schema's names, adding null columns for names
        the frame lacks; types are accepted for source compat and
        ignored (dynamically typed engine)."""
        names = _schema_names(schema)
        df = self
        for c in names:
            if c not in df._columns:
                df = df.withColumn(c, lambda r: None)
        return df.select(*names)

    def sameSemantics(self, other: "DataFrame") -> bool:
        """Conservative plan identity (pyspark sameSemantics is also
        best-effort): True for the same object, or for frames over the
        SAME partition objects with the SAME op chain (element
        identity — ops are closures, so equality is identity) and
        columns. Never a false positive; false negatives are allowed,
        like pyspark's own analyzed-plan comparison."""
        if self is other:
            return True
        return (
            isinstance(other, DataFrame)
            and len(self._source) == len(other._source)
            and all(a is b for a, b in zip(self._source, other._source))
            and len(self._ops) == len(other._ops)
            and all(a is b for a, b in zip(self._ops, other._ops))
            and self._columns == other._columns
        )

    def semanticHash(self) -> int:
        return hash((
            tuple(map(id, self._source)),
            tuple(map(id, self._ops)),
            tuple(self._columns),
        ))

    def toJSON(self) -> List[str]:
        """One JSON document per row (Spark ``toJSON``, collected:
        there is no RDD layer to return)."""
        import json

        return [
            json.dumps(r.asDict(), default=str) for r in self.collect()
        ]

    def withMetadata(self, columnName: str, metadata: dict) -> "DataFrame":
        """Spark ``withMetadata``: column metadata has no consumer in
        this engine (no Catalyst optimizer); validated and dropped."""
        if columnName not in self._columns:
            raise KeyError(f"No such column {columnName!r}")
        if not isinstance(metadata, dict):
            raise TypeError("metadata must be a dict")
        return self

    def explain(self, extended: Any = None, mode: str = None) -> None:
        """Print the pending logical plan (Spark ``explain``): the
        source partition count and each queued partition-level op."""
        del extended, mode
        lines = [
            f"DataFrame[{', '.join(self._columns)}]",
            f"  partitions: {self.numPartitions}",
            f"  pending ops: {len(self._ops)}",
        ]
        for i, op in enumerate(self._ops):
            name = getattr(op, "__qualname__", repr(op))
            lines.append(f"    [{i}] {name}")
        print("\n".join(lines))

    def sample(self, *args, **kwargs) -> "DataFrame":
        """Random row sample without replacement (Spark ``sample``):
        each row kept independently with probability ``fraction``;
        deterministic for a given seed.

        Accepts both pyspark call forms: ``sample(fraction, seed=0)``
        and the legacy ``sample(withReplacement, fraction[, seed])``
        (with-replacement sampling is not supported and raises).
        """
        params = list(args)
        with_replacement = kwargs.pop("withReplacement", None)
        if params and isinstance(params[0], bool):
            with_replacement = params.pop(0)
        if with_replacement:
            raise NotImplementedError(
                "sample(withReplacement=True) is not supported"
            )
        fraction = kwargs.pop("fraction", None)
        if fraction is None:
            if not params:
                raise TypeError("sample() missing 'fraction'")
            fraction = params.pop(0)
        if "seed" in kwargs:
            seed = kwargs.pop("seed")
        else:
            seed = params.pop(0) if params else 0
        if params or kwargs:
            raise TypeError(
                f"sample() got unexpected arguments: {params or kwargs}"
            )
        if isinstance(fraction, bool) or not 0.0 <= float(fraction) <= 1.0:
            raise ValueError(f"fraction must be in [0, 1]: {fraction!r}")
        fraction = float(fraction)
        kept, _ = self.randomSplit(
            [fraction, 1.0 - fraction], seed=int(seed)
        )
        return kept

    def show(self, n: int = 20, truncate: int = 20) -> None:
        """Print the first ``n`` rows as an aligned text table (Spark
        ``show``). ``truncate``: max cell width (0 = no truncation);
        array/struct cells render as a shape/type summary."""

        def render(v):
            if v is None:
                return "null"
            if isinstance(v, np.ndarray):
                s = f"array{list(v.shape)}:{v.dtype}"
            elif isinstance(v, dict):
                s = "{" + ", ".join(sorted(v)) + "}"
            elif isinstance(v, float):
                s = f"{v:.6g}"
            else:
                s = str(v)
            if truncate and len(s) > truncate:
                if truncate <= 3:
                    s = s[:truncate]
                else:
                    s = s[: truncate - 3] + "..."
            return s

        # n+1 probe: detects truncation without a full count() pass (a
        # show() on an image frame must stay an O(n)-row action)
        rows = self.head(n + 1)
        more = len(rows) > n
        rows = rows[:n]
        cols = self._columns
        cells = [[render(r.get(c)) for c in cols] for r in rows]
        widths = [
            max(len(c), *(len(row[i]) for row in cells)) if cells else len(c)
            for i, c in enumerate(cols)
        ]
        sep = "+" + "+".join("-" * (w + 2) for w in widths) + "+"
        fmt = "|" + "|".join(f" {{:<{w}}} " for w in widths) + "|"
        print(sep)
        print(fmt.format(*cols))
        print(sep)
        for row in cells:
            print(fmt.format(*row))
        print(sep)
        if more:
            print(f"only showing top {len(rows)} rows")

    def describe(self, *cols: str) -> "DataFrame":
        """count/mean/stddev/min/max summary (Spark ``describe``).

        Defaults to every numeric column (incl. numpy scalar dtypes).
        Explicitly requested non-numeric columns get count/min/max with
        null mean/stddev, like pyspark.
        """
        import numbers

        merged = self.collectColumns()

        def is_num(v):
            return isinstance(v, numbers.Number) and not isinstance(
                v, bool
            )

        def all_numeric(c):
            vals = [v for v in merged[c] if v is not None]
            return bool(vals) and all(is_num(v) for v in vals)

        wanted = list(cols) if cols else [
            c for c in self._columns if all_numeric(c)
        ]
        for c in wanted:
            if c not in self._columns:
                raise KeyError(f"Unknown column {c!r} in describe")
        out: Dict[str, List[Any]] = {
            "summary": ["count", "mean", "stddev", "min", "max"]
        }
        for c in wanted:
            vals = merged[c]
            cnt = aggregate_values("count", vals)
            mean = (
                aggregate_values("avg", vals) if all_numeric(c) else None
            )
            std = None
            if mean is not None and cnt > 1:
                std = math.sqrt(
                    sum(
                        (v - mean) ** 2
                        for v in vals
                        if v is not None
                    )
                    / (cnt - 1)
                )
            try:
                lo = aggregate_values("min", vals)
                hi = aggregate_values("max", vals)
            except TypeError:  # unorderable mixed cells
                lo = hi = None
            out[c] = [cnt, mean, std, lo, hi]
        return DataFrame.fromColumns(out)

    def collect(self) -> List[Row]:
        rows: List[Row] = []
        parts = self._execute()
        with span("collect.box") as sp:
            for part in parts:
                n = _part_num_rows(part)
                for i in range(n):
                    rows.append(Row({c: part[c][i] for c in part}))
            sp.add(rows=len(rows))
        return rows

    def collectColumns(self) -> Dict[str, list]:
        """Collect as a single column-dict (driver-side concatenation).
        Columns that are TensorColumn blocks in every partition come back as
        ONE concatenated block (sequence-compatible, no per-row boxing)."""
        parts = self._execute()
        out: Dict[str, Any] = {}
        for c in self._columns:
            chunks = [part[c] for part in parts]
            if chunks and all(isinstance(ch, TensorColumn) for ch in chunks):
                out[c] = TensorColumn(
                    np.concatenate([ch.block for ch in chunks], axis=0)
                )
            else:
                vals: list = []
                for ch in chunks:
                    vals.extend(ch)
                out[c] = vals
        return out

    def count(self) -> int:
        if not self._ops:
            # metadata fast path: no decode, no execution
            return sum(self.partitionRowCounts())
        if any(isinstance(p, LazyPartition) for p in self._source):
            # a plan over file-backed partitions: stream + release so the
            # count never holds more than one decoded partition
            return sum(_part_num_rows(p) for p in self.iterPartitions())
        return sum(_part_num_rows(p) for p in self._execute())

    def _take_rows(self, n: int) -> List[Row]:
        """Execute the plan partition-by-partition, stopping as soon as n rows
        are gathered — head(1) on a large image frame decodes one partition,
        not the whole dataset."""
        ops, cols = self._ops, self._columns
        rows: List[Row] = []
        if n <= 0:
            return rows
        for pi, part in enumerate(self._source):
            cur = _run_plan(ops, cols, part, index=pi)
            m = _part_num_rows(cur)
            done = False
            for i in range(m):
                rows.append(Row({c: cur[c][i] for c in cur}))
                if len(rows) >= n:
                    done = True
                    break
            if isinstance(part, LazyPartition):
                # rows hold their own cell references; don't also pin the
                # partition's column cache (or its open file handle)
                part.release()
            if done:
                return rows
        return rows

    def head(self, n: int = 1) -> List[Row]:
        return self._take_rows(n)

    def limit(self, n: int) -> "DataFrame":
        rows = self._take_rows(n)
        return DataFrame.fromRows(rows, numPartitions=1) if rows else DataFrame(
            [], self._columns
        )

    def offset(self, n: int) -> "DataFrame":
        """Skip the first ``n`` rows (pyspark 3.4 ``DataFrame.offset``).
        Streams partitions and stops materializing once the skip is
        paid — O(partition) memory like limit."""
        if n < 0:
            raise ValueError(f"offset must be non-negative, got {n}")
        if n == 0:
            return self
        out_parts: List[Dict[str, list]] = []
        remaining = n
        for part in self.iterPartitions():
            rows = _part_num_rows(part)
            if remaining >= rows:
                remaining -= rows
                continue
            if remaining:
                part = {
                    c: _take(part[c], list(range(remaining, rows)))
                    for c in part
                }
                remaining = 0
            out_parts.append(part)
        if not out_parts:
            return DataFrame([], self._columns)
        # already-executed partitions ARE the new frame: no merge, no
        # repartition, tensor blocks stay columnar
        return DataFrame(out_parts, self._columns)

    def repartition(self, numPartitions: int) -> "DataFrame":
        cols = self.collectColumns()
        return DataFrame.fromColumns(cols, numPartitions)

    def repartitionByRange(self, numPartitions, *cols) -> "DataFrame":
        """Range partitioning (Spark ``repartitionByRange``): sort by
        the key columns (names or asc()/desc()-marked Columns; Spark's
        default ascending, nulls first) and slice the sorted rows into
        ``numPartitions`` contiguous ranges. Both pyspark overloads
        work — ``repartitionByRange(4, "v")`` and
        ``repartitionByRange("v")`` (keeping the current partition
        count). A global sort, so driver-side like :meth:`orderBy`."""
        if not isinstance(numPartitions, int) or isinstance(
            numPartitions, bool
        ):
            cols = (numPartitions,) + cols
            numPartitions = self.numPartitions
        if numPartitions < 1:
            raise ValueError("repartitionByRange needs >= 1 partition")
        if not cols:
            raise ValueError(
                "repartitionByRange needs at least one key column"
            )
        out = self.orderBy(*cols)
        return DataFrame.fromColumns(
            out.collectColumns(), numPartitions
        )

    def coalesce(self, numPartitions: int) -> "DataFrame":
        """Reduce the partition count (pyspark ``coalesce``): never
        increases it, and — unlike :meth:`repartition` — stays LAZY:
        contiguous source partitions group into concat-partitions whose
        pending ops run at first access, so a file-backed frame is not
        materialized driver-side at the coalesce call."""
        if numPartitions < 1:
            raise ValueError("coalesce needs at least one partition")
        n = self.numPartitions
        if numPartitions >= n:
            return self
        base, extra = divmod(n, numPartitions)
        parts = []
        idx = 0
        for b in range(numPartitions):
            size = base + (1 if b < extra else 0)
            parts.append(
                _CoalescedPartition(
                    self._source[idx: idx + size],
                    self._ops,
                    self._columns,
                    base_index=idx,
                )
            )
            idx += size
        return DataFrame(parts, self._columns)

    def melt(
        self,
        ids: Sequence[str],
        values: Optional[Sequence[str]] = None,
        variableColumnName: str = "variable",
        valueColumnName: str = "value",
    ) -> "DataFrame":
        """Unpivot (pyspark 3.4 ``melt``/``unpivot``, the inverse of
        pivot): id columns repeat, each value column becomes one output
        row as (variable, value). ``values`` defaults to every non-id
        column. Lazy per-partition expansion."""
        if isinstance(ids, str):
            ids = [ids]
        ids = list(ids)
        for c in ids:
            if c not in self._columns:
                raise KeyError(f"Unknown id column {c!r} in melt")
        if values is None:
            values = [c for c in self._columns if c not in ids]
        else:
            if isinstance(values, str):
                values = [values]
            values = list(values)
            for c in values:
                if c not in self._columns:
                    raise KeyError(f"Unknown value column {c!r} in melt")
        if not values:
            raise ValueError("melt needs at least one value column")
        out_cols = ids + [variableColumnName, valueColumnName]
        dups = {c for c in out_cols if out_cols.count(c) > 1}
        if dups:
            raise ValueError(
                f"melt output column collision: {sorted(dups)}; pick "
                "different variable/value names"
            )

        def op(part: Partition) -> Partition:
            n = _part_num_rows(part)
            out: Dict[str, list] = {c: [] for c in out_cols}
            for i in range(n):
                for vcol in values:
                    for idc in ids:
                        out[idc].append(part[idc][i])
                    out[variableColumnName].append(vcol)
                    out[valueColumnName].append(part[vcol][i])
            return out

        return self._with_op(op, out_cols)

    unpivot = melt  # pyspark offers both names

    def toDF(self, *names: str) -> "DataFrame":
        """Rename ALL columns positionally (pyspark ``toDF``). Unlike
        Spark (which tolerates duplicate output names), this frame's
        columns must stay unique — duplicates are rejected rather than
        silently dropping data."""
        if len(names) != len(self._columns):
            raise ValueError(
                f"toDF got {len(names)} names for {len(self._columns)} "
                "columns"
            )
        dups = {n for n in names if names.count(n) > 1}
        if dups:
            raise ValueError(
                f"toDF duplicate column name(s) {sorted(dups)}"
            )
        mapping = dict(zip(self._columns, names))

        def op(part: Partition) -> Partition:
            return {mapping[c]: part[c] for c in part}

        return self._with_op(op, list(names))

    def isEmpty(self) -> bool:
        """True when the frame has no rows (pyspark ``isEmpty``);
        stops at the first non-empty partition. Uses _take_rows'
        release discipline directly — an abandoned iterPartitions
        generator would skip the post-yield LazyPartition release and
        pin the column cache/file handle."""
        return not self._take_rows(1)

    def hint(self, name: str, *parameters) -> "DataFrame":
        """Accepted for pyspark API compatibility and IGNORED: this
        engine has one join strategy (driver-side hash), so broadcast/
        merge/shuffle hints have nothing to steer."""
        return self

    # -- streaming actions ----------------------------------------------------
    # Bounded-memory execution: one partition is materialized at a time and
    # released before the next (the Spark executor/iterator discipline) —
    # featurizing N images needs O(partition) driver memory, not O(N).

    def iterPartitions(
        self, order: Optional[Sequence[int]] = None
    ) -> Iterable[Partition]:
        """Execute the plan partition-by-partition, yielding each result and
        retaining none. Same bounded per-partition retry as the pooled
        executor path. ``order``: visit only these partition indices, in
        this order (the streaming trainer's epoch shuffle permutes here)."""
        from sparkdl_tpu.runtime.executor import PartitionTaskError

        ops, cols = self._ops, self._columns
        max_failures = default_executor().max_failures
        indices = range(len(self._source)) if order is None else order
        for i in indices:
            part = self._source[i]
            last_err = None
            for _attempt in range(max_failures):
                try:
                    result = _run_plan(ops, cols, part, index=i)
                    break
                except Exception as e:
                    last_err = e
            else:
                raise PartitionTaskError(i, max_failures, last_err)
            yield result
            if isinstance(part, LazyPartition):
                part.release()  # keep streaming passes bounded-memory

    def foreachPartition(self, fn: Callable[[Partition], None]) -> None:
        """Run ``fn`` over each executed partition, streaming (Spark
        ``foreachPartition``)."""
        for part in self.iterPartitions():
            fn(part)

    def _partition_to_arrow(self, part: Partition):
        import pyarrow as pa

        return pa.table(
            {c: to_arrow_array(part[c]) for c in self._columns if c in part}
        )

    def toArrowBatches(self) -> Iterable:
        """Streaming Arrow interchange: one Table per partition."""
        for part in self.iterPartitions():
            yield self._partition_to_arrow(part)

    def toArrow(self):
        """Whole-frame Arrow table. Tensor columns (contiguous blocks) are
        converted zero-copy as FixedShapeTensor arrays — no per-cell
        ``tolist`` boxing anywhere.

        Executes on the pooled executor and decides each column's Arrow type
        ONCE over the whole collected column (a filtered-empty or ragged
        partition can't produce a divergent per-partition schema)."""
        import pyarrow as pa

        cols = self.collectColumns()
        return pa.table({c: to_arrow_array(cols[c]) for c in self._columns})

    def writeCSV(self, path: str, header: bool = True) -> None:
        """Streaming CSV writer (pyspark ``df.write.csv`` analogue):
        one partition in memory at a time; nulls write as empty fields.
        Scalar columns only — tensor/list cells belong in parquet/Arrow."""
        import csv as _csv

        with open(path, "w", newline="") as f:
            w = _csv.writer(f)
            if header:
                w.writerow(self._columns)
            for part in self.iterPartitions():
                n = _part_num_rows(part)
                for i in range(n):
                    w.writerow(
                        [
                            "" if part[c][i] is None else part[c][i]
                            for c in self._columns
                        ]
                    )

    @staticmethod
    def readCSV(
        path: str,
        header: bool = True,
        inferSchema: bool = True,
        numPartitions: int = 1,
    ) -> "DataFrame":
        """CSV reader (pyspark ``spark.read.csv`` analogue): with
        ``inferSchema``, cells parse as int, then float, else string
        (pyspark's simple inference); empty fields are null. Without a
        header row, columns are named _c0.._cN like pyspark."""
        import csv as _csv

        def conv(s: str):
            if s == "":
                return None
            if not inferSchema:
                return s
            # STRICT numeric forms only: Python's int()/float() accept
            # underscores and surrounding whitespace, which would
            # silently corrupt ID-like string data ('12_34' -> 1234)
            if s != s.strip() or "_" in s:
                return s
            try:
                return int(s)
            except ValueError:
                pass
            try:
                return float(s)
            except ValueError:
                return s

        with open(path, newline="") as f:
            reader = _csv.reader(f)
            rows = [r for r in reader if r]  # skip blank lines
        if not rows:
            return DataFrame([], [])
        if header:
            names, data = list(rows[0]), rows[1:]
            dups = {n for n in names if names.count(n) > 1}
            if dups:
                raise ValueError(
                    f"readCSV: duplicate header column(s) {sorted(dups)}; "
                    "the frame requires unique names"
                )
        else:
            names = [f"_c{i}" for i in range(len(rows[0]))]
            data = rows
        cols = {
            name: [
                conv(r[i]) if i < len(r) else None for r in data
            ]
            for i, name in enumerate(names)
        }
        return DataFrame.fromColumns(cols, numPartitions=numPartitions)

    def writeJSON(self, path: str) -> None:
        """Streaming JSON-lines writer (pyspark ``df.write.json``):
        one object per line; null cells serialize as JSON null; list
        and dict cells serialize natively."""
        import json as _json

        with open(path, "w") as f:
            for part in self.iterPartitions():
                n = _part_num_rows(part)
                for i in range(n):
                    f.write(
                        _json.dumps(
                            {c: _json_cell(part[c][i]) for c in self._columns}
                        )
                    )
                    f.write("\n")

    @staticmethod
    def readJSON(path: str, numPartitions: int = 1) -> "DataFrame":
        """JSON-lines reader (pyspark ``spark.read.json``): the column
        set is the union of keys across lines (missing keys -> null),
        in first-seen order like pyspark's schema inference."""
        import json as _json

        records = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    records.append(_json.loads(line))
        if not records:
            return DataFrame([], [])
        names: List[str] = []
        for r in records:
            for k in r:
                if k not in names:
                    names.append(k)
        cols = {c: [r.get(c) for r in records] for c in names}
        return DataFrame.fromColumns(cols, numPartitions=numPartitions)

    def writeParquet(self, path: str) -> None:
        """Streaming parquet writer: partitions are executed, converted, and
        written one at a time (bounded memory for ImageNet-scale frames).
        Empty partitions are skipped; every written partition must convert
        to the schema established by the first one (a partition whose cells
        pack differently — e.g. ragged where others are uniform — raises
        with a clear error rather than writing a corrupt file)."""
        import pyarrow as pa
        import pyarrow.parquet as pq

        writer = None
        try:
            for part in self.iterPartitions():
                if _part_num_rows(part) == 0:
                    continue
                table = self._partition_to_arrow(part)
                if writer is None:
                    writer = pq.ParquetWriter(path, table.schema)
                elif table.schema != writer.schema:
                    try:
                        table = table.cast(writer.schema)
                    except (
                        pa.ArrowInvalid,
                        pa.ArrowNotImplementedError,
                        pa.ArrowTypeError,
                    ) as e:
                        raise ValueError(
                            "writeParquet: partition schema diverged from "
                            f"the first partition's ({table.schema} vs "
                            f"{writer.schema}); make the column uniformly "
                            "shaped (or repartition(1) to force a single "
                            "global conversion)"
                        ) from e
                writer.write_table(table)
            if writer is None:  # no non-empty partition: still a valid file
                empty = self._partition_to_arrow(
                    {c: [] for c in self._columns}
                )
                writer = pq.ParquetWriter(path, empty.schema)
                writer.write_table(empty)
        finally:
            if writer is not None:
                writer.close()

    def toPandas(self):
        return self.toArrow().to_pandas()

    @property
    def write(self):
        """pyspark's writer namespace: ``df.write.parquet(path)`` /
        ``.csv`` / ``.json``, with ``.mode('errorifexists')``."""
        from sparkdl_tpu.session import DataFrameWriter

        return DataFrameWriter(self)

    def mapInPandas(self, func, schema) -> "DataFrame":
        """Per-partition pandas transform (pyspark ``mapInPandas``):
        ``func`` receives an ITERATOR of pandas DataFrames (one per
        partition here) and yields output DataFrames; row counts may
        change. ``schema`` declares the OUTPUT column names — a list,
        or a DDL-ish string ("id long, name string"; types are
        accepted for pyspark source compat and ignored, the engine's
        columns are dynamically typed). Lazy, partition-local."""
        out_cols = _schema_names(schema)

        def op(part: Partition) -> Partition:
            import pandas as pd

            pdf = pd.DataFrame({c: list(part[c]) for c in part})
            frames = list(func(iter([pdf])))
            for f in frames:
                if not isinstance(f, pd.DataFrame):
                    raise TypeError(
                        "mapInPandas function must yield pandas "
                        f"DataFrames, got {type(f).__name__}"
                    )
                # validate EACH yielded frame: concat's column union
                # would silently NaN-fill a frame missing a declared
                # column when any sibling frame has it
                missing = [c for c in out_cols if c not in f.columns]
                if missing:
                    raise ValueError(
                        f"mapInPandas output is missing declared "
                        f"columns {missing}; got {list(f.columns)}"
                    )
            if not frames:
                return {c: [] for c in out_cols}
            out = pd.concat(frames, ignore_index=True)
            return {c: _pandas_cells(out[c]) for c in out_cols}

        return self._with_op(op, list(out_cols))

    def mapInArrow(self, func, schema) -> "DataFrame":
        """Per-partition Arrow transform (pyspark ``mapInArrow``):
        ``func`` receives an ITERATOR of pyarrow RecordBatches (one
        per partition here) and yields RecordBatches; row counts may
        change. ``schema`` declares the OUTPUT column names (types
        accepted for source compat and ignored). Lazy,
        partition-local, zero pandas in the loop."""
        out_cols = _schema_names(schema)

        def op(part: Partition) -> Partition:
            import pyarrow as pa

            batch = pa.RecordBatch.from_pydict(
                {c: list(part[c]) for c in part}
            )
            out_batches = list(func(iter([batch])))
            cols: Dict[str, list] = {c: [] for c in out_cols}
            for b in out_batches:
                if not isinstance(b, pa.RecordBatch):
                    raise TypeError(
                        "mapInArrow function must yield pyarrow "
                        f"RecordBatches, got {type(b).__name__}"
                    )
                names = set(b.schema.names)
                missing = [c for c in out_cols if c not in names]
                if missing:
                    raise ValueError(
                        f"mapInArrow output is missing declared "
                        f"columns {missing}; got {b.schema.names}"
                    )
                for c in out_cols:
                    cols[c].extend(b.column(c).to_pylist())
            return cols

        return self._with_op(op, list(out_cols))



# aliases normalize before dispatch: Spark's _samp spellings ARE the
# defaults, and approx_count_distinct runs exact here (rsd accepted and
# ignored — the driver-scale engine has no need for HyperLogLog)
_AGG_ALIASES = {
    "stddev_samp": "stddev",
    "var_samp": "variance",
    "approx_count_distinct": "count_distinct",
    "every": "bool_and",
    "any_value": "first",
}


def _agg_spec_key(fn: str, params) -> str:
    """Encode call-level parameters into the spec's fn string
    ('percentile:[0.5]') — the (fn, col) spec tuple is the only channel
    the streaming engine sees. Paired with :func:`_agg_params`; both
    the SQL planner and GroupedData._agg_columns encode through here."""
    if params is None:
        return fn
    import json

    return fn + ":" + json.dumps(params)


def _agg_base_fn(fn: str) -> str:
    """The base name of a (possibly parameterized) spec key — CHEAP,
    for the per-row update path (no JSON decode)."""
    return fn.split(":", 1)[0] if ":" in fn else fn


def _agg_params(fn: str):
    """Decode a spec key into (base_fn, params); only the finalization
    path needs the decoded parameters."""
    if ":" in fn:
        import json

        base, blob = fn.split(":", 1)
        return base, json.loads(blob)
    return fn, None


def _agg_init(fn: str):
    fn = _agg_base_fn(fn)
    fn = _AGG_ALIASES.get(fn, fn)
    if fn in ("stddev_pop", "var_pop"):
        return (0, 0.0, 0.0)  # Welford, population finalization
    if fn in ("skewness", "kurtosis"):
        return (0, 0.0, 0.0, 0.0, 0.0)  # (n, mean, M2, M3, M4)
    if fn == "sum_distinct":
        return set()
    if fn in ("percentile", "percentile_approx"):
        return []  # exact: holds the group's values, like median
    if fn in ("corr", "covar_pop", "covar_samp"):
        # online co-moments over packed [x, y] cells:
        # (n, mean_x, mean_y, C_xy, M2_x, M2_y)
        return (0, 0.0, 0.0, 0.0, 0.0, 0.0)
    if fn in ("bool_and", "bool_or"):
        return None  # null when no non-null inputs (Spark)
    if fn == "mode":
        return {}  # value -> [count, first_seen_index, value]
    if fn == "count":
        return 0
    if fn == "count_distinct":
        return set()  # cell keys seen; memory O(distinct values)
    if fn == "avg":
        return (None, 0)  # (running sum, non-null count)
    if fn in ("stddev", "variance"):
        return (0, 0.0, 0.0)  # Welford: (n, mean, M2)
    if fn in ("sum", "min", "max"):
        return None
    if fn == "collect_list":
        return []  # memory O(values) per group, documented
    if fn == "median":
        return []  # exact median: holds the group's values
    if fn == "collect_set":
        return ([], set())  # (first-occurrence order, seen cell keys)
    if fn in ("first", "last"):
        return (False, None)  # (seen a non-null, value)
    raise ValueError(
        f"Unknown aggregate {fn!r}; see sql._AGGREGATES for the "
        "supported set"
    )


def _agg_update(fn: str, acc, v, star: bool):
    fn = _agg_base_fn(fn)  # no JSON decode on the per-row hot path
    fn = _AGG_ALIASES.get(fn, fn)
    if fn == "count":
        return acc + (1 if star or v is not None else 0)
    if v is None:  # SUM/AVG/MIN/MAX/COUNT(DISTINCT) skip nulls
        return acc
    if fn in ("stddev_pop", "var_pop"):
        n, mean, m2 = acc
        n += 1
        d = v - mean
        mean += d / n
        m2 += d * (v - mean)
        return (n, mean, m2)
    if fn in ("skewness", "kurtosis"):
        # one-pass central moments (Pebay's update), numerically stable
        n1, mean, m2, m3, m4 = acc
        n = n1 + 1
        d = v - mean
        dn = d / n
        dn2 = dn * dn
        t1 = d * dn * n1
        mean += dn
        m4 += t1 * dn2 * (n * n - 3 * n + 3) + 6 * dn2 * m2 - 4 * dn * m3
        m3 += t1 * dn * (n - 2) - 3 * dn * m2
        m2 += t1
        return (n, mean, m2, m3, m4)
    if fn == "sum_distinct":
        acc.add(v)
        return acc
    if fn in ("percentile", "percentile_approx"):
        acc.append(v)
        return acc
    if fn in ("corr", "covar_pop", "covar_samp"):
        # v is a packed [x, y] cell; a null in EITHER slot skips the
        # pair (Spark drops incomplete observations)
        if not isinstance(v, (list, tuple)) or len(v) != 2:
            return acc
        x, y = v
        if x is None or y is None:
            return acc
        n, mx, my, cxy, m2x, m2y = acc
        n += 1
        dx = x - mx
        mx += dx / n
        # UPDATED mean_x against the PREVIOUS mean_y — the standard
        # online co-moment update; using the stale dx here inflates C
        cxy += (x - mx) * (y - my)
        dy = y - my
        my += dy / n
        m2x += dx * (x - mx)
        m2y += dy * (y - my)
        return (n, mx, my, cxy, m2x, m2y)
    if fn in ("bool_and", "bool_or"):
        b = bool(v)
        if acc is None:
            return b
        return (acc and b) if fn == "bool_and" else (acc or b)
    if fn == "mode":
        key = _cell_key(v)
        ent = acc.get(key)
        if ent is None:
            acc[key] = [1, len(acc), v]
        else:
            ent[0] += 1
        return acc
    if fn == "count_distinct":
        acc.add(_cell_key(v))
        return acc
    if fn == "sum":
        return v if acc is None else acc + v
    if fn == "avg":
        s, c = acc
        return (v if s is None else s + v, c + 1)
    if fn in ("stddev", "variance"):
        n, mean, m2 = acc
        n += 1
        d = v - mean
        mean += d / n
        m2 += d * (v - mean)
        return (n, mean, m2)  # Welford: numerically stable streaming
    if fn == "min":
        return v if acc is None or v < acc else acc
    if fn == "max":
        return v if acc is None or v > acc else acc
    if fn in ("collect_list", "median"):
        acc.append(v)
        return acc
    if fn == "collect_set":
        order, seen = acc
        key = _cell_key(v)
        if key not in seen:
            seen.add(key)
            order.append(v)
        return acc
    if fn == "first":
        return acc if acc[0] else (True, v)
    if fn == "last":
        return (True, v)
    raise ValueError(
        f"Unknown aggregate {fn!r}; see sql._AGGREGATES for the "
        "supported set"
    )


def _percentile_of(s, p: float, discrete: bool):
    """p in [0, 1] over SORTED s: continuous linear interpolation
    (Spark percentile) or the actual element at ceil(p*n)-1 (Spark
    percentile_approx with exact accuracy)."""
    n = len(s)
    if discrete:
        idx = max(0, min(n - 1, math.ceil(p * n) - 1))
        return s[idx]
    pos = p * (n - 1)
    lo = int(math.floor(pos))
    hi = int(math.ceil(pos))
    if lo == hi:
        return s[lo]
    frac = pos - lo
    return s[lo] * (1 - frac) + s[hi] * frac


def _agg_final(fn: str, acc):
    fn, params = _agg_params(fn)
    fn = _AGG_ALIASES.get(fn, fn)
    if fn in ("stddev_pop", "var_pop"):
        n, _, m2 = acc
        if n < 1:
            return None
        var = m2 / n
        return math.sqrt(var) if fn == "stddev_pop" else var
    if fn in ("skewness", "kurtosis"):
        n, _, m2, m3, m4 = acc
        if n < 1:
            return None
        if m2 == 0:
            return float("nan")  # zero variance (Spark divides by it)
        if fn == "skewness":
            return math.sqrt(n) * m3 / m2 ** 1.5
        return n * m4 / (m2 * m2) - 3.0  # excess kurtosis (Spark)
    if fn == "sum_distinct":
        return sum(acc) if acc else None
    if fn in ("percentile", "percentile_approx"):
        if not acc:
            return None
        s = sorted(acc)
        discrete = fn == "percentile_approx"
        pcts = params[0] if params else 0.5
        if isinstance(pcts, list):
            return [_percentile_of(s, float(p), discrete) for p in pcts]
        return _percentile_of(s, float(pcts), discrete)
    if fn in ("corr", "covar_pop", "covar_samp"):
        n, _, _, cxy, m2x, m2y = acc
        if fn == "covar_pop":
            return None if n < 1 else cxy / n
        if fn == "covar_samp":
            return None if n < 2 else cxy / (n - 1)
        if n < 1:
            return None
        den = math.sqrt(m2x * m2y)
        return float("nan") if den == 0 else cxy / den
    if fn in ("bool_and", "bool_or"):
        return acc
    if fn == "mode":
        if not acc:
            return None
        # highest count wins; ties break on first occurrence (Spark
        # leaves tie order undefined)
        return min(acc.values(), key=lambda e: (-e[0], e[1]))[2]
    if fn == "avg":
        s, c = acc
        return None if c == 0 else s / c
    if fn in ("stddev", "variance"):
        # sample statistics (Spark's stddev = stddev_samp); fewer than
        # two non-null values -> null
        n, _, m2 = acc
        if n < 2:
            return None
        var = m2 / (n - 1)
        return math.sqrt(var) if fn == "stddev" else var
    if fn == "count_distinct":
        return len(acc)
    if fn == "collect_list":
        # COPY: running-frame windows snapshot per row while the same
        # accumulator keeps growing — the live list must not leak out
        return list(acc)
    if fn == "median":
        if not acc:
            return None
        if any(
            isinstance(x, bool) or not isinstance(x, (int, float))
            for x in acc
        ):
            # a clear error on ANY group shape — not a data-dependent
            # crash only when a group happens to have an even count
            raise ValueError(
                "median requires numeric values (Spark rejects "
                "non-numeric median at analysis time)"
            )
        s = sorted(acc)
        n = len(s)
        mid = n // 2
        # Spark median = percentile(0.5): midpoint interpolation
        return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2
    if fn == "collect_set":
        return list(acc[0])  # first-occurrence order (Spark: undefined)
    if fn in ("first", "last"):
        return acc[1]
    return acc


def streaming_group_agg(
    df: "DataFrame",
    keys: Sequence[str],
    specs: Sequence[Tuple[str, Optional[str]]],
):
    """Grouped aggregation streamed partition-at-a-time: memory is
    O(groups), never O(rows) — the scale path for GROUP BY over
    ImageNet-sized frames (shared by ``GroupedData.agg`` and the SQL
    layer). ``specs`` is ``[(fn, col)]`` with ``col=None`` for COUNT(*).
    Exception: ``count_distinct`` holds a per-group set of distinct
    cell keys — memory O(distinct values), worst case O(rows) on a
    mostly-unique column.

    Returns ``(key_rows, agg_columns)``: the original key-value tuples in
    first-appearance order, and one value list per spec. Null semantics
    match :func:`aggregate_values` exactly; group identity uses
    :func:`_cell_key`, so tensor/struct keys group by content."""
    keys = list(keys)
    needed = sorted(set(keys) | {c for _, c in specs if c is not None})
    if not needed and not df._ops:
        # pure COUNT(*) on an op-free frame: a row count needs no column
        # data at all — answer from metadata (parquet footers / column
        # lengths), zero decode
        total = sum(df.partitionRowCounts())
        return [()], [[total] for _ in specs]
    proj = df.select(*needed) if needed else df
    groups: Dict[Tuple, list] = {}  # cell-key tuple -> [orig_keys, accs]
    order: List[Tuple] = []
    for part in proj.iterPartitions():
        m = _part_num_rows(part)
        keycols = [part[k] for k in keys]
        speccols = [
            part[c] if c is not None else None for _, c in specs
        ]
        for i in range(m):
            kt_orig = tuple(col[i] for col in keycols)
            kt = tuple(_cell_key(v) for v in kt_orig)
            g = groups.get(kt)
            if g is None:
                g = groups[kt] = [
                    kt_orig, [_agg_init(fn) for fn, _ in specs]
                ]
                order.append(kt)
            accs = g[1]
            for j, (fn, c) in enumerate(specs):
                v = None if speccols[j] is None else speccols[j][i]
                accs[j] = _agg_update(fn, accs[j], v, star=c is None)
    if not keys and not groups:
        # global aggregate over zero rows still yields ONE row (Spark's
        # one-row global-aggregate semantics)
        groups[()] = [(), [_agg_init(fn) for fn, _ in specs]]
        order.append(())
    key_rows = [groups[kt][0] for kt in order]
    agg_columns = [
        [_agg_final(fn, groups[kt][1][j]) for kt in order]
        for j, (fn, _) in enumerate(specs)
    ]
    return key_rows, agg_columns


def aggregate_values(fn: str, values) -> Any:
    """One SQL-style aggregate over raw values: COUNT counts non-nulls;
    SUM/AVG/MIN/MAX skip nulls and return null for empty/all-null input.
    Thin wrapper over the streaming accumulators, so the one-shot and
    streamed paths cannot drift."""
    acc = _agg_init(fn)
    for v in values:
        acc = _agg_update(fn, acc, v, star=False)
    return _agg_final(fn, acc)


def _json_cell(v):
    """JSON-serializable form of a cell: numpy scalars/arrays unwrap,
    recursively through list/tuple/dict cells (embedding lists hold
    numpy floats in the pipelines this library targets)."""
    if isinstance(v, np.generic):
        return v.item()
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, (list, tuple)):
        return [_json_cell(x) for x in v]
    if isinstance(v, dict):
        return {k: _json_cell(x) for k, x in v.items()}
    return v


class _NAFunctions:
    """pyspark's ``DataFrameNaFunctions``: the ``df.na`` accessor."""

    def __init__(self, df: DataFrame):
        self._df = df

    def drop(
        self,
        how: str = "any",
        thresh: Optional[int] = None,
        subset: Optional[Sequence[str]] = None,
    ) -> DataFrame:
        return self._df.dropna(how=how, thresh=thresh, subset=subset)

    def fill(
        self, value, subset: Optional[Sequence[str]] = None
    ) -> DataFrame:
        return self._df.fillna(value, subset=subset)

    def replace(self, to_replace, value=None, subset=None) -> DataFrame:
        return self._df.replace(to_replace, value, subset)


class DataFrameStatFunctions:
    """``df.stat`` namespace (pyspark DataFrameStatFunctions): thin
    delegation onto the DataFrame's own statistics methods."""

    def __init__(self, df: DataFrame):
        self._df = df

    def approxQuantile(self, col, probabilities, relativeError=0.0):
        return self._df.approxQuantile(col, probabilities, relativeError)

    def corr(self, col1: str, col2: str, method: str = "pearson"):
        if method != "pearson":
            raise ValueError(
                f"Only pearson correlation is supported (pyspark "
                f"likewise), got {method!r}"
            )
        return self._df.corr(col1, col2)

    def cov(self, col1: str, col2: str):
        return self._df.cov(col1, col2)

    def crosstab(self, col1: str, col2: str) -> DataFrame:
        return self._df.crosstab(col1, col2)

    def freqItems(self, cols, support: float = 0.01) -> DataFrame:
        return self._df.freqItems(cols, support)

    def sampleBy(self, col, fractions, seed=None) -> DataFrame:
        return self._df.sampleBy(col, fractions, seed)


class GroupedData:
    """Result of :meth:`DataFrame.groupBy` — pyspark's dict-form ``agg``.

    ``agg({"score": "avg", "*": "count"})`` yields one row per group
    with columns named ``avg(score)`` / ``count(*)`` after the group
    keys. Null is a valid group key; aggregate null semantics follow
    :func:`aggregate_values`. Unlike orderBy/join, aggregation STREAMS
    partition-at-a-time over only the referenced columns — memory is
    O(groups), so it works at any row count.
    """

    def __init__(
        self, df: DataFrame, keys: List[str], mode: str = "groupby",
        explicit_sets: Optional[List[Tuple[str, ...]]] = None,
    ):
        self._df = df
        self._keys = keys
        self._mode = mode  # 'groupby' | 'rollup' | 'cube' | 'sets'
        self._explicit_sets = explicit_sets

    def _grouping_sets(self) -> List[Tuple[str, ...]]:
        """The key subsets this grouping mode aggregates over, FULL set
        first (it defines the output schema for the union)."""
        keys = tuple(self._keys)
        if self._mode == "rollup":
            return [keys[:i] for i in range(len(keys), -1, -1)]
        if self._mode == "cube":
            import itertools as _it

            sets: List[Tuple[str, ...]] = []
            for r in range(len(keys), -1, -1):
                sets.extend(_it.combinations(keys, r))
            return sets
        if self._mode == "sets":
            return list(self._explicit_sets or [])
        return [keys]

    def agg(self, *exprs) -> DataFrame:
        """Two pyspark forms: the dict form
        (``agg({"score": "avg", "*": "count"})``) and the Column form
        (``agg(F.sum("v").alias("s"), F.countDistinct("k"))``, aggregate
        args may be expressions — ``F.sum(F.col("p") * F.col("q"))``).

        Under rollup/cube, the aggregation runs once per grouping set
        (each a streamed groupBy) and unions the results with
        null-filled key columns on subtotal rows, like SQL GROUP BY
        ROLLUP/CUBE."""
        if self._mode != "groupby":
            frames: List[DataFrame] = []
            out_cols: Optional[List[str]] = None
            for s in self._grouping_sets():
                part = GroupedData(self._df, list(s)).agg(*exprs)
                if out_cols is None:  # full-key frame defines the schema
                    out_cols = list(self._keys) + [
                        c for c in part.columns if c not in self._keys
                    ]
                for k in self._keys:
                    if k not in part.columns:
                        part = part.withColumn(k, lambda r: None)
                frames.append(part.select(*out_cols))
            df = frames[0]
            for f in frames[1:]:
                df = df.unionAll(f)
            return df
        if len(exprs) == 1 and isinstance(exprs[0], dict):
            return self._agg_dict(exprs[0])
        if not exprs:
            raise ValueError("agg needs at least one aggregate")
        return self._agg_columns(list(exprs))

    def _agg_columns(self, exprs: list) -> DataFrame:
        from sparkdl_tpu import sql as _sql
        from sparkdl_tpu.dataframe.column import Column

        df = self._df
        specs: List[Tuple[str, Optional[str]]] = []
        names: List[str] = []
        for c in exprs:
            if not isinstance(c, Column):
                raise TypeError(
                    "agg() takes aggregate Columns (F.sum, F.count, ...)"
                    f" or one dict, got {type(c).__name__}"
                )
            e = c._expr
            if not (
                isinstance(e, _sql.Call)
                and e.fn.lower() in _sql._AGGREGATES
            ):
                raise ValueError(
                    f"agg() Columns must be single aggregate calls; got "
                    f"{c._output_name()!r}"
                )
            fn = e.fn.lower()
            if e.distinct:
                fn = "sum_distinct" if fn == "sum" else "count_distinct"
            fn = _agg_spec_key(fn, getattr(e, "_params", None))
            if e.arg == "*":
                if fn != "count":
                    raise ValueError(f"{fn}(*) is not valid; only count(*)")
                col = None
            elif isinstance(e.arg, _sql.Col):
                col = e.arg.name
                if col not in df.columns:
                    raise KeyError(f"Unknown column {col!r} in agg")
            else:
                # aggregate over an expression: validate column refs
                # eagerly (a typo must fail at plan time, not as a
                # retried partition task) and materialize the arg under
                # the SQL planner's collision-proof helper name
                _sql._check_expr_columns(e.arg, df.columns)
                col = f"__sql_aggarg_{_sql._expr_name(e.arg)}"
                if col not in df.columns:
                    df = _sql._apply_expr(df, e.arg, col)
            specs.append((fn, col))
            names.append(c._alias or _sql._expr_name(e))
        dups = {n for n in names if names.count(n) > 1}
        if dups:
            raise ValueError(
                f"Duplicate aggregate output name(s) {sorted(dups)}; "
                "disambiguate with .alias()"
            )
        key_rows, agg_cols = streaming_group_agg(df, self._keys, specs)
        out: Dict[str, List[Any]] = {
            k: [kr[j] for kr in key_rows]
            for j, k in enumerate(self._keys)
        }
        for name, vals in zip(names, agg_cols):
            if name in out:
                raise ValueError(f"Duplicate aggregate column {name!r}")
            out[name] = vals
        return DataFrame.fromColumns(out)

    def _agg_dict(self, exprs: Dict[str, str]) -> DataFrame:
        if not exprs:
            raise ValueError("agg needs at least one column: fn entry")
        from sparkdl_tpu import sql as _sql

        for col, fn in exprs.items():
            if (
                fn.lower() not in _sql._AGGREGATES
                and fn.lower() != "count_distinct"
            ) or fn.lower() in (
                # parameterized/two-column forms need the Column API
                "percentile", "percentile_approx", "corr", "covar_pop",
                "covar_samp",
            ):
                raise ValueError(f"Unknown aggregate {fn!r} for {col!r}")
            if col != "*" and col not in self._df.columns:
                raise KeyError(f"Unknown column {col!r} in agg")
            if col == "*" and fn.lower() != "count":
                raise ValueError(f"{fn}(*) is not valid; only count(*)")

        specs = [
            (fn.lower(), None if col == "*" else col)
            for col, fn in exprs.items()
        ]
        key_rows, agg_cols = streaming_group_agg(
            self._df, self._keys, specs
        )
        out: Dict[str, List[Any]] = {
            k: [kr[j] for kr in key_rows]
            for j, k in enumerate(self._keys)
        }
        for (fn, col), vals in zip(specs, agg_cols):
            name = f"{fn}(*)" if col is None else f"{fn}({col})"
            if name in out:
                raise ValueError(f"Duplicate aggregate column {name!r}")
            out[name] = vals
        return DataFrame.fromColumns(out)

    def pivot(
        self, pivot_col: str, values: Optional[List[Any]] = None
    ) -> "PivotedGroupedData":
        """Pivot a column's values into output columns (pyspark
        ``groupBy(...).pivot(col[, values]).agg(...)``). ``values``
        fixes the output columns; omitted, distinct observed values are
        discovered (and sorted) from the data like pyspark does."""
        if pivot_col not in self._df.columns:
            raise KeyError(f"Unknown column {pivot_col!r} in pivot")
        if pivot_col in self._keys:
            raise ValueError(
                f"pivot column {pivot_col!r} is already a group key"
            )
        return PivotedGroupedData(
            self._df, self._keys, pivot_col,
            list(values) if values is not None else None,
        )

    def applyInPandas(self, func, schema) -> DataFrame:
        """Grouped-map pandas transform (pyspark ``applyInPandas``):
        ``func`` receives each group as ONE pandas DataFrame (keys
        included) and returns a DataFrame; outputs concatenate in
        first-occurrence group order. ``schema`` declares the output
        columns (list or DDL string, types ignored). Driver-side like
        join/orderBy — the whole frame is collected (collect-guarded);
        memory O(rows)."""
        if self._mode != "groupby":
            raise ValueError(
                "applyInPandas works on groupBy(), not rollup/cube"
            )
        if not self._keys:
            raise ValueError("applyInPandas needs grouping keys")
        import pandas as pd

        out_cols = _schema_names(schema)
        # pyspark dispatches on the function's arity: func(pdf) or
        # func(key, pdf) where key is the raw grouping-value tuple
        wants_key = _sniff_pos_arity(func, default=1) >= 2
        df = self._df
        merged, groups, order, raw_keys = _collect_groups(
            df, self._keys, "applyInPandas"
        )
        frames = []
        for kt in order:
            idxs = groups[kt]
            pdf = pd.DataFrame({
                c: [merged[c][i] for i in idxs] for c in df.columns
            })
            out = func(raw_keys[kt], pdf) if wants_key else func(pdf)
            frames.append(
                _validated_pandas_frame(out, out_cols, "applyInPandas")
            )
        return _assemble_pandas_output(frames, out_cols, df.numPartitions)

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        """Pair two grouped frames by key for a joint pandas transform
        (pyspark ``groupBy(...).cogroup(other.groupBy(...))``); the two
        key lists must have equal length (names may differ — keys pair
        positionally, like pyspark)."""
        if not isinstance(other, GroupedData):
            raise TypeError(
                f"cogroup takes a GroupedData, got {type(other).__name__}"
            )
        if self._mode != "groupby" or other._mode != "groupby":
            raise ValueError("cogroup works on groupBy(), not rollup/cube")
        if len(self._keys) != len(other._keys) or not self._keys:
            raise ValueError(
                "cogroup needs the same number of (non-zero) grouping "
                f"keys on both sides; got {self._keys} vs {other._keys}"
            )
        return CoGroupedData(self, other)

    def count(self) -> DataFrame:
        """Group sizes as a ``count`` column (pyspark ``groupBy().count()``)."""
        return self.agg({"*": "count"}).withColumnRenamed("count(*)", "count")

    def avg(self, *cols: str) -> DataFrame:
        return self.agg({c: "avg" for c in cols})

    mean = avg  # pyspark alias

    def sum(self, *cols: str) -> DataFrame:
        return self.agg({c: "sum" for c in cols})

    def min(self, *cols: str) -> DataFrame:
        return self.agg({c: "min" for c in cols})

    def max(self, *cols: str) -> DataFrame:
        return self.agg({c: "max" for c in cols})


def _sniff_pos_arity(func, default: int) -> int:
    """Positional-parameter count of a pandas-transform callable —
    pyspark dispatches func(pdf) vs func(key, pdf) (and the cogroup
    pair forms) on it; unsniffable callables get the default."""
    import inspect

    try:
        return len([
            p
            for p in inspect.signature(func).parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
        ])
    except (TypeError, ValueError):
        return default


def _collect_groups(df: "DataFrame", keys, what: str):
    """Driver-side grouping shared by applyInPandas and cogroup:
    collect (guarded), bucket row indexes by _cell_key tuples, keep
    first-occurrence order and the raw key values."""
    _guard_driver_collect(df, what)
    merged = df.collectColumns()
    n = len(merged[df.columns[0]]) if df.columns else 0
    groups: Dict[Tuple, List[int]] = {}
    order: List[Tuple] = []
    raw: Dict[Tuple, Tuple] = {}
    key_cols = [merged[k] for k in keys]
    for i in range(n):
        kt = tuple(_cell_key(col[i]) for col in key_cols)
        if kt not in groups:
            groups[kt] = []
            order.append(kt)
            raw[kt] = tuple(col[i] for col in key_cols)
        groups[kt].append(i)
    return merged, groups, order, raw


def _validated_pandas_frame(out, out_cols, what: str):
    import pandas as pd

    if not isinstance(out, pd.DataFrame):
        raise TypeError(
            f"{what} function must return a pandas DataFrame, got "
            f"{type(out).__name__}"
        )
    missing = [c for c in out_cols if c not in out.columns]
    if missing:
        raise ValueError(
            f"{what} output is missing declared columns {missing}; "
            f"got {list(out.columns)}"
        )
    return out[out_cols]


def _assemble_pandas_output(frames, out_cols, numPartitions: int):
    import pandas as pd

    if not frames:
        return DataFrame.fromColumns({c: [] for c in out_cols})
    cat = pd.concat(frames, ignore_index=True)
    return DataFrame.fromColumns(
        {c: _pandas_cells(cat[c]) for c in out_cols},
        numPartitions=max(1, numPartitions),
    )


class CoGroupedData:
    """``a.groupBy(k).cogroup(b.groupBy(k))`` intermediate (pyspark
    PandasCogroupedOps): each key present on EITHER side yields one
    ``func(left_pdf, right_pdf)`` call — the absent side arrives as an
    EMPTY pandas DataFrame with that side's columns, exactly pyspark.
    Driver-side like applyInPandas (collect-guarded)."""

    def __init__(self, left: "GroupedData", right: "GroupedData"):
        self._left = left
        self._right = right

    def applyInPandas(self, func, schema) -> DataFrame:
        import pandas as pd

        out_cols = _schema_names(schema)
        # func(left, right) or func(key, left, right)
        wants_key = _sniff_pos_arity(func, default=2) >= 3

        lm, lg, lo, lraw = _collect_groups(
            self._left._df, self._left._keys, "cogroup.applyInPandas"
        )
        rm, rg, ro, rraw = _collect_groups(
            self._right._df, self._right._keys, "cogroup.applyInPandas"
        )
        lcols = list(self._left._df.columns)
        rcols = list(self._right._df.columns)
        keys = list(lo) + [k for k in ro if k not in lg]

        def pdf_of(merged, groups, cols, kt):
            idxs = groups.get(kt, [])
            return pd.DataFrame({
                c: [merged[c][i] for i in idxs] for c in cols
            })

        frames = []
        for kt in keys:
            left_pdf = pdf_of(lm, lg, lcols, kt)
            right_pdf = pdf_of(rm, rg, rcols, kt)
            if wants_key:
                key = lraw.get(kt, rraw.get(kt))
                out = func(key, left_pdf, right_pdf)
            else:
                out = func(left_pdf, right_pdf)
            frames.append(
                _validated_pandas_frame(
                    out, out_cols, "cogroup.applyInPandas"
                )
            )
        return _assemble_pandas_output(
            frames, out_cols, self._left._df.numPartitions
        )


_NO_VALUE = object()  # pivot sentinel: row's value not in configured set


class PivotedGroupedData:
    """``groupBy(keys).pivot(col)`` intermediate: aggregation runs the
    same streamed engine with the pivot column as an extra group key,
    then reshapes driver-side (memory O(groups x values)). Column naming
    follows pyspark: just the pivot value for a single aggregate,
    ``<value>_<agg(col)>`` for several; combinations absent from the
    data come back null."""

    def __init__(
        self,
        df: DataFrame,
        keys: List[str],
        pivot_col: str,
        values: Optional[List[Any]],
    ):
        self._df = df
        self._keys = keys
        self._pivot = pivot_col
        self._values = values

    def agg(self, *exprs) -> DataFrame:
        """Both GroupedData.agg forms work here: the dict form and
        aggregate Columns (pivot("k").agg(F.sum("v").alias("s")))."""
        inner = GroupedData(
            self._df, self._keys + [self._pivot]
        ).agg(*exprs)
        # aggregate output names come FROM the inner frame (everything
        # after the group keys + pivot column), so pivot can never drift
        # from GroupedData.agg's naming scheme
        agg_names = [
            c
            for c in inner.columns
            if c not in self._keys and c != self._pivot
        ]
        rows = inner.collect()
        if self._values is not None:
            values = self._values
        else:
            seen = {r[self._pivot] for r in rows}
            # discovered values sort like pyspark; None (a valid group
            # key) orders last
            values = sorted(
                (v for v in seen if v is not None),
                key=lambda v: (str(type(v)), v),
            ) + ([None] if None in seen else [])
        single = len(agg_names) == 1

        def canonical(v):
            """The configured value this row's pivot cell matches, by
            VALUE equality (1 matches 1.0) but never across bool/int
            (True must not match 1) — row matching and column naming
            must use the same representative or cells silently drop."""
            for cv in values:
                if v is None or cv is None:
                    if v is None and cv is None:
                        return cv
                    continue
                if isinstance(cv, bool) != isinstance(v, bool):
                    continue
                if cv == v:
                    return cv
            return _NO_VALUE

        def out_name(v, agg_name):
            base = "null" if v is None else str(v)
            return base if single else f"{base}_{agg_name}"

        cells: Dict[tuple, Dict[str, Any]] = {}
        key_order: List[tuple] = []
        for r in rows:
            k = tuple(_cell_key(r[key]) for key in self._keys)
            if k not in cells:
                cells[k] = {key: r[key] for key in self._keys}
                key_order.append(k)
            cv = canonical(r[self._pivot])
            if cv is _NO_VALUE:
                continue  # excluded pivot value
            for agg_name in agg_names:
                cells[k][out_name(cv, agg_name)] = r[agg_name]
        out: Dict[str, List[Any]] = {
            key: [cells[k][key] for k in key_order] for key in self._keys
        }
        for v in values:
            for agg_name in agg_names:
                name = out_name(v, agg_name)
                if name in out:
                    raise ValueError(
                        f"Duplicate pivot output column {name!r}"
                    )
                out[name] = [
                    cells[k].get(name) for k in key_order
                ]
        return DataFrame.fromColumns(out)

    def count(self) -> DataFrame:
        return self.agg({"*": "count"})

    def avg(self, *cols: str) -> DataFrame:
        return self.agg({c: "avg" for c in cols})

    mean = avg  # pyspark alias

    def sum(self, *cols: str) -> DataFrame:
        return self.agg({c: "sum" for c in cols})

    def min(self, *cols: str) -> DataFrame:
        return self.agg({c: "min" for c in cols})

    def max(self, *cols: str) -> DataFrame:
        return self.agg({c: "max" for c in cols})
