"""Partitioned, lazily-transformed Arrow DataFrame.

Plays the role Spark DataFrames played for the reference: rows live in
partitions (one ``pyarrow.RecordBatch`` each), transformations are
recorded as a per-partition plan of batch functions and only run when the
frame is materialized (``collect``/``stream``/``count``). Host stages run
in parallel across CPU threads; device stages (jitted TPU applies) are
serialized by the engine so the chip sees an orderly batch stream.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
from typing import Callable, Iterator, List, Optional, Sequence

import numpy as np
import pyarrow as pa


Row = dict  # a collected row is a plain dict, keyed by column name


class _DeferredSide:
    """One side of a different-plan :meth:`DataFrame.union`, materialized
    lazily exactly once per process.

    Materialization runs on a PRIVATE small thread pool: running on the
    engine's own pool from a pool worker deadlocks once outer partitions
    saturate it (``max_inflight >= num_workers``), while fully-inline
    materialization serializes an N-partition decode. Each partition
    runs through the engine's retrying ``_run_partition`` when it has
    one (LocalEngine: device stages still serialize on its device
    lock); duck-typed engines without it (SparkEngine) get the plain
    stage contract (``apply_plan``).

    Pickle-safe for Spark task shipping: the lock, the cached batches,
    and the engine are process-local and dropped on the wire — a remote
    task computes ONLY the side partition it asks for via
    ``apply_plan`` (per-task copies share nothing, so full
    materialization there would cost O(P²) partition decodes
    cluster-wide; Spark's own different-plan unions likewise recompute
    or shuffle)."""

    def __init__(self, engine, plan, sources):
        self._engine = engine
        self._plan = list(plan)
        self._sources = list(sources)
        self._lock = threading.Lock()
        self._batches: Optional[List[pa.RecordBatch]] = None

    def __getstate__(self):
        state = self.__dict__.copy()
        del state["_lock"]
        state["_batches"] = None
        state["_engine"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()

    def _run_partition(self, s: "Source", j: int) -> pa.RecordBatch:
        runner = getattr(self._engine, "_run_partition", None)
        if runner is not None:
            return runner(s, self._plan, j)
        from sparkdl_tpu.data.spark_binding import apply_plan
        idx = s.logical_index if s.logical_index is not None else j
        return apply_plan(self._plan, s.load(), idx)

    def get(self, i: int) -> pa.RecordBatch:
        if self._engine is None:
            # Post-pickle (remote task) path: there is no process-local
            # cache another partition could reuse — compute just this
            # partition instead of pool-mapping the whole side.
            # sparkdl-lint: allow[H17] -- _sources is immutable after __init__ (bound once, never rebound/mutated); the lock guards the _batches memoization, the source list just rides inside it
            return self._run_partition(self._sources[i], i)
        with self._lock:
            if self._batches is None:
                from concurrent.futures import ThreadPoolExecutor
                n_workers = min(4, max(1, len(self._sources)))
                with ThreadPoolExecutor(
                        max_workers=n_workers,
                        thread_name_prefix="sparkdl-union") as pool:
                    self._batches = list(pool.map(
                        self._run_partition, self._sources,
                        range(len(self._sources))))
            return self._batches[i]


class _CoalescedGroup:
    """One :meth:`DataFrame.coalesce` output partition: runs its input
    partitions through the baked plan SEQUENTIALLY — via the owning
    engine's retrying, device-locked ``_run_partition`` when it has one
    (so device stages never run concurrently from multiple coalesced
    loads) — and concatenates. Pickle-safe for Spark task shipping: the
    engine is process-local and drops on the wire; a remote task
    applies the plain stage contract."""

    def __init__(self, engine, plan, sources, base_index, schema):
        self._engine = engine
        self._plan = list(plan)
        self._sources = list(sources)
        self._base = base_index
        self._schema = schema

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_engine"] = None
        return state

    def _run_partition(self, s: "Source", j: int) -> pa.RecordBatch:
        runner = getattr(self._engine, "_run_partition", None)
        if runner is not None:
            return runner(s, self._plan, j)
        from sparkdl_tpu.data.spark_binding import apply_plan
        idx = s.logical_index if s.logical_index is not None else j
        return apply_plan(self._plan, s.load(), idx)

    def load(self) -> pa.RecordBatch:
        batches = []
        for off, src in enumerate(self._sources):
            b = self._run_partition(src, self._base + off)
            if b.num_rows:
                batches.append(b)
        if not batches:
            return pa.RecordBatch.from_pylist([], schema=self._schema)
        if len(batches) == 1:
            return batches[0]
        return pa.Table.from_batches(batches).combine_chunks() \
            .to_batches()[0]


def column_index(data, name: str) -> int:
    """Resolve a column name to its index in a RecordBatch/Table/Schema,
    raising KeyError for unknown names (pyarrow's get_field_index
    returns -1, which would silently negative-index the last column —
    and it returns -1 for DUPLICATED names too, so the ambiguous case
    gets its own message instead of reading as 'missing')."""
    schema = data if isinstance(data, pa.Schema) else data.schema
    idx = schema.get_field_index(name)
    if idx < 0:
        dups = schema.get_all_field_indices(name)
        if len(dups) > 1:
            raise KeyError(
                f"column {name!r} is ambiguous: {len(dups)} columns "
                "share that name (e.g. after a join); drop the "
                "unwanted one by position or avoid the collision "
                "upstream")
        raise KeyError(
            f"column {name!r} not in batch ({schema.names})")
    return idx


class LiveBatchHint:
    """A ``Stage.batch_hint`` that follows its runner's
    ``preferred_chunk`` LIVE instead of freezing the value at plan
    build. The engine reads hints through ``int(...)`` / ``bool(...)``
    (``LocalEngine._stream_rechunk`` re-reads between blocks), so a
    runner whose device batch the autotune controller moves along its
    pre-warmed shape ladder (``sparkdl_tpu/autotune``) pulls the
    engine's re-chunk cut along with it — blocks cut after the change
    align to the new batch, already-cut blocks stay row-exact (the
    runner pads/truncates any N). Duck-typed: anything with a
    ``preferred_chunk`` attribute works; pickles with its runner (the
    stage-closure shipping discipline)."""

    __slots__ = ("runner",)

    def __init__(self, runner):
        self.runner = runner

    def __int__(self) -> int:
        return int(self.runner.preferred_chunk)

    __index__ = __int__

    def __bool__(self) -> bool:
        return int(self.runner.preferred_chunk) > 0

    def __repr__(self) -> str:
        return f"LiveBatchHint({int(self)})"

    # pickle via __reduce__ keeps the __slots__ class cloudpickle-safe
    def __reduce__(self):
        return (LiveBatchHint, (self.runner,))


@dataclasses.dataclass(frozen=True)
class Stage:
    """One plan step: RecordBatch → RecordBatch. With ``with_index``,
    ``fn(batch, partition_index)`` — for per-partition determinism
    (sampling, sharded IO), the mapPartitionsWithIndex affordance.

    ``batch_hint`` (device stages): the stage's preferred input row
    count — its device batch (or global mesh batch). A row-preserving,
    index-free device stage with a hint may be RE-CHUNKED by the engine:
    fed row blocks cut at multiples of the hint from the ordered
    partition stream instead of per-partition blocks, so partitions
    smaller than the device batch stop padding up to the static shape
    (the 2.4× small-partition tax measured in BASELINE.md). The
    reference had no such constraint to absorb — TensorFrames blocks
    were whatever size the partition was (SURVEY §3.2); static-shape
    XLA makes batch alignment the engine's job, not the user's.

    ``with_upcoming`` (re-chunked device stages): the engine calls
    ``fn(block, upcoming=look)``, where ``look()`` gives the block
    that will be passed next if its rows are already loaded (else
    None; it never waits), so the stage can start the next block's
    device work under this one's (``BatchRunner.run(upcoming=)``).
    ``on_close`` (optional) is called when a stream through the stage
    ends, however it ends: the place to forget work started for a
    block that will not come."""
    fn: Callable[..., pa.RecordBatch]
    kind: str = "host"            # "host" (thread-parallel) | "device" (serial)
    name: str = "stage"
    row_preserving: bool = True
    with_index: bool = False
    batch_hint: Optional[int] = None
    # True for stages with externally visible side effects (parquet
    # part writers): on error/abandonment the engine then DRAINS
    # in-flight siblings before returning control, so a straggler
    # can't e.g. re-create a staging dir after cleanup swept it. Pure
    # plans skip the drain — take(1)/first() must not block for a
    # full in-flight wave of decodes.
    effectful: bool = False
    with_upcoming: bool = False
    on_close: Optional[Callable[[], None]] = None


@dataclasses.dataclass(frozen=True)
class Source:
    """One partition source. ``load`` materializes the partition's batch;
    ``num_rows`` is a hint for count() fast-path (None = unknown).
    ``logical_index``, when set, is the partition's identity for
    ``with_index`` stages — so reordering/subsetting partitions
    (``with_partition_order``, host sharding, per-epoch shuffles) never
    changes what a deterministic stage like ``sample`` draws for a
    given partition. None = use the positional index.
    ``schema_hint``, when set, must EQUAL ``load()``'s schema — it lets
    ``DataFrame.schema`` probe the plan on an empty prototype without
    materializing the first partition (decoding a whole image partition
    to answer ``.columns`` is the trap; only leaf constructors whose
    schema is statically known set it).
    ``effectful`` marks a ``load`` with externally visible side effects
    (cache_to_disk spill sources write Arrow IPC files inside load):
    the engine then QUIESCES in-flight sibling loads before returning
    control on error/abandonment, so a straggler load can't e.g.
    re-create spill files after the owner's cleanup rmtree ran — the
    Source twin of ``Stage.effectful``."""
    load: Callable[[], pa.RecordBatch]
    num_rows: Optional[int] = None
    logical_index: Optional[int] = None
    schema_hint: Optional[pa.Schema] = None
    effectful: bool = False


def _empty_batch(schema: pa.Schema) -> pa.RecordBatch:
    """Zero-row batch carrying ``schema`` (field metadata included)."""
    return pa.RecordBatch.from_arrays(
        [pa.array([], f.type) for f in schema], schema=schema)


class DataFrame:
    """Immutable partitioned frame; transforms return new frames."""

    def __init__(self, sources: Sequence[Source], plan: Sequence[Stage] = (),
                 engine=None):
        from sparkdl_tpu.data.engine import default_engine
        self._sources: List[Source] = list(sources)
        self._plan: List[Stage] = list(plan)
        self._engine = engine or default_engine()
        self._schema: Optional[pa.Schema] = None

    # -- constructors -------------------------------------------------------

    @staticmethod
    def from_table(table: pa.Table, num_partitions: int = 8,
                   engine=None) -> "DataFrame":
        table = table.combine_chunks()
        n = table.num_rows
        num_partitions = max(1, min(num_partitions, n) if n else 1)
        bounds = np.linspace(0, n, num_partitions + 1).astype(int)
        sources = []
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            lo_i, hi_i = int(lo), int(hi)
            sub = table.slice(lo_i, hi_i - lo_i)

            def _load(sub=sub) -> pa.RecordBatch:
                batches = sub.combine_chunks().to_batches()
                if not batches:
                    return pa.RecordBatch.from_pylist([], schema=sub.schema)
                if len(batches) == 1:
                    return batches[0]
                return pa.Table.from_batches(batches).combine_chunks() \
                    .to_batches()[0]

            sources.append(Source(_load, hi_i - lo_i,
                                  schema_hint=table.schema))
        return DataFrame(sources, engine=engine)

    @staticmethod
    def from_pandas(df, num_partitions: int = 8, engine=None) -> "DataFrame":
        return DataFrame.from_table(pa.Table.from_pandas(df),
                                    num_partitions, engine)

    @staticmethod
    def from_pylist(rows: List[dict], num_partitions: int = 8,
                    engine=None) -> "DataFrame":
        return DataFrame.from_table(pa.Table.from_pylist(rows),
                                    num_partitions, engine)

    @staticmethod
    def from_batches(batches: Sequence[pa.RecordBatch],
                     engine=None) -> "DataFrame":
        sources = [Source((lambda b=b: b), b.num_rows,
                          schema_hint=b.schema) for b in batches]
        return DataFrame(sources, engine=engine)

    @staticmethod
    def read_parquet(path: str, engine=None,
                     allow_uncommitted: bool = False) -> "DataFrame":
        """Lazy frame over a parquet directory written by
        :meth:`write_parquet` (or any directory of part files): one
        partition per file, loaded on demand; row counts come from
        parquet footers so ``count()`` never reads data. Tensor-column
        shape metadata survives the round-trip (Arrow schema is stored
        in the parquet file).

        A directory holding part files plus a ``_tmp.*`` staging
        remnant is a DEFINITIVE interrupted :meth:`write_parquet`
        commit — refused by default (Spark's committer semantics:
        uncommitted output is not readable); ``allow_uncommitted=True``
        overrides. A marker-less directory with no staging remnant was
        written by another tool (pyarrow/pandas, or Spark with the
        marker suppressed — neither requires ``_SUCCESS`` on read):
        served with a warning."""
        import glob

        import pyarrow.parquet as pq

        if os.path.isdir(path):
            files = sorted(glob.glob(os.path.join(path, "*.parquet")))
            if files and not os.path.exists(
                    os.path.join(path, "_SUCCESS")):
                staging = glob.glob(os.path.join(path, "_tmp.*"))
                if staging and not allow_uncommitted:
                    raise FileNotFoundError(
                        f"{path!r} holds part files, no _SUCCESS "
                        f"marker, and a staging remnant "
                        f"({os.path.basename(staging[0])}): a "
                        "write_parquet was interrupted mid-commit and "
                        "the dataset may be PARTIAL. Pass "
                        "allow_uncommitted=True to read it anyway.")
                import logging
                logging.getLogger(__name__).warning(
                    "%r has no _SUCCESS marker%s: serving a dataset "
                    "this library did not commit. COMPLETENESS CANNOT "
                    "BE VERIFIED — foreign writers (pyarrow/pandas) "
                    "don't produce the marker, but a writer that died "
                    "without leaving its _tmp.* staging remnant looks "
                    "identical. If these rows feed training, confirm "
                    "the row count or rewrite via write_parquet (touch "
                    "_SUCCESS to silence this warning).", path,
                    " and a _tmp.* staging remnant" if staging else "")
        else:
            files = [path]
        if not files:
            raise FileNotFoundError(
                f"no .parquet files under {path!r}")

        schema = None

        def make(f: str) -> Source:
            nonlocal schema
            pf = pq.ParquetFile(f)
            if schema is None:
                schema = pf.schema_arrow
            num_rows = pf.metadata.num_rows

            def _load(f=f) -> pa.RecordBatch:
                table = pq.read_table(f).combine_chunks()
                if table.num_rows == 0:
                    return pa.RecordBatch.from_pylist(
                        [], schema=table.schema)
                return table.to_batches()[0]

            return Source(_load, num_rows)

        out = DataFrame([make(f) for f in files], engine=engine)
        # schema from the footer already parsed for num_rows — the
        # default zero-row probe would read and decode a whole part
        # file to answer .columns
        out._schema = schema
        return out

    def write_parquet(self, path: str,
                      row_group_rows: Optional[int] = None) -> str:
        """Materialize the plan and write one parquet part file per
        partition under ``path`` (Spark's ``df.write.parquet`` shape).
        ``row_group_rows`` caps rows per parquet row group (default:
        pyarrow's) — smaller groups let range readers
        (``repartition(cacheDir=)``) fetch only what they need.

        Part writing is a PLAN STAGE: each partition's task writes its
        own part into a staging subdirectory and returns only a tiny
        (file name, row count) summary — on :class:`SparkEngine` the
        parts are written ON THE EXECUTORS (Spark's committer model:
        ``path`` must be storage every executor reaches — NFS/GCS/
        fuse), and the driver never sees the data, only summaries. The
        driver then commits: renames staged parts into place in
        partition order and writes ``_SUCCESS``. A crash mid-stream
        leaves no part files; a kill mid-commit leaves parts without
        ``_SUCCESS``, which :meth:`read_parquet` refuses. Refuses a
        directory already holding part files. Returns ``path``."""
        import glob
        import shutil

        import pyarrow.parquet as pq

        os.makedirs(path, exist_ok=True)
        if glob.glob(os.path.join(path, "*.parquet")):
            raise FileExistsError(
                f"{path!r} already holds parquet part files; write to "
                "a fresh directory (overwrite is never implicit)")
        stale = glob.glob(os.path.join(path, "_tmp.*"))
        if stale:
            # staging leftovers: a concurrent writer, or a writer killed
            # mid-stream. Refusing (not sweeping) is the safe call — a
            # sweep would delete a LIVE concurrent writer's staged parts
            raise FileExistsError(
                f"{path!r} holds staging leftovers ({stale[0]}): "
                "another write_parquet is in progress, or a previous "
                "one was killed mid-stream — delete the _tmp.* "
                "directory if no writer is running")
        staging = os.path.join(path, f"_tmp.{os.getpid()}")
        # bare makedirs: a second same-process writer racing into the
        # same path must fail HERE (FileExistsError), not interleave
        # commits with this writer (tasks re-create it with exist_ok
        # because remote executors start without it)
        os.makedirs(staging)
        summary_schema = pa.schema([("part", pa.string()),
                                    ("rows", pa.int64())])

        def _write_part(batch: pa.RecordBatch, index: int
                        ) -> pa.RecordBatch:
            # runs INSIDE the task; tmp + os.replace makes retried /
            # duplicate task attempts idempotent (last writer wins on
            # an identical part name)
            if batch.num_rows == 0:
                # emptied partitions may carry imprecise computed-column
                # types (see collect()); they contribute no rows
                return pa.RecordBatch.from_pylist(
                    [], schema=summary_schema)
            os.makedirs(staging, exist_ok=True)
            import uuid
            # unique per attempt: repeated logical indices (partition
            # repeats) and task retries each stage their own file; only
            # names returned in summaries commit, orphans are swept
            # with the staging dir
            fname = f"part-{index:05d}-{uuid.uuid4().hex[:8]}.parquet"
            tmp = os.path.join(
                staging,
                f"{fname}.tmp.{os.getpid()}.{threading.get_ident()}")
            kw = ({"row_group_size": int(row_group_rows)}
                  if row_group_rows else {})
            pq.write_table(pa.Table.from_batches([batch]), tmp, **kw)
            os.replace(tmp, os.path.join(staging, fname))
            return pa.RecordBatch.from_pylist(
                [{"part": fname, "rows": batch.num_rows}],
                schema=summary_schema)

        committed = 0
        try:
            entries = []
            for b in self.map_batches(_write_part, name="write_parquet",
                                      row_preserving=False,
                                      with_index=True,
                                      effectful=True).stream():
                entries.extend(b.to_pylist())
            if not entries:
                # all-empty frame: one empty part so the dataset (and
                # its schema) still round-trips through read_parquet
                f = os.path.join(staging, "part-empty.parquet")
                pq.write_table(self.schema.empty_table(), f)
                entries = [{"part": "part-empty.parquet", "rows": 0}]
            # commit in stream (= partition) order: read_parquet sorts
            # part files lexicographically, so sequential names keep
            # row order stable even when logical indices are sparse
            for seq, e in enumerate(entries):
                os.replace(os.path.join(staging, e["part"]),
                           os.path.join(path, f"part-{seq:05d}.parquet"))
                committed += 1
            # commit marker (Spark's _SUCCESS): the rename loop itself
            # is not atomic, so a kill mid-commit leaves part files but
            # no marker — read_parquet refuses to read without it
            with open(os.path.join(path, "_SUCCESS"), "w"):
                pass
        except BaseException:
            # Once ANY part moved into `path`, the staging dir IS the
            # interrupted-commit evidence read_parquet keys on —
            # sweeping it would downgrade a PARTIAL dataset to
            # "foreign writer, warn-and-serve". Before the first
            # rename, `path` holds no parts, so sweeping is safe.
            if not committed:
                shutil.rmtree(staging, ignore_errors=True)
            raise
        shutil.rmtree(staging, ignore_errors=True)
        return path

    # -- plan building ------------------------------------------------------

    def map_batches(self, fn: Callable[..., pa.RecordBatch],
                    kind: str = "host", name: str = "map_batches",
                    row_preserving: bool = True,
                    with_index: bool = False,
                    batch_hint: Optional[int] = None,
                    effectful: bool = False,
                    with_upcoming: bool = False,
                    on_close: Optional[Callable[[], None]] = None
                    ) -> "DataFrame":
        return DataFrame(
            self._sources,
            self._plan + [Stage(fn, kind, name, row_preserving,
                                with_index, batch_hint, effectful,
                                with_upcoming, on_close)],
            self._engine)

    def with_column(self, name: str,
                    fn: Callable[[pa.RecordBatch], pa.Array],
                    kind: str = "host") -> "DataFrame":
        """Add — or REPLACE, pyspark ``withColumn`` semantics, position
        preserved — a column computed per batch. ``fn`` may return an
        Arrow array or a numpy array (auto-converted to a tensor
        column)."""
        from sparkdl_tpu.data.tensors import append_tensor_column

        if not callable(fn):
            raise TypeError(
                f"with_column({name!r}) needs a per-batch function "
                f"(batch -> column), got {type(fn).__name__}; a literal "
                "column can't be appended lazily — partitions stream, "
                "so compute it from each batch (e.g. from a key column)")

        def _stage(batch: pa.RecordBatch) -> pa.RecordBatch:
            col = fn(batch)
            if isinstance(col, np.ndarray):
                return append_tensor_column(batch, name, col,
                                            replace=True)
            if isinstance(col, pa.ChunkedArray):
                col = col.combine_chunks()
            # all-indices: get_field_index reads DUPLICATED names as -1
            idxs = batch.schema.get_all_field_indices(name)
            if len(idxs) > 1:
                raise ValueError(
                    f"cannot replace column {name!r}: {len(idxs)} "
                    "columns share that name (e.g. after a join); "
                    "rename/drop first")
            if idxs:
                return batch.set_column(idxs[0], name, col)
            return batch.append_column(name, col)

        return self.map_batches(_stage, kind=kind, name=f"with_column({name})")

    def select(self, *cols: str) -> "DataFrame":
        cols = list(cols)

        def _stage(batch: pa.RecordBatch) -> pa.RecordBatch:
            return batch.select(cols)

        return self.map_batches(_stage, name=f"select({','.join(cols)})")

    def drop(self, *cols: str) -> "DataFrame":
        to_drop = set(cols)

        def _stage(batch: pa.RecordBatch) -> pa.RecordBatch:
            keep = [n for n in batch.schema.names if n not in to_drop]
            return batch.select(keep)

        return self.map_batches(_stage, name=f"drop({','.join(cols)})")

    def rename(self, mapping: dict) -> "DataFrame":
        # Duplicate-creating renames fail LOUDLY (Spark tolerates the
        # duplicate and errors lazily on the first ambiguous
        # resolution; our by-name lookups would serve the FIRST column
        # silently). Only names whose count INCREASES are the mapping's
        # fault — a frame already carrying duplicates may still rename
        # its other columns. Validation runs eagerly when the schema is
        # free (cached, or a leaf schema_hint means the probe loads
        # nothing); otherwise per batch at execution — computing the
        # schema here would load a whole partition just to check names.
        import collections

        def _validate(names) -> None:
            before = collections.Counter(names)
            after = collections.Counter(mapping.get(n, n)
                                        for n in names)
            dup = sorted(n for n, c in after.items()
                         if c > 1 and c > before[n])
            if dup:
                raise ValueError(
                    f"rename would duplicate column name(s) {dup}; "
                    "drop the existing column first")

        if self.schema_probe_free:
            _validate(list(self.schema.names))
            validate_per_batch = None
        else:
            validate_per_batch = _validate

        def _stage(batch: pa.RecordBatch) -> pa.RecordBatch:
            if validate_per_batch is not None:
                validate_per_batch(batch.schema.names)
            return batch.rename_columns(
                [mapping.get(n, n) for n in batch.schema.names])

        return self.map_batches(_stage, name="rename")

    def filter(self, predicate: Callable[[pa.RecordBatch], "pa.Array | np.ndarray"]
               ) -> "DataFrame":
        def _stage(batch: pa.RecordBatch) -> pa.RecordBatch:
            mask = predicate(batch)
            if isinstance(mask, np.ndarray):
                mask = pa.array(mask)
            return batch.filter(mask)

        return self.map_batches(_stage, name="filter", row_preserving=False)

    def repartition(self, num_partitions: int,
                    cacheDir: Optional[str] = None) -> "DataFrame":
        """Change the partition count, preserving row order (Spark's
        shuffle repartition — SURVEY §1 L0).

        Without ``cacheDir``: materializes the whole frame on the
        driver, then re-slices — fine for frames that fit in RAM.

        With ``cacheDir``: OUT-OF-CORE (VERDICT r4 #6). The frame
        streams through :meth:`write_parquet` into a spill under
        ``cacheDir`` (parts written partition-at-a-time, bounded
        memory), then the result is ``num_partitions`` lazy sources
        each reading only its own contiguous row range from the spill
        (row counts come from parquet footers, so planning reads no
        data). Peak memory is one input partition while spilling and
        ~2 spill files per output partition while reading — never the
        whole frame. The spill persists for the returned frame's
        lifetime; it lives under a unique subdirectory of ``cacheDir``
        and can be reclaimed by deleting it once the frame is done."""
        if int(num_partitions) <= 0:
            raise ValueError(  # Spark raises too; clamping hides typos
                f"num_partitions must be positive, got {num_partitions}")
        if cacheDir is None:
            return DataFrame.from_table(self.collect(), num_partitions,
                                        self._engine)
        import uuid

        spill = os.path.join(cacheDir,
                             f"repartition_spill_{uuid.uuid4().hex[:12]}")
        # small row groups so range reads fetch only what they need —
        # whole-file loads would re-decode each multi-GB part once per
        # overlapping output partition (review r5 finding)
        self.write_parquet(spill, row_group_rows=4096)
        return DataFrame._from_parquet_ranges(spill, int(num_partitions),
                                              self._engine)

    @staticmethod
    def _from_parquet_ranges(path: str, num_partitions: int,
                             engine=None) -> "DataFrame":
        """``num_partitions`` lazy sources over a parquet directory,
        each reading ONLY the row groups its contiguous row range
        overlaps (counts from footers; no data read at plan time).
        Peak memory per load ≈ the range plus one boundary row group."""
        import glob as _glob

        import pyarrow.parquet as pq

        files = sorted(_glob.glob(os.path.join(path, "*.parquet")))
        if not files:
            raise FileNotFoundError(
                f"no parquet part files under {path!r}")
        groups = []  # (file, row_group_index, rows)
        for f in files:
            md = pq.ParquetFile(f).metadata
            for g in range(md.num_row_groups):
                groups.append((f, g, md.row_group(g).num_rows))
        offsets = np.concatenate(
            [[0], np.cumsum([g[2] for g in groups])]) if groups \
            else np.array([0])
        total = int(offsets[-1])
        n_out = max(1, min(int(num_partitions), total) if total else 1)
        bounds = np.linspace(0, total, n_out + 1).astype(int)

        def _make_load(lo: int, hi: int):
            def _load() -> pa.RecordBatch:
                frags = []
                pf = None
                open_name = None
                # overlapping row-group window straight from offsets
                i0 = max(0, int(np.searchsorted(offsets, lo,
                                                "right")) - 1)
                i1 = int(np.searchsorted(offsets, hi, "left"))
                for i in range(i0, min(i1, len(groups))):
                    f, g, _rows = groups[i]
                    s_lo, s_hi = int(offsets[i]), int(offsets[i + 1])
                    if s_hi <= lo or s_lo >= hi:
                        continue
                    if f != open_name:
                        pf = pq.ParquetFile(f)
                        open_name = f
                    tbl = pf.read_row_group(g)
                    a = max(lo, s_lo) - s_lo
                    z = min(hi, s_hi) - s_lo
                    frags.extend(tbl.slice(a, z - a).combine_chunks()
                                 .to_batches())
                frags = [b for b in frags if b.num_rows]
                if not frags:
                    return _empty_batch(pq.read_schema(files[0]))
                # _concat_batches raises loudly on >2GiB columns that
                # refuse to combine — returning a subset would silently
                # drop rows on exactly the larger-than-RAM path this
                # exists for
                from sparkdl_tpu.data.engine import _concat_batches
                return _concat_batches(frags)
            return _load

        sources = [Source(_make_load(int(lo), int(hi)), int(hi - lo))
                   for lo, hi in zip(bounds[:-1], bounds[1:])]
        out = DataFrame(sources, engine=engine)
        # footer-only read: the default probe would load a whole row
        # range (the read_parquet precedent)
        out._schema = pq.read_schema(files[0])
        return out

    def coalesce(self, num_partitions: int) -> "DataFrame":
        """Merge ADJACENT partitions down to ``num_partitions`` without
        a global materialization (Spark ``coalesce(shuffle=False)``):
        each output partition runs its group of input partitions
        through the full plan ONE AT A TIME — through the engine's
        retrying, device-locked partition runner, so device stages stay
        serialized — and concatenates. Memory per in-flight output
        partition is one group's rows (≈ total/num_partitions), and the
        engine bounds in-flight partitions as usual; coalescing to very
        FEW partitions therefore approaches full materialization — for
        a larger-than-RAM re-layout use :meth:`write_parquet` or
        :meth:`cache_to_disk` instead. Row order is preserved, and
        ``with_index`` plan stages keep each input partition's own
        logical identity, so deterministic stages like ``sample`` draw
        exactly what they draw un-coalesced."""
        if int(num_partitions) <= 0:
            raise ValueError(  # Spark raises too; clamping hides typos
                f"num_partitions must be positive, got {num_partitions}")
        n_out = min(int(num_partitions), len(self._sources))
        if n_out == len(self._sources):
            return self
        preserving = all(st.row_preserving for st in self._plan)
        bounds = np.linspace(0, len(self._sources), n_out + 1).astype(int)
        schema = self.schema  # capture the VALUE, not self (pickling)
        sources = []
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            group = _CoalescedGroup(self._engine, self._plan,
                                    self._sources[lo:hi], int(lo),
                                    schema)
            rows = (sum(s.num_rows for s in self._sources[lo:hi])
                    if preserving and all(s.num_rows is not None
                                          for s in self._sources[lo:hi])
                    else None)
            sources.append(Source(group.load, rows))
        out = DataFrame(sources, engine=self._engine)
        # pre-seeded: the coalesced frame's plan is empty and its load
        # IS the baked plan, so the default zero-row probe would decode
        # a whole GROUP just to answer .columns (cache_to_disk's trap)
        out._schema = schema
        return out

    def _materialize_prefix(self, n: int) -> "DataFrame":
        """First ``n`` FINAL rows as a 1-partition frame, streaming
        partitions only until the cutoff is met and slicing whole Arrow
        batches (no per-row Python — image/tensor columns stay
        columnar)."""
        batches: List[pa.RecordBatch] = []
        remaining = n
        if remaining > 0:
            for batch in self.stream():
                if batch.num_rows > remaining:
                    batch = batch.slice(0, remaining)
                batches.append(batch)
                remaining -= batch.num_rows
                if remaining <= 0:
                    break
        table = (pa.Table.from_batches(batches, schema=self.schema)
                 if batches else
                 pa.Table.from_pylist([], schema=self.schema))
        return DataFrame.from_table(table, 1, self._engine)

    def limit(self, n: int) -> "DataFrame":
        """First ``n`` rows (across partitions, in order), lazily:
        partitions past the cutoff are never loaded."""
        if n < 0:
            raise ValueError(f"limit must be >= 0, got {n}")
        if any(not st.row_preserving for st in self._plan):
            # a filter in the plan changes row counts — the cutoff must
            # apply to FINAL rows, so materialize just enough
            return self._materialize_prefix(n)
        out_sources: List[Source] = []
        remaining = n
        for s in self._sources:
            if remaining <= 0:
                break
            if s.num_rows is None:
                # Unknown partition size (union's deferred sides): a
                # lazy prefix cannot know whether this source satisfies
                # the cutoff — slicing it and stopping here silently
                # under-returns when it holds fewer than ``remaining``
                # rows. Materialize just enough instead.
                return self._materialize_prefix(n)
            if s.num_rows <= remaining:
                out_sources.append(s)
                remaining -= s.num_rows
            else:
                take = remaining

                def _load(s=s, take=take) -> pa.RecordBatch:
                    return s.load().slice(0, take)

                # keep the partition's logical identity for with_index
                # stages (the un-limited frame's draws must be a prefix)
                out_sources.append(dataclasses.replace(
                    s, load=_load, num_rows=take))
                remaining = 0
        if not out_sources:  # keep the schema even with zero rows
            return DataFrame.from_table(
                pa.Table.from_pylist([], schema=self.schema), 1,
                self._engine)
        return DataFrame(out_sources, self._plan, self._engine)

    def with_partition_order(self, indices: Sequence[int]) -> "DataFrame":
        """A frame over the given subset/permutation of this frame's
        partitions, same plan — the public seam for per-epoch partition
        shuffles (streaming training) and host sharding (each index
        selects one existing partition; repeats allowed)."""
        n = len(self._sources)
        indices = [int(i) for i in indices]  # one-shot iterables: read once
        bad = [i for i in indices if not (0 <= i < n)]
        if bad:
            raise IndexError(
                f"partition index {bad[0]} out of range [0, {n})")

        def keep_identity(i: int) -> Source:
            src = self._sources[i]
            if src.logical_index is not None:
                return src  # already pinned by an earlier reorder
            return dataclasses.replace(src, logical_index=i)

        return DataFrame([keep_identity(i) for i in indices],
                         self._plan, self._engine)

    def union(self, other: "DataFrame") -> "DataFrame":
        """Concatenate two frames' rows (self's first). Stays fully lazy
        when both share the same plan; otherwise each side materializes
        lazily (once, at first execution) through its own engine path so
        device-stage serialization is preserved."""
        if self.schema != other.schema:
            raise ValueError(
                f"union schema mismatch: {self.schema.names} vs "
                f"{other.schema.names}")
        if self._plan == other._plan:
            out = DataFrame(self._sources + other._sources, self._plan,
                            self._engine)
            out._schema = self._schema  # just computed by the check
            return out

        def deferred(df: "DataFrame") -> List[Source]:
            side = _DeferredSide(df._engine, df._plan, df._sources)
            preserving = all(st.row_preserving for st in df._plan)
            return [Source(functools.partial(side.get, i),
                           s.num_rows if preserving else None)
                    for i, s in enumerate(df._sources)]

        out = DataFrame(deferred(self) + deferred(other),
                        engine=self._engine)
        out._schema = self._schema  # deferred loads END in this plan
        return out

    def join(self, other: "DataFrame", on, how: str = "inner", *,
             broadcast_limit_rows: int = 2_000_000,
             broadcast_limit_bytes: int = 256 << 20) -> "DataFrame":
        """Broadcast hash join: ``other`` (the small side — e.g. a label
        table) materializes ONCE and ships into a per-batch probe;
        this frame streams. The Spark-shaped affordance behind every
        "attach labels to images" flow (reference README's
        transfer-learning example joined labels onto readImages output).

        ``on``: key column name or list of names present on both sides;
        ``how``: ``inner`` (drop unmatched left rows) or ``left`` (keep
        them, right columns null). Keys must be UNIQUE on the right
        side — duplicate right keys raise (this is a broadcast lookup,
        not a general shuffle join).

        The right side must fit the broadcast contract: at most
        ``broadcast_limit_rows`` rows / ``broadcast_limit_bytes``
        materialized bytes (Spark's autoBroadcastJoinThreshold shape,
        sized for driver RAM rather than shuffle traffic). Joining two
        big frames raises a named error instead of an OOM; raise the
        limits explicitly if the right side genuinely fits in memory."""
        keys = [on] if isinstance(on, str) else list(on)
        if not keys:
            raise ValueError("join needs at least one key column")
        if how not in ("inner", "left"):
            raise ValueError(f"how must be 'inner' or 'left', got {how!r}")
        # single streamed pass over the right side: both guards fire as
        # soon as a limit is crossed, BEFORE the full table is held (and
        # the build side's plan executes once, not count()+collect())
        r_batches, n_right, nbytes_right = [], 0, 0
        for rb in other.stream():
            if rb.num_rows == 0:
                # emptied partitions may carry imprecise computed-column
                # types (see collect()) — and contribute nothing
                continue
            n_right += rb.num_rows
            nbytes_right += rb.nbytes
            if n_right > broadcast_limit_rows:
                raise ValueError(
                    f"broadcast join: right side exceeds "
                    f"broadcast_limit_rows={broadcast_limit_rows:,} "
                    "(the right side materializes in full on every "
                    "process). Swap the sides, pre-aggregate, or pass a "
                    "higher broadcast_limit_rows if it truly fits in "
                    "memory.")
            if nbytes_right > broadcast_limit_bytes:
                raise ValueError(
                    f"broadcast join: right side exceeds "
                    f"broadcast_limit_bytes={broadcast_limit_bytes:,} "
                    f"({nbytes_right:,} bytes so far; the right side "
                    "materializes in full on every process). Swap the "
                    "sides, drop payload columns, or pass a higher "
                    "broadcast_limit_bytes if it truly fits.")
            r_batches.append(rb)
        right = (pa.Table.from_batches(r_batches) if r_batches
                 else other.schema.empty_table())
        for k in keys:
            column_index(right, k)   # raise early on a bad key
            column_index(self.schema, k)
        overlap = (set(self.schema.names) & set(right.schema.names)) \
            - set(keys)
        if overlap:
            raise ValueError(
                f"non-key columns {sorted(overlap)} exist on both "
                "sides; rename or drop one side first")

        import pyarrow.compute as pc

        def key_array(table_or_batch) -> pa.Array:
            """Key column(s) → one hashable array, all in C++ — the
            probe is a per-batch hot stage and must not drop to
            per-row Python. Multi-key: columns cast to string and
            joined with a separator (a composite hash key)."""
            arrs = []
            for k in keys:
                col = table_or_batch.column(
                    column_index(table_or_batch, k))
                if isinstance(col, pa.ChunkedArray):
                    col = col.combine_chunks()
                arrs.append(col)
            if len(arrs) == 1:
                return arrs[0]
            # escape the separator inside each field before joining, or
            # values containing \x1f would make distinct key tuples
            # collide (('x\x1fy','z') vs ('x','y\x1fz')) — wrong
            # matches / spurious duplicate-key errors
            parts = []
            for a in arrs:
                s = pc.cast(a, pa.string())
                s = pc.replace_substring(s, "\\", "\\\\")
                s = pc.replace_substring(s, "\x1f", "\\u")
                parts.append(s)
            return pc.binary_join_element_wise(*parts, "\x1f")

        right_keys = key_array(right)
        if right_keys.null_count:
            raise ValueError("right-side join keys contain nulls")
        if pc.count_distinct(right_keys).as_py() != len(right_keys):
            dup = [k for k, c in
                   zip(*np.unique(np.asarray(right_keys.to_pylist(),
                                             dtype=object),
                                  return_counts=True)) if c > 1][0]
            raise ValueError(
                f"duplicate join key {dup!r} on the right side; "
                "broadcast join needs unique right keys")
        payload = right.drop_columns(keys)

        def _stage(batch: pa.RecordBatch) -> pa.RecordBatch:
            idx = pc.index_in(key_array(batch), value_set=right_keys)
            if how == "inner":
                keep = idx.is_valid()
                batch = batch.filter(keep)
                take = idx.drop_null()
            else:
                take = idx  # null index → null payload row
            picked = payload.take(take)
            for col_i, field in enumerate(picked.schema):
                batch = batch.append_column(
                    field, picked.column(col_i).combine_chunks())
            return batch

        return self.map_batches(
            _stage, name=f"join({','.join(keys)})",
            row_preserving=(how == "left"))

    def sample(self, fraction: float, seed: int = 42) -> "DataFrame":
        """Bernoulli row sample (per-row coin flip, like Spark's).
        Deterministic per (seed, partition): re-materializations return
        the same rows, and concurrent partitions each use their own
        generator."""
        if not (0.0 <= fraction <= 1.0):
            raise ValueError(f"fraction must be in [0, 1], got {fraction}")

        def _stage(batch: pa.RecordBatch, index: int) -> pa.RecordBatch:
            rng = np.random.default_rng((seed, index))
            keep = rng.random(batch.num_rows) < fraction
            return batch.filter(pa.array(keep))

        return self.map_batches(_stage, name=f"sample({fraction})",
                                row_preserving=False, with_index=True)

    def show(self, n: int = 20, truncate: int = 40) -> None:
        """Print the first ``n`` rows as a simple table (Spark
        ``df.show`` affordance)."""
        rows = self.take(n)
        cols = self.columns
        def fmt(v):
            s = repr(v)
            return s if len(s) <= truncate else s[:truncate - 1] + "…"
        widths = {c: len(c) for c in cols}
        rendered = [{c: fmt(r.get(c)) for c in cols} for r in rows]
        for r in rendered:
            for c in cols:
                widths[c] = max(widths[c], len(r[c]))
        line = "+" + "+".join("-" * (widths[c] + 2) for c in cols) + "+"
        print(line)
        print("|" + "|".join(f" {c.ljust(widths[c])} " for c in cols)
              + "|")
        print(line)
        for r in rendered:
            print("|" + "|".join(f" {r[c].ljust(widths[c])} "
                                 for c in cols) + "|")
        print(line)

    def cache(self) -> "DataFrame":
        """Materialize the plan ONCE and return a frame over the
        in-memory result (Spark's ``df.cache()`` affordance, eager).
        Repeated materializations of the returned frame — CV folds,
        multi-trial fits, per-epoch passes — re-slice the table instead
        of re-running a decode-bearing plan."""
        return DataFrame.from_table(self.collect(),
                                    max(1, len(self._sources)),
                                    self._engine)

    _spill_manifest_lock = threading.Lock()

    def cache_to_disk(self, directory: str,
                      fingerprint: str = "") -> "DataFrame":
        """A frame whose partitions spill to Arrow IPC files on first
        load and re-read from disk afterwards — the multi-pass analogue
        of :meth:`cache` for data too big (or too numerous in epochs) to
        pin in memory. Each partition runs this frame's FULL plan once,
        writes the result atomically (tmp + rename), and every later
        materialization streams the file back; partition identity
        (``logical_index``) is preserved so per-epoch partition shuffles
        (``with_partition_order``) compose. Intended for host-stage
        plans (decode/resize); a device stage inside the spilled plan
        would run outside the engine's device lock on first load, and
        the spilled stages run inside ``Source.load`` so StageMetrics
        does not time them (the trade for running them at most once).
        Each executing machine spills to ITS OWN ``directory`` — on a
        distributed engine the cache is per-machine, not shared.

        A populated ``directory`` is only reused when its manifest
        matches this frame's SHAPE (schema + partition count) and the
        caller-supplied ``fingerprint``. Shape alone cannot distinguish
        two datasets with identical schema — callers reusing a cache
        directory across runs should pass a content fingerprint (e.g. a
        hash of source paths); mismatches raise rather than silently
        returning another dataset's rows."""
        import json

        os.makedirs(directory, exist_ok=True)
        plan = list(self._plan)
        preserving = all(st.row_preserving for st in plan)
        manifest_path = os.path.join(directory, "_manifest.json")
        manifest = {"schema": self.schema.to_string(),
                    "num_partitions": len(self._sources),
                    "fingerprint": str(fingerprint)}
        # in-process lock + atomic rename: concurrent callers sharing a
        # spill dir (fitMultiple trials) must not race the
        # check-then-act below into spurious "not empty" errors
        with DataFrame._spill_manifest_lock:
            if os.path.exists(manifest_path):
                with open(manifest_path) as f:
                    existing = json.load(f)
                # manifests written before the fingerprint field count
                # as the default fingerprint, not as a mismatch
                existing.setdefault("fingerprint", "")
                if existing != manifest:
                    raise ValueError(
                        f"cache directory {directory!r} holds a spill "
                        "of a DIFFERENT frame (schema, partition count "
                        "or fingerprint mismatch); use a fresh "
                        "directory")
            elif [n for n in os.listdir(directory)
                  if not n.startswith("_manifest.json.tmp")]:
                raise ValueError(
                    f"cache directory {directory!r} is not empty and "
                    "has no spill manifest; use a fresh directory")
            else:
                tmp = (f"{manifest_path}.tmp.{os.getpid()}"
                       f".{threading.get_ident()}")
                with open(tmp, "w") as f:
                    json.dump(manifest, f)
                os.replace(tmp, manifest_path)

        def make(i: int, src: Source) -> Source:
            logical = (src.logical_index
                       if src.logical_index is not None else i)
            path = os.path.join(directory, f"part_{logical:05d}.arrow")

            def _load(src=src, logical=logical, path=path
                      ) -> pa.RecordBatch:
                if os.path.exists(path):
                    with pa.memory_map(path) as source:
                        table = pa.ipc.open_file(source).read_all()
                    return table.combine_chunks().to_batches()[0] \
                        if table.num_rows else \
                        pa.RecordBatch.from_pylist([],
                                                   schema=table.schema)
                from sparkdl_tpu.data.spark_binding import apply_plan
                batch = apply_plan(plan, src.load(), logical)
                # tmp unique per pid AND thread: the engine's
                # early-stop cancel() doesn't stop already-running
                # loads, so a re-submitted partition can overlap one —
                # a shared tmp would interleave writers. The closure
                # may also run on a remote executor where the calling
                # process's makedirs never happened.
                os.makedirs(directory, exist_ok=True)
                tmp = (f"{path}.tmp.{os.getpid()}"
                       f".{threading.get_ident()}")
                with pa.OSFile(tmp, "wb") as sink:
                    with pa.ipc.new_file(sink, batch.schema) as w:
                        w.write_batch(batch)
                os.replace(tmp, path)
                return batch

            # effectful: the first load WRITES the spill file — the
            # engine must drain straggler loads on error so none can
            # re-create a file after the tuning cleanup's rmtree
            return Source(_load,
                          src.num_rows if preserving else None,
                          logical_index=src.logical_index,
                          effectful=True)

        out = DataFrame([make(i, s) for i, s in enumerate(self._sources)],
                        engine=self._engine)
        # schema from the UNDERLYING frame's zero-row probe: the cached
        # frame's plan is empty and its load IS the spilled plan, so
        # the default probe would decode+spill a whole partition just
        # to answer .columns / union schema checks
        out._schema = self.schema
        return out

    def snapshot(self, root: str, fingerprint: str = "",
                 decode_key: Optional[str] = None) -> "DataFrame":
        """A frame backed by the CONTENT-ADDRESSED snapshot store
        (``sparkdl_tpu/inputsvc/snapshot.py``; docs/DATA_SERVICE.md) —
        the multi-run, multi-tenant evolution of :meth:`cache_to_disk`.
        The store key hashes ``fingerprint`` (corpus identity — e.g. a
        hash of source paths) with ``decode_key`` (the decode
        configuration; defaults to the plan's stage-name signature)
        and the snapshot format version: a corpus change, a config
        change, or a format bump each lands in a fresh key directory
        and decodes cold, so a warm hit can NEVER be stale. Chunks are
        self-validating (per-chunk blake2b digests): corruption or
        truncation re-decodes that partition cleanly instead of
        crashing or serving bad rows. The second epoch — or the second
        tenant sharing ``root`` — streams with decode busy-seconds
        ≈ 0 (the ``inputsvc.snapshot_*`` counters tell the story)."""
        from sparkdl_tpu.inputsvc.snapshot import snapshot_sources
        out = DataFrame(
            snapshot_sources(self._sources, list(self._plan),
                             self.schema, root, fingerprint,
                             decode_key),
            engine=self._engine)
        # schema from the UNDERLYING frame (the cache_to_disk
        # reasoning): the snapshot frame's plan is empty and its load
        # IS the decode, so the default probe would decode+write a
        # whole partition just to answer .columns
        out._schema = self.schema
        return out

    def filter_rows(self, mask: np.ndarray) -> "DataFrame":
        """Keep rows where the GLOBAL boolean mask is true (mask indexed in
        collected row order). Used by CrossValidator k-fold splits."""
        table = self.collect()
        if len(mask) != table.num_rows:
            raise ValueError(f"mask length {len(mask)} != rows "
                             f"{table.num_rows}")
        kept = table.filter(pa.array(np.asarray(mask, dtype=bool)))
        return DataFrame.from_table(kept, max(1, len(self._sources)),
                                    self._engine)

    # -- introspection ------------------------------------------------------

    @property
    def num_partitions(self) -> int:
        return len(self._sources)

    @property
    def schema(self) -> pa.Schema:
        """Schema after the plan, computed once on a zero-row prototype
        (stages must tolerate empty batches) and cached — ``limit``/
        ``union``/``show`` all consult it, and a decode-bearing plan
        must not re-load partition 0 per access. When the first source
        publishes a ``schema_hint`` (statically-known leaf schemas:
        in-memory tables, file listings) the prototype is built from it
        WITHOUT loading the partition; otherwise the source loads once
        and is sliced to zero rows."""
        if self._schema is None:
            if not self._sources:
                return pa.schema([])
            src = self._sources[0]
            idx = src.logical_index if src.logical_index is not None else 0
            proto = (_empty_batch(src.schema_hint)
                     if src.schema_hint is not None
                     else src.load().slice(0, 0))
            for stage in self._plan:
                proto = (stage.fn(proto, idx) if stage.with_index
                         else stage.fn(proto))
            self._schema = proto.schema
        return self._schema

    @property
    def schema_probe_free(self) -> bool:
        """Whether reading :attr:`schema` costs no partition load:
        already cached, or the first source publishes a ``schema_hint``
        (the probe then runs the plan on an empty prototype only).
        Free-by-contract callers — ``rename`` validation, sizing
        estimates — consult this instead of silently decoding a
        partition at plan time."""
        return (self._schema is not None or not self._sources
                or self._sources[0].schema_hint is not None)

    @property
    def columns(self) -> List[str]:
        return list(self.schema.names)

    # -- materialization ----------------------------------------------------

    def stream(self) -> Iterator[pa.RecordBatch]:
        """Ordered iterator of fully-transformed partition batches."""
        return self._engine.execute(self._sources, self._plan)

    def collect(self, on_batch=None) -> pa.Table:
        """Materialize the frame as one Arrow table.

        ``on_batch``: optional observer called with each streamed batch
        as it arrives — the seam for byte/row watchdogs (e.g.
        ``LogisticRegression``'s mid-collect budget warning) so callers
        that need to watch the stream don't re-implement collect's
        empty-batch rules."""
        batches = []
        for b in self.stream():
            if on_batch is not None:
                on_batch(b)
            batches.append(b)
        if not batches:
            return pa.table({})
        non_empty = [b for b in batches if b.num_rows]
        if non_empty and len(non_empty) != len(batches):
            # A zero-row batch contributes no rows but MAY carry
            # imprecise column types: a computed column (e.g. a decoded
            # image tensor) cannot infer its row shape from an empty
            # input, so an emptied partition's schema can disagree with
            # the populated ones (plan-stage filters — CV folds,
            # sample — routinely empty whole partitions). Drop them
            # rather than fail the concat.
            batches = non_empty
        elif not non_empty:
            # ALL partitions emptied: the same imprecise-type hazard
            # means sibling empty batches can disagree with each other
            # — keep one as the schema carrier instead of failing a
            # meaningless 0-row concat
            batches = batches[:1]
        return pa.Table.from_batches(batches)

    def collect_rows(self) -> List[Row]:
        return self.collect().to_pylist()

    def to_pandas(self):
        return self.collect().to_pandas()

    def count(self) -> int:
        known = self.known_count()
        if known is not None:
            return known
        return sum(b.num_rows for b in self.stream())

    def known_count(self) -> Optional[int]:
        """Row count WITHOUT executing the plan, or None when it would
        require execution (a non-row-preserving stage, or sources
        without counts). Lets sizing decisions — e.g.
        ``LogisticRegression``'s memory-budget auto-switch — stay free
        instead of silently running an expensive upstream plan twice."""
        if all(st.row_preserving for st in self._plan) and \
                all(s.num_rows is not None for s in self._sources):
            return sum(s.num_rows for s in self._sources)
        return None

    def take(self, n: int) -> List[Row]:
        out: List[Row] = []
        for batch in self.stream():
            out.extend(batch.to_pylist())
            if len(out) >= n:
                break
        return out[:n]

    def first(self) -> Optional[Row]:
        rows = self.take(1)
        return rows[0] if rows else None

    def tensor(self, col: str) -> np.ndarray:
        """Collect one tensor column as a stacked ndarray [N, *shape]."""
        from sparkdl_tpu.data.tensors import arrow_to_tensor
        table = self.collect()
        idx = column_index(table, col)
        return arrow_to_tensor(table.column(idx), table.schema.field(idx))

    def __repr__(self) -> str:
        names = ",".join(self.columns) if self._sources else ""
        return (f"DataFrame[{names}] "
                f"({len(self._sources)} partitions, {len(self._plan)} stages)")
