"""DataFrame/engine tests (the engine seam that replaces Spark local-mode
in the reference's test harness, SURVEY §4.1)."""

import threading

import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu.data import DataFrame, LocalEngine, arrow_to_tensor
from sparkdl_tpu.data.frame import Source
from sparkdl_tpu.data.tensors import append_tensor_column, tensor_shape_of


def _df(n=100, parts=7):
    return DataFrame.from_table(
        pa.table({"x": np.arange(n, dtype=np.float64),
                  "s": [f"r{i}" for i in range(n)]}), parts)


class TestConstruction:
    def test_partition_count(self):
        assert _df(100, 7).num_partitions == 7
        assert _df(3, 8).num_partitions == 3  # capped at rows

    def test_order_preserved(self):
        tab = _df(100, 7).collect()
        np.testing.assert_array_equal(tab.column("x").to_numpy(),
                                      np.arange(100))

    def test_from_pylist(self):
        df = DataFrame.from_pylist([{"a": 1}, {"a": 2}], 2)
        assert df.count() == 2

    def test_schema_and_columns(self):
        df = _df()
        assert df.columns == ["x", "s"]


class TestOps:
    def test_with_column_numpy_tensor(self):
        df = _df(10, 2).with_column(
            "t", lambda b: np.ones((b.num_rows, 2, 3), np.float32))
        t = df.tensor("t")
        assert t.shape == (10, 2, 3)

    def test_tensor_shape_metadata(self):
        batch = pa.RecordBatch.from_pydict({"x": pa.array([1.0, 2.0])})
        batch = append_tensor_column(batch, "t",
                                     np.zeros((2, 4, 5), np.float32))
        assert tensor_shape_of(batch.schema.field("t")) == (4, 5)
        back = arrow_to_tensor(batch.column(1), batch.schema.field("t"))
        assert back.shape == (2, 4, 5)

    def test_select_drop_rename(self):
        df = _df()
        assert df.select("x").columns == ["x"]
        assert df.drop("s").columns == ["x"]
        assert df.rename({"x": "y"}).columns == ["y", "s"]

    def test_filter(self):
        df = _df(100, 5).filter(
            lambda b: b.column(0).to_numpy(zero_copy_only=False) < 10)
        assert df.count() == 10

    def test_filter_rows_global_mask(self):
        mask = np.zeros(100, dtype=bool)
        mask[::2] = True
        df = _df(100, 5).filter_rows(mask)
        assert df.count() == 50
        np.testing.assert_array_equal(
            df.collect().column("x").to_numpy(), np.arange(0, 100, 2))

    def test_count_fast_path_and_slow_path(self):
        df = _df(100, 5)
        assert df.count() == 100
        assert df.filter(lambda b: b.column(0).to_numpy(
            zero_copy_only=False) >= 0).count() == 100

    def test_take_first(self):
        df = _df(100, 5)
        assert df.first()["x"] == 0.0
        assert [r["x"] for r in df.take(3)] == [0.0, 1.0, 2.0]

    def test_chained_lazy_plan(self):
        calls = []

        def stage(b):
            calls.append(1)
            return b

        df = _df(10, 2).map_batches(stage)
        assert not calls  # lazy until materialized
        df.collect()
        assert len(calls) == 2  # once per partition


class TestEngine:
    def test_host_stages_parallel(self):
        """Host stages run on multiple threads."""
        seen = set()

        def stage(b):
            seen.add(threading.current_thread().name)
            return b

        engine = LocalEngine(num_workers=4)
        df = DataFrame.from_table(
            pa.table({"x": np.arange(64.0)}), 16, engine) \
            .map_batches(stage)
        df.collect()
        assert len(seen) >= 2

    def test_with_index_stages_survive_partition_reorder(self):
        """with_index stages (sample's per-partition determinism) must
        see each partition's LOGICAL index, so reordering partitions —
        per-epoch shuffles, host sharding — keeps the same rows
        (regression: the engine passed the positional index)."""
        df = DataFrame.from_table(pa.table({"x": np.arange(400.0)}), 8)
        sampled = df.sample(0.3, seed=5)
        baseline = sorted(r["x"] for r in sampled.collect_rows())

        reordered = sampled.with_partition_order([5, 2, 7, 0, 1, 6, 3, 4])
        got = sorted(r["x"] for r in reordered.collect_rows())
        assert got == baseline

        subset = sampled.with_partition_order([3, 1])
        sub_rows = set(r["x"] for r in subset.collect_rows())
        assert sub_rows <= set(baseline)
        # nested reorder keeps the original identity pinned
        nested = sampled.with_partition_order([3, 1]) \
            .with_partition_order([1, 0])
        assert set(r["x"] for r in nested.collect_rows()) == sub_rows

        # a one-shot iterable must be read once, not consumed by the
        # bounds check and then silently produce a 0-partition frame
        gen = (i for i in [5, 2, 7, 0, 1, 6, 3, 4])
        from_gen = sorted(
            r["x"] for r in sampled.with_partition_order(gen)
            .collect_rows())
        assert from_gen == baseline

        # limit's partially-taken source keeps the pinned identity too:
        # the limited rows must be a prefix of the reordered frame's
        n_lim = 7
        tag = df.with_partition_order([5, 2, 7, 0, 1, 6, 3, 4]) \
            .map_batches(lambda b, i: b.append_column(
                "pid", pa.array([i] * b.num_rows)), with_index=True)
        full_rows = tag.collect_rows()
        lim_rows = tag.limit(n_lim).collect_rows()
        assert lim_rows == full_rows[:n_lim]

    def test_concurrent_frames_share_engine_safely(self):
        """Two frames materializing concurrently on ONE engine (the
        default-engine reality: every transformer shares it) must each
        stream their own partitions in order with no cross-talk, and
        device stages must stay globally serialized across frames."""
        active = [0]
        max_active = [0]
        lock = threading.Lock()

        def dev_stage(b):
            with lock:
                active[0] += 1
                max_active[0] = max(max_active[0], active[0])
            import time
            time.sleep(0.002)
            with lock:
                active[0] -= 1
            return b

        engine = LocalEngine(num_workers=4)
        a = DataFrame.from_table(
            pa.table({"x": np.arange(40.0)}), 8, engine) \
            .map_batches(dev_stage, kind="device")
        b = DataFrame.from_table(
            pa.table({"x": np.arange(100.0, 140.0)}), 8, engine) \
            .map_batches(dev_stage, kind="device")

        results = {}

        def run(name, df):
            results[name] = [r["x"] for r in df.collect_rows()]

        ta = threading.Thread(target=run, args=("a", a))
        tb = threading.Thread(target=run, args=("b", b))
        ta.start(); tb.start(); ta.join(); tb.join()

        assert results["a"] == list(np.arange(40.0))
        assert results["b"] == list(np.arange(100.0, 140.0))
        assert max_active[0] == 1  # device serialization held across frames

    def test_device_stage_serialized(self):
        """Device stages never overlap."""
        active = [0]
        max_active = [0]
        lock = threading.Lock()

        def dev_stage(b):
            with lock:
                active[0] += 1
                max_active[0] = max(max_active[0], active[0])
            import time
            time.sleep(0.005)
            with lock:
                active[0] -= 1
            return b

        engine = LocalEngine(num_workers=8)
        df = DataFrame.from_table(
            pa.table({"x": np.arange(64.0)}), 16, engine) \
            .map_batches(dev_stage, kind="device")
        df.collect()
        assert max_active[0] == 1

    def test_stream_order(self):
        df = _df(50, 10)
        batches = list(df.stream())
        xs = np.concatenate(
            [b.column(0).to_numpy(zero_copy_only=False) for b in batches])
        np.testing.assert_array_equal(xs, np.arange(50))


class TestJoin:
    def _frames(self):
        left = DataFrame.from_table(
            pa.table({"path": [f"p{i}" for i in range(8)],
                      "x": np.arange(8.0)}), 3)
        right = DataFrame.from_table(
            pa.table({"path": [f"p{i}" for i in range(0, 8, 2)],
                      "label": [10, 12, 14, 16]}), 2)
        return left, right

    def test_inner_join_attaches_and_drops(self):
        left, right = self._frames()
        out = left.join(right, on="path").collect()
        assert out.column("path").to_pylist() == \
            ["p0", "p2", "p4", "p6"]
        assert out.column("label").to_pylist() == [10, 12, 14, 16]
        assert out.column("x").to_pylist() == [0.0, 2.0, 4.0, 6.0]

    def test_left_join_keeps_unmatched_with_nulls(self):
        left, right = self._frames()
        out = left.join(right, on="path", how="left").collect()
        assert out.num_rows == 8
        labels = out.column("label").to_pylist()
        assert labels[0::2] == [10, 12, 14, 16]
        assert labels[1::2] == [None] * 4

    def test_join_preserves_tensor_columns(self):
        feats = np.arange(12, dtype=np.float32).reshape(4, 3)
        rb = pa.RecordBatch.from_pylist(
            [{"path": f"p{i}"} for i in range(4)])
        rb = append_tensor_column(rb, "feat", feats)
        right = DataFrame.from_batches([rb])
        left = DataFrame.from_table(
            pa.table({"path": [f"p{i}" for i in range(4)]}), 2)
        out = left.join(right, on="path")
        np.testing.assert_array_equal(out.tensor("feat"), feats)

    def test_join_validation(self):
        left, right = self._frames()
        with pytest.raises(KeyError):
            left.join(right, on="nope")
        with pytest.raises(ValueError, match="how"):
            left.join(right, on="path", how="outer")
        with pytest.raises(ValueError, match="at least one"):
            left.join(right, on=[])
        dup = DataFrame.from_table(
            pa.table({"path": ["p0", "p0"], "label": [1, 2]}), 1)
        with pytest.raises(ValueError, match="duplicate join key"):
            left.join(dup, on="path").collect()
        clash = DataFrame.from_table(
            pa.table({"path": ["p0"], "x": [9.0]}), 1)
        with pytest.raises(ValueError, match="both"):
            left.join(clash, on="path")

    def test_broadcast_size_guard(self):
        """VERDICT r3 weak #7: a right side over the broadcast contract
        raises a named error (not an OOM), before full materialization
        for the row guard; limits are explicitly raisable."""
        left, right = self._frames()
        with pytest.raises(ValueError, match="broadcast_limit_rows"):
            left.join(right, on="path", broadcast_limit_rows=2)
        with pytest.raises(ValueError, match="broadcast_limit_bytes"):
            left.join(right, on="path", broadcast_limit_bytes=16)
        # raising the limit explicitly lets the join through
        out = left.join(right, on="path",
                        broadcast_limit_rows=4).collect()
        assert out.num_rows == 4

    def test_multi_key_separator_safety(self):
        """Key values containing the composite separator must neither
        collide (('x\\x1fy','z') vs ('x','y\\x1fz')) nor mis-match."""
        left = DataFrame.from_table(
            pa.table({"a": ["x\x1fy", "x"], "b": ["z", "y\x1fz"],
                      "v": [1.0, 2.0]}), 1)
        right = DataFrame.from_table(
            pa.table({"a": ["x\x1fy", "x"], "b": ["z", "y\x1fz"],
                      "tag": ["first", "second"]}), 1)
        out = left.join(right, on=["a", "b"]).collect()
        assert out.column("tag").to_pylist() == ["first", "second"]

    def test_join_schema_probe_and_empty_partitions(self):
        """.schema / .columns on a joined frame probes the stage with a
        zero-row batch — the inner-join mask must stay boolean-typed
        there (regression: empty pa.array infers type null, which
        filter() rejects)."""
        left, right = self._frames()
        joined = left.join(right, on="path")
        assert joined.columns == ["path", "x", "label"]
        assert joined.limit(2).collect().num_rows == 2

    def test_multi_key_join(self):
        left = DataFrame.from_table(
            pa.table({"a": [1, 1, 2], "b": ["x", "y", "x"],
                      "v": [1.0, 2.0, 3.0]}), 2)
        right = DataFrame.from_table(
            pa.table({"a": [1, 2], "b": ["y", "x"],
                      "tag": ["one-y", "two-x"]}), 1)
        out = left.join(right, on=["a", "b"]).collect()
        assert out.column("v").to_pylist() == [2.0, 3.0]
        assert out.column("tag").to_pylist() == ["one-y", "two-x"]


class TestCoalesce:
    def test_merges_preserving_order_and_plan(self):
        calls = {"n": 0}

        def counting(batch):
            if batch.num_rows:
                calls["n"] += 1
            return batch

        df = _df(40, 8).map_batches(counting, name="decode")
        c = df.coalesce(3)
        assert c.num_partitions == 3
        assert c.count() == 40  # num_rows survives (row-preserving plan)
        got = c.collect().column("x").to_pylist()
        assert got == df.collect().column("x").to_pylist()
        # the plan ran once per INPUT partition per materialization —
        # coalescing composes, it doesn't re-run or collect globally
        assert calls["n"] == 8 * 2  # c.collect() + df.collect()

    def test_bounded_memory_no_global_collect(self, monkeypatch):
        """Each output partition materializes only its own group —
        streaming a coalesced frame never collects the whole table."""
        df = _df(60, 6)
        c = df.coalesce(2)
        monkeypatch.setattr(DataFrame, "collect", lambda self: (_ for _ in ()).throw(
            AssertionError("coalesce materialized the frame")))
        try:
            seen = [b.num_rows for b in c.stream()]
        finally:
            monkeypatch.undo()
        assert sum(seen) == 60 and len(seen) == 2

    def test_with_index_keeps_input_identity(self):
        """sample() must draw identically coalesced or not — the plan
        runs per INPUT partition with its logical index."""
        df = _df(80, 8).sample(0.5, seed=9)
        a = df.collect().column("x").to_pylist()
        b = df.coalesce(3).collect().column("x").to_pylist()
        assert a == b

    def test_noop_and_clamp(self):
        df = _df(10, 4)
        assert df.coalesce(4) is df
        assert df.coalesce(99) is df
        assert df.coalesce(1).num_partitions == 1
        assert df.coalesce(1).collect().column("x").to_pylist() == \
            df.collect().column("x").to_pylist()

    def test_schema_probe_decodes_nothing(self):
        """.columns on a coalesced frame must come from the pre-seeded
        schema — the load IS the baked plan over a whole group."""
        loads = {"n": 0}

        def counting(batch):
            if batch.num_rows:
                loads["n"] += 1
            return batch

        df = _df(20, 4).map_batches(counting, name="decode")
        df.schema  # probe once on the UNcoalesced frame (zero-row)
        loads["n"] = 0
        c = df.coalesce(2)
        assert c.columns == ["x", "s"]
        assert loads["n"] == 0  # no group decoded to answer .columns

    def test_ships_through_spark_engine(self):
        """A coalesced frame's sources must survive Spark task
        serialization (the group helper drops its engine on the wire)."""
        from tests.test_spark_binding import _FakeSparkSession

        from sparkdl_tpu.data.spark_binding import SparkEngine

        df = _df(24, 6).filter_rows(np.arange(24.0) >= 4)
        c = df.coalesce(2)
        engine = SparkEngine(spark=_FakeSparkSession())
        got = pa.Table.from_batches(
            list(engine.execute(c._sources, c._plan)))
        assert got.column("x").to_pylist() == \
            df.collect().column("x").to_pylist()


class TestParquetIO:
    def test_round_trip_with_tensor_columns(self, tmp_path):
        X = np.arange(40, dtype=np.float32).reshape(10, 4)
        batch = pa.RecordBatch.from_pylist(
            [{"i": int(i)} for i in range(10)])
        batch = append_tensor_column(batch, "feat", X)
        df = DataFrame.from_batches([batch, batch])
        out = str(tmp_path / "pq")
        df.write_parquet(out)

        back = DataFrame.read_parquet(out)
        assert back.num_partitions == 2
        assert back.columns == ["i", "feat"]
        np.testing.assert_array_equal(back.tensor("feat"),
                                      np.concatenate([X, X]))
        # shape metadata survived (multi-dim reshaping still works)
        assert tensor_shape_of(back.collect().schema.field("feat")) \
            == (4,)

    def test_count_reads_footers_not_data(self, tmp_path):
        df = _df(100, 4)
        out = str(tmp_path / "pq")
        df.write_parquet(out)
        back = DataFrame.read_parquet(out)
        assert back.count() == 100  # from parquet metadata (num_rows)

    def test_image_struct_round_trip(self, tmp_path, image_dir):
        from sparkdl_tpu.image import imageIO

        df = imageIO.readImages(image_dir, numPartitions=2)
        out = str(tmp_path / "imgs_pq")
        df.write_parquet(out)
        back = DataFrame.read_parquet(out)
        a = df.collect()
        b = back.collect()
        assert a.column("filePath").to_pylist() == \
            b.column("filePath").to_pylist()
        assert a.column("image").to_pylist() == \
            b.column("image").to_pylist()

    def test_no_silent_overwrite_and_missing_path(self, tmp_path):
        df = _df(10, 2)
        out = str(tmp_path / "pq")
        df.write_parquet(out)
        with pytest.raises(FileExistsError, match="fresh"):
            df.write_parquet(out)
        with pytest.raises(FileNotFoundError):
            DataFrame.read_parquet(str(tmp_path / "empty_dir"))

    def test_success_marker_gates_reads(self, tmp_path, caplog):
        import logging
        import os

        df = _df(10, 2)
        out = str(tmp_path / "pq")
        df.write_parquet(out)
        assert os.path.exists(os.path.join(out, "_SUCCESS"))
        with caplog.at_level(logging.WARNING):
            DataFrame.read_parquet(out)
        assert "partial" not in caplog.text.lower()

        os.remove(os.path.join(out, "_SUCCESS"))
        # marker-less with NO staging remnant = a foreign writer
        # (pyarrow/pandas, Spark with the marker suppressed — none
        # require _SUCCESS on read): warn-and-serve
        with caplog.at_level(logging.WARNING):
            back = DataFrame.read_parquet(out)
        assert "did not commit" in caplog.text
        assert back.count() == 10

        # a _tmp.* staging remnant is a DEFINITIVE interrupted
        # write_parquet commit: refused without explicit opt-in
        os.mkdir(os.path.join(out, "_tmp.123"))
        with pytest.raises(FileNotFoundError, match="PARTIAL"):
            DataFrame.read_parquet(out)
        back = DataFrame.read_parquet(out, allow_uncommitted=True)
        assert back.count() == 10

    def test_failed_write_leaves_no_partial_dataset(self, tmp_path):
        """A crash mid-stream must not leave part files a later
        read_parquet would silently serve as a complete dataset — parts
        stage in a temp subdir and only rename into place on success."""
        import glob
        import os

        boom = {"n": 0}

        def failing(batch):
            boom["n"] += 1
            if boom["n"] == 2:
                raise RuntimeError("decode exploded on partition 2")
            return batch

        df = _df(30, 3).map_batches(failing)
        out = str(tmp_path / "pq")
        with pytest.raises(RuntimeError, match="exploded"):
            df.write_parquet(out)
        assert glob.glob(os.path.join(out, "*.parquet")) == []
        assert not glob.glob(os.path.join(out, "_tmp*"))
        # the directory is reusable after the failure
        boom["n"] = -100
        df.write_parquet(out)
        assert DataFrame.read_parquet(out).count() == 30

    def test_schema_from_footer_not_data(self, tmp_path):
        """Reading .columns on a read_parquet frame must come from the
        parquet footer, not a full read of part 0."""
        df = _df(10, 2)
        out = str(tmp_path / "pq")
        df.write_parquet(out)
        import pyarrow.parquet as pq
        orig = pq.read_table
        reads = []
        pq.read_table = lambda *a, **k: (reads.append(a),
                                         orig(*a, **k))[1]
        try:
            back = DataFrame.read_parquet(out)
            assert back.columns == ["x", "s"]
        finally:
            pq.read_table = orig
        assert reads == []  # schema answered without touching data


class TestCacheToDisk:
    def test_spills_once_and_rereads_identically(self, tmp_path):
        calls = {"n": 0}

        def expensive(batch):
            if batch.num_rows:  # zero-row schema probes are free
                calls["n"] += 1
            return batch.append_column(
                "y", pa.array(np.asarray(batch.column("x")) * 2.0))

        df = DataFrame.from_table(
            pa.table({"x": np.arange(12.0)}), 3).map_batches(expensive)
        cached = df.cache_to_disk(str(tmp_path / "spill"))
        first = cached.collect()
        assert calls["n"] == 3  # one plan run per partition
        second = cached.collect()
        assert calls["n"] == 3  # later passes stream the Arrow files
        assert first.equals(second)
        assert second.column("y").to_pylist() == \
            list(np.arange(12.0) * 2.0)

    def test_preserves_partition_identity_for_shuffles(self, tmp_path):
        df = DataFrame.from_table(pa.table({"x": np.arange(9.0)}), 3)
        cached = df.cache_to_disk(str(tmp_path / "spill"))
        cached.collect()  # spill
        reordered = cached.with_partition_order([2, 0, 1])
        got = reordered.collect().column("x").to_pylist()
        assert got == [6.0, 7.0, 8.0, 0.0, 1.0, 2.0, 3.0, 4.0, 5.0]

    def test_rejects_foreign_or_unmanifested_directory(self, tmp_path):
        """A populated cache dir is reused only when its manifest
        matches this frame — never silently serving another frame's
        spilled rows."""
        d = str(tmp_path / "spill")
        df1 = DataFrame.from_table(pa.table({"x": np.arange(6.0)}), 2)
        df1.cache_to_disk(d).collect()

        # same schema + partitions → warm reuse is allowed
        again = DataFrame.from_table(
            pa.table({"x": np.arange(6.0)}), 2).cache_to_disk(d)
        assert again.collect().column("x").to_pylist() == \
            list(np.arange(6.0))

        # different schema → refuse
        df2 = DataFrame.from_table(pa.table({"y": np.arange(6.0)}), 2)
        with pytest.raises(ValueError, match="DIFFERENT frame"):
            df2.cache_to_disk(d)
        # different partition count → refuse
        df3 = DataFrame.from_table(pa.table({"x": np.arange(6.0)}), 3)
        with pytest.raises(ValueError, match="DIFFERENT frame"):
            df3.cache_to_disk(d)

        # non-empty dir without a manifest → refuse
        stray = tmp_path / "stray"
        stray.mkdir()
        (stray / "junk.bin").write_bytes(b"x")
        with pytest.raises(ValueError, match="no spill manifest"):
            df1.cache_to_disk(str(stray))

    def test_concurrent_callers_share_a_spill_dir(self, tmp_path):
        """fitMultiple's trials call cache_to_disk on the SAME dir from
        threads; the manifest check-then-act must not race into
        spurious 'not empty' errors."""
        import concurrent.futures

        d = str(tmp_path / "spill")
        table = pa.table({"x": np.arange(30.0)})

        def run(_):
            df = DataFrame.from_table(table, 3)
            return df.cache_to_disk(d).collect().num_rows

        with concurrent.futures.ThreadPoolExecutor(8) as ex:
            got = list(ex.map(run, range(8)))
        assert got == [30] * 8

    def test_pre_fingerprint_manifest_still_reusable(self, tmp_path):
        """Manifests written before the fingerprint field existed must
        count as the default fingerprint, not as a mismatch."""
        import json
        import os

        d = str(tmp_path / "spill")
        df = DataFrame.from_table(pa.table({"x": np.arange(6.0)}), 2)
        df.cache_to_disk(d).collect()
        mp = os.path.join(d, "_manifest.json")
        with open(mp) as f:
            manifest = json.load(f)
        del manifest["fingerprint"]  # simulate an old-version spill
        with open(mp, "w") as f:
            json.dump(manifest, f)
        warm = DataFrame.from_table(
            pa.table({"x": np.arange(6.0)}), 2).cache_to_disk(d)
        assert warm.collect().column("x").to_pylist() == \
            list(np.arange(6.0))

    def test_fingerprint_distinguishes_same_shape_content(self, tmp_path):
        """Same schema + partition count but a different caller
        fingerprint must refuse the warm cache (shape alone cannot see
        content)."""
        d = str(tmp_path / "spill")
        df1 = DataFrame.from_table(pa.table({"x": np.arange(6.0)}), 2)
        df1.cache_to_disk(d, fingerprint="day1").collect()
        df2 = DataFrame.from_table(pa.table({"x": np.arange(6.0) * 9}),
                                   2)
        with pytest.raises(ValueError, match="fingerprint"):
            df2.cache_to_disk(d, fingerprint="day2")

    def test_schema_probe_does_not_spill(self, tmp_path):
        """.columns / union schema checks must come from the underlying
        frame's zero-row probe, not a full decode+spill of partition 0."""
        calls = {"n": 0}

        def expensive(batch):
            if batch.num_rows:  # zero-row probes are free
                calls["n"] += 1
            return batch

        df = DataFrame.from_table(
            pa.table({"x": np.arange(6.0)}), 2).map_batches(expensive)
        cached = df.cache_to_disk(str(tmp_path / "spill"))
        assert cached.columns == ["x"]
        assert calls["n"] == 0  # schema answered without materializing

    def test_tensor_columns_round_trip(self, tmp_path):
        X = np.arange(24, dtype=np.float32).reshape(6, 4)
        batch = pa.RecordBatch.from_pylist(
            [{"i": int(i)} for i in range(6)])
        batch = append_tensor_column(batch, "t", X)
        df = DataFrame.from_batches([batch])
        cached = df.cache_to_disk(str(tmp_path / "spill"))
        cached.collect()
        np.testing.assert_array_equal(cached.tensor("t"), X)


class TestFrameUsability:
    def _df(self, n=20, parts=4):
        return DataFrame.from_pylist(
            [{"x": i} for i in range(n)], num_partitions=parts)

    def test_limit_lazy(self):
        loaded = []

        def make(i):
            def _load():
                loaded.append(i)
                return pa.RecordBatch.from_pydict(
                    {"x": pa.array([i * 10, i * 10 + 1])})
            return Source(_load, 2)

        df = DataFrame([make(i) for i in range(5)])
        out = df.limit(3).collect_rows()
        assert [r["x"] for r in out] == [0, 1, 10]
        assert sorted(loaded) == [0, 1]  # partitions 2..4 never loaded

    def test_limit_after_filter_counts_final_rows(self):
        df = self._df(20, 4).filter(
            lambda b: np.asarray([v % 2 == 0 for v in
                                  b.column(0).to_pylist()], dtype=bool))
        out = df.limit(5).collect_rows()
        assert [r["x"] for r in out] == [0, 2, 4, 6, 8]

    def test_limit_zero_and_over(self):
        assert self._df(5).limit(0).count() == 0
        assert self._df(5).limit(99).count() == 5

    def test_union(self):
        a = self._df(3).with_column(
            "y", lambda b: np.asarray(b.column(0).to_pylist(),
                                      np.float32))
        b = self._df(2).with_column(
            "y", lambda b: np.asarray(b.column(0).to_pylist(),
                                      np.float32))
        u = a.union(b)
        assert u.count() == 5
        assert [r["x"] for r in u.collect_rows()] == [0, 1, 2, 0, 1]

    def test_limit_over_unknown_count_partitions(self):
        """limit(n) must return exactly n rows even when partition row
        counts are unknown — union of different-plan frames produces
        deferred sources with num_rows=None, and a lazy prefix that
        stops at the first unknown source silently under-returns
        (regression: limit(5) over 6+6 rows returned 3)."""
        a = self._df(6, 2).filter_rows(np.ones(6, bool))  # non-preserving
        b = self._df(6, 2)
        u = a.union(b)
        assert u.count() == 12
        got = [r["x"] for r in u.limit(5).collect_rows()]
        assert got == [0, 1, 2, 3, 4]
        assert u.limit(0).count() == 0
        assert u.limit(12).count() == 12
        assert u.limit(50).count() == 12

    def test_sample(self):
        df = self._df(200, 4)
        kept = df.sample(0.3, seed=7).count()
        assert 30 <= kept <= 90  # loose Bernoulli bounds
        assert df.sample(0.0).count() == 0
        assert df.sample(1.0).count() == 200

    def test_show_renders(self, capsys):
        self._df(3).show()
        out = capsys.readouterr().out
        assert "| x" in out and "| 2" in out

    def test_schema_cached_across_accesses(self):
        """Repeated schema accesses (limit/union/show all consult it)
        must not re-load partition 0 or re-run plan stages."""
        loads, stage_runs = [], []

        def _load():
            loads.append(1)
            return pa.RecordBatch.from_pydict({"x": pa.array([1, 2])})

        def _probe(batch):
            stage_runs.append(1)
            return batch

        df = DataFrame([Source(_load, 2)]).map_batches(_probe, name="probe")
        for _ in range(5):
            _ = df.schema
            _ = df.columns
        assert len(loads) == 1
        assert len(stage_runs) == 1
        # materialization still runs the stage (on the real batch)
        assert df.count() == 2

    def test_sample_partition_index_determinism(self):
        """sample() must see the true partition index on every engine
        path: same frame re-materialized gives identical rows, and
        distinct partitions don't all reuse index 0's coin flips."""
        df = self._df(400, 4)
        s = df.sample(0.5, seed=11)
        first = [r["x"] for r in s.collect_rows()]
        second = [r["x"] for r in s.collect_rows()]
        assert first == second
        # partitions hold disjoint value ranges (0-99, 100-199, ...); if
        # every partition were sampled with the same rng the kept row
        # *offsets* within each partition would coincide — astronomically
        # unlikely with per-index seeding.
        offsets = [sorted(v % 100 for v in first if v // 100 == p)
                   for p in range(4)]
        assert not all(o == offsets[0] for o in offsets[1:])


class TestEngineScale:
    def test_many_partitions_stream_bounded(self):
        """64 partitions stream through the engine in order with bounded
        in-flight load (backpressure: peak concurrent loads stays near
        max_inflight, far below the partition count)."""
        import threading
        engine = LocalEngine(num_workers=4, max_inflight=4)
        live = {"now": 0, "peak": 0}
        lock = threading.Lock()

        def make(i):
            def _load():
                with lock:
                    live["now"] += 1
                    live["peak"] = max(live["peak"], live["now"])
                batch = pa.RecordBatch.from_pydict(
                    {"x": pa.array(np.full(100, i))})
                with lock:
                    live["now"] -= 1
                return batch
            return Source(_load, 100)

        df = DataFrame([make(i) for i in range(64)], engine=engine)
        total = 0
        last = -1
        for batch in df.map_batches(lambda b: b).stream():
            v = batch.column(0)[0].as_py()
            assert v == last + 1  # partition order preserved
            last = v
            total += batch.num_rows
        assert total == 6400
        assert live["peak"] <= 8  # bounded, not 64


class TestCrossPartitionRechunk:
    """Engine-level device-batch alignment (VERDICT r4 next #3): a
    row-preserving device stage with a batch_hint is fed hint-aligned
    row blocks spanning partition boundaries, so partitions smaller
    than the device batch stop padding the static shape (the measured
    2.4× tax, BASELINE.md). Chunk count is the deterministic proxy for
    the throughput criterion: 32-row partitions at batch 128 must
    dispatch exactly ceil(N/128) device chunks — identical to the
    batch-aligned layout — instead of one padded chunk per partition."""

    def _frame_and_transformer(self, n_rows, n_parts, batch_size,
                               width=6):
        from sparkdl_tpu.graph.function import ModelFunction
        from sparkdl_tpu.transformers.tensor_transform import (
            TensorTransformer,
        )

        rng = np.random.default_rng(42)
        feats = rng.normal(size=(n_rows, width)).astype(np.float32)
        tbl = pa.table({"rid": pa.array(np.arange(n_rows))})
        batch = pa.RecordBatch.from_pydict({"rid": tbl.column("rid")
                                            .combine_chunks()})
        batch = append_tensor_column(batch, "x", feats)
        df = DataFrame.from_table(pa.Table.from_batches([batch]),
                                  num_partitions=n_parts)

        def apply_fn(params, inputs):
            import jax.numpy as jnp
            return {"y": jnp.tanh(inputs["x"]) * 2.0}

        mf = ModelFunction(apply_fn, params={},
                           input_signature={"x": ((width,), np.float32)},
                           output_names=["y"])
        t = TensorTransformer(modelFunction=mf,
                              inputMapping={"x": "x"},
                              outputMapping={"y": "y"},
                              batchSize=batch_size)
        return df, t, feats

    def test_small_partitions_dispatch_aligned_chunks(self):
        df, t, feats = self._frame_and_transformer(512, 16, 128)
        out = t.transform(df)
        got = out.tensor("y")
        # exactly ceil(512/128)=4 device chunks, not 16 padded ones
        assert t.metrics.batches == 4, t.metrics.batches
        np.testing.assert_allclose(got, np.tanh(feats) * 2.0,
                                   atol=1e-6)
        # row identity: rid column still pairs with its own row's output
        rids = out.collect().column("rid").to_numpy()
        np.testing.assert_array_equal(rids, np.arange(512))

    def test_uneven_partitions_and_tail_flush(self):
        # 19 rows over 4 uneven partitions, batch 4: greedy dispatch
        # still totals ceil(19/4)=5 chunks, tail padded once at flush
        from sparkdl_tpu.graph.function import ModelFunction
        from sparkdl_tpu.transformers.tensor_transform import (
            TensorTransformer,
        )
        rng = np.random.default_rng(1)
        sizes = [5, 3, 9, 2]
        batches = []
        offset = 0
        for s in sizes:
            b = pa.RecordBatch.from_pydict(
                {"rid": pa.array(np.arange(offset, offset + s))})
            b = append_tensor_column(
                b, "x", rng.normal(size=(s, 3)).astype(np.float32))
            batches.append(b)
            offset += s
        sources = [Source((lambda bb=bb: bb), bb.num_rows)
                   for bb in batches]
        df = DataFrame(sources)

        def apply_fn(params, inputs):
            return {"y": inputs["x"] + 1.0}

        mf = ModelFunction(apply_fn, params={},
                           input_signature={"x": ((3,), np.float32)},
                           output_names=["y"])
        t = TensorTransformer(modelFunction=mf, inputMapping={"x": "x"},
                              outputMapping={"y": "y"}, batchSize=4)
        out = t.transform(df)
        table = out.collect()
        assert t.metrics.batches == 5, t.metrics.batches
        np.testing.assert_array_equal(
            table.column("rid").to_numpy(), np.arange(19))
        x = arrow_to_tensor(table.column("x"))
        y = arrow_to_tensor(table.column("y"))
        np.testing.assert_allclose(y, x + 1.0, atol=1e-6)

    def test_empty_partition_mid_stream(self):
        from sparkdl_tpu.graph.function import ModelFunction
        from sparkdl_tpu.transformers.tensor_transform import (
            TensorTransformer,
        )
        mk = lambda lo, n: append_tensor_column(  # noqa: E731
            pa.RecordBatch.from_pydict(
                {"rid": pa.array(np.arange(lo, lo + n))}),
            "x", np.full((n, 2), 1.5, np.float32))
        batches = [mk(0, 3), mk(3, 0), mk(3, 4)]
        df = DataFrame([Source((lambda bb=bb: bb), bb.num_rows)
                        for bb in batches])

        def apply_fn(params, inputs):
            return {"y": inputs["x"] * 3.0}

        mf = ModelFunction(apply_fn, params={},
                           input_signature={"x": ((2,), np.float32)},
                           output_names=["y"])
        t = TensorTransformer(modelFunction=mf, inputMapping={"x": "x"},
                              outputMapping={"y": "y"}, batchSize=4)
        table = t.transform(df).collect()
        np.testing.assert_array_equal(table.column("rid").to_numpy(),
                                      np.arange(7))
        np.testing.assert_allclose(arrow_to_tensor(table.column("y")),
                                   np.full((7, 2), 4.5), atol=1e-6)

    def test_downstream_host_stage_and_filter(self):
        df, t, feats = self._frame_and_transformer(40, 10, 16)
        out = t.transform(df)
        out = out.with_column(
            "norm", lambda b: np.linalg.norm(
                arrow_to_tensor(b.column(b.schema.get_field_index("y"))),
                axis=1).astype(np.float32))
        out = out.filter(lambda b: pa.array(
            b.column(b.schema.get_field_index("rid")).to_numpy() % 2
            == 0))
        table = out.collect()
        assert table.num_rows == 20
        np.testing.assert_array_equal(
            table.column("rid").to_numpy() % 2, 0)

    def test_stream_stage_retries_transient_errors(self):
        calls = {"n": 0}

        def flaky(batch):
            calls["n"] += 1
            if calls["n"] == 1:
                raise OSError("transient")
            return batch

        b = pa.RecordBatch.from_pydict({"v": pa.array([1, 2, 3])})
        df = DataFrame([Source(lambda: b, 3)])
        df = df.map_batches(flaky, kind="device", batch_hint=2,
                            name="flaky")
        table = df.collect()
        assert table.num_rows == 3
        assert calls["n"] >= 2

    def test_row_nonpreserving_device_stage_not_rechunked(self):
        """A device stage that drops rows must keep per-partition
        execution (the re-chunk path requires 1:1 rows)."""
        def drop_first(batch):
            return batch.slice(1)

        batches = [pa.RecordBatch.from_pydict({"v": pa.array([1, 2])}),
                   pa.RecordBatch.from_pydict({"v": pa.array([3, 4])})]
        df = DataFrame([Source((lambda bb=bb: bb), 2)
                        for bb in batches])
        df = df.map_batches(drop_first, kind="device",
                            row_preserving=False, batch_hint=64,
                            name="drop")
        assert df.collect().column("v").to_pylist() == [2, 4]

    def test_misaligned_throughput_parity_cpu(self):
        """The VERDICT r4 #3 criterion: 32-row partitions at batch 128
        reach ≥90% of batch-aligned throughput on CPU. Both layouts now
        dispatch identical device chunks (the deterministic guarantee
        asserted above); the wall-clock ratio check uses a model heavy
        enough that chunk count dominates scheduling noise."""
        import time

        from sparkdl_tpu.graph.function import ModelFunction
        from sparkdl_tpu.transformers.tensor_transform import (
            TensorTransformer,
        )
        rng = np.random.default_rng(7)
        n, width = 512, 256
        feats = rng.normal(size=(n, width)).astype(np.float32)
        w = rng.normal(size=(width, width)).astype(np.float32) * 0.05

        def apply_fn(params, inputs):
            import jax.numpy as jnp
            x = inputs["x"]
            for _ in range(8):
                x = jnp.tanh(x @ params["w"])
            return {"y": x}

        mf = ModelFunction(apply_fn, params={"w": w},
                           input_signature={"x": ((width,), np.float32)},
                           output_names=["y"])

        def make_layout(n_parts):
            base = pa.RecordBatch.from_pydict(
                {"rid": pa.array(np.arange(n))})
            base = append_tensor_column(base, "x", feats)
            df = DataFrame.from_table(pa.Table.from_batches([base]),
                                      num_partitions=n_parts)
            t = TensorTransformer(modelFunction=mf,
                                  inputMapping={"x": "x"},
                                  outputMapping={"y": "y"},
                                  batchSize=128)
            t.transform(df).collect()  # warm the jit
            return df, t

        def one_pass(df, t):
            t0 = time.perf_counter()
            out = t.transform(df).collect()
            dt = time.perf_counter() - t0
            assert out.num_rows == n
            return dt

        # chunk parity (asserted above, exact) is the hard ≥90%
        # guarantee — identical device dispatches; this wall-clock
        # check is a smoke bound with slack for CI scheduler noise.
        # Passes ALTERNATE layouts so a load spike on a small shared
        # runner degrades both bests instead of tanking whichever
        # layout it happened to land on.
        aligned = make_layout(4)    # 128-row partitions
        small = make_layout(16)     # 32-row partitions
        t_aligned = t_small = float("inf")
        for _ in range(5):
            t_aligned = min(t_aligned, one_pass(*aligned))
            t_small = min(t_small, one_pass(*small))
        batches = small[1].metrics.batches
        assert batches % 4 == 0  # ceil(512/128) per pass, no extras
        ratio = t_aligned / t_small
        assert ratio >= 0.6, (t_small, t_aligned, ratio)


class TestOutOfCoreRepartition:
    """VERDICT r4 #6: repartition(cacheDir=...) must re-cut a frame
    UPWARD in partition count without ever materializing it whole."""

    def _frame(self, n=96, parts=4):
        rng = np.random.default_rng(5)
        tbl = pa.table({"rid": np.arange(n),
                        "v": rng.normal(size=n)})
        df = DataFrame.from_table(tbl, parts)
        # a plan stage proves the spill runs the full plan, not raw
        # sources
        return df.map_batches(lambda b: b.append_column(
            "v2", pa.array(np.asarray(b.column(1)) * 2.0)))

    def test_upward_repartition_spill_backed(self, tmp_path,
                                             monkeypatch):
        df = self._frame()
        # the memory-bounded proof pattern (cf. CV cacheDir): global
        # collect is FORBIDDEN for the whole operation
        monkeypatch.setattr(
            DataFrame, "collect",
            lambda self: (_ for _ in ()).throw(
                AssertionError("repartition(cacheDir) must not "
                               "collect the frame")))
        out = df.repartition(12, cacheDir=str(tmp_path))
        assert out.num_partitions == 12
        rows = 0
        rids = []
        for b in out.stream():
            assert b.num_rows == 8  # 96/12, contiguous even ranges
            rows += b.num_rows
            rids.extend(b.column(b.schema.get_field_index("rid"))
                        .to_pylist())
        assert rows == 96
        assert rids == list(range(96))  # row order preserved

    def test_plan_applied_before_spill(self, tmp_path):
        df = self._frame()
        out = df.repartition(6, cacheDir=str(tmp_path))
        t = out.collect()
        np.testing.assert_allclose(
            np.asarray(t.column("v2")), np.asarray(t.column("v")) * 2.0)

    def test_count_uses_footers_not_data(self, tmp_path):
        df = self._frame()
        out = df.repartition(10, cacheDir=str(tmp_path))
        assert out.count() == 96
        # each source advertises its exact range size, near-even split
        sizes = [s.num_rows for s in out._sources]
        assert sum(sizes) == 96 and len(sizes) == 10
        assert set(sizes) <= {9, 10}

    def test_in_memory_path_unchanged(self):
        df = self._frame()
        out = df.repartition(3)
        assert out.num_partitions == 3
        assert out.count() == 96


class TestColumnCollisions:
    """Arrow happily stores duplicate column names, and every by-name
    lookup then silently serves the FIRST (stale) one — so name
    collisions follow pyspark: with_column REPLACES in place
    (withColumn semantics); transformer/model output columns RAISE
    (Spark ML's 'output column already exists'); joins keep Spark's
    duplicate-name behavior."""

    def test_with_column_replaces_in_place(self):
        df = _df(10, 2).with_column(
            "x", lambda b: pa.array(np.full(b.num_rows, 7.5)))
        table = df.collect()
        assert table.schema.names == ["x", "s"]  # position preserved
        np.testing.assert_array_equal(table.column("x").to_numpy(), 7.5)
        # tensor-valued replacement too
        df2 = _df(6, 2).with_column(
            "x", lambda b: np.ones((b.num_rows, 2), np.float32))
        t2 = df2.collect()
        assert t2.schema.names == ["x", "s"]
        assert arrow_to_tensor(t2.column("x")).shape == (6, 2)

    def test_transformer_output_collision_raises(self):
        from sparkdl_tpu.graph.function import ModelFunction
        from sparkdl_tpu.transformers.tensor_transform import (
            TensorTransformer,
        )

        b = pa.RecordBatch.from_pydict({"rid": pa.array([0, 1])})
        b = append_tensor_column(b, "x", np.ones((2, 3), np.float32))
        df = DataFrame.from_batches([b])
        mf = ModelFunction(lambda p, i: {"y": i["x"] * 2}, params={},
                           input_signature={"x": ((3,), np.float32)},
                           output_names=["y"])
        t = TensorTransformer(modelFunction=mf, inputMapping={"x": "x"},
                              outputMapping={"y": "x"}, batchSize=2)
        with pytest.raises(ValueError, match="already exists"):
            t.transform(df).collect()

    def test_rename_collision_raises(self):
        # EAGER when the schema is free: the error fires at rename()
        with pytest.raises(ValueError, match="duplicate"):
            _df(6, 2).rename({"x": "s"})
        # hint-less sources must NOT load a partition at rename() —
        # validation defers to execution, same error
        loads = {"n": 0}
        b = pa.RecordBatch.from_pydict(
            {"x": pa.array([1.0]), "s": pa.array(["a"])})

        def load():
            loads["n"] += 1
            return b

        df = DataFrame([Source(load, 1)])
        renamed = df.rename({"x": "s"})  # no raise, no load
        assert loads["n"] == 0
        with pytest.raises(ValueError, match="duplicate"):
            renamed.collect()

    def test_rename_tolerates_preexisting_duplicates(self):
        # only count INCREASES are the mapping's fault: a frame already
        # carrying duplicate names may rename its OTHER columns
        b = pa.RecordBatch.from_arrays(
            [pa.array([1.0]), pa.array([2.0]), pa.array([3.0])],
            names=["x", "x", "y"])
        df = DataFrame.from_batches([b])
        out = df.rename({"y": "z"}).collect()
        assert out.schema.names == ["x", "x", "z"]

    def test_nonpositive_partition_counts_raise(self):
        # Spark raises for repartition/coalesce(<=0); clamping hid typos
        df = _df(10, 2)
        with pytest.raises(ValueError, match="positive"):
            df.repartition(0)
        with pytest.raises(ValueError, match="positive"):
            df.repartition(-3)
        with pytest.raises(ValueError, match="positive"):
            df.coalesce(0)

    def test_ambiguous_column_message(self):
        # duplicated names read as -1 from get_field_index; the lookup
        # error must say AMBIGUOUS, not missing
        from sparkdl_tpu.data.frame import column_index
        b = pa.RecordBatch.from_arrays(
            [pa.array([1.0]), pa.array([2.0])], names=["x", "x"])
        with pytest.raises(KeyError, match="ambiguous"):
            column_index(b, "x")

    def test_lr_output_collision_raises(self):
        from sparkdl_tpu.estimators import LogisticRegression

        b = pa.RecordBatch.from_pylist(
            [{"label": 0, "prediction": 9.0},
             {"label": 1, "prediction": 9.0}])
        b = append_tensor_column(
            b, "features", np.eye(2, dtype=np.float32))
        df = DataFrame.from_batches([b])
        model = LogisticRegression(maxIter=2).fit(df)
        with pytest.raises(ValueError, match="already exists"):
            model.transform(df).collect()


class TestCollectSeam:
    def test_on_batch_observes_every_batch(self):
        seen = []
        table = _df(40, 4).collect(on_batch=lambda b: seen.append(
            b.num_rows))
        assert table.num_rows == 40
        assert sum(seen) == 40 and len(seen) == 4

    def test_all_empty_keeps_one_schema_carrier(self):
        # every partition emptied: sibling empty batches may carry
        # imprecise computed-column types that disagree — collect keeps
        # one as the schema carrier instead of failing the concat
        df = _df(40, 4).filter(lambda b: np.zeros(b.num_rows, bool))
        table = df.collect()
        assert table.num_rows == 0
        assert table.schema.names == ["x", "s"]


class TestSchemaHint:
    """Leaf sources with a statically-known schema publish it as
    ``Source.schema_hint`` so the zero-row schema probe never
    materializes partition 0 (review r5: LR's free sizing estimate was
    decoding a whole image partition just to read the feature width)."""

    def test_schema_probe_does_not_load_partition(self):
        loads = {"n": 0}
        batch = pa.RecordBatch.from_pydict(
            {"x": pa.array([1.0, 2.0]), "s": pa.array(["a", "b"])})

        def load():
            loads["n"] += 1
            return batch

        df = DataFrame([Source(load, batch.num_rows,
                               schema_hint=batch.schema)])
        assert df.columns == ["x", "s"]
        assert loads["n"] == 0  # hint answered the probe
        assert df.collect().num_rows == 2
        assert loads["n"] == 1

    def test_plan_stages_run_on_hint_prototype(self):
        # the probe still runs the plan (on a zero-row prototype), so
        # plan-added columns appear in .columns without a load
        loads = {"n": 0}
        batch = pa.RecordBatch.from_pydict({"x": pa.array([1.0, 2.0])})

        def load():
            loads["n"] += 1
            return batch

        df = DataFrame([Source(load, 2, schema_hint=batch.schema)])
        df = df.with_column(
            "y", lambda b: np.zeros((b.num_rows, 3), np.float32))
        assert df.columns == ["x", "y"]
        assert loads["n"] == 0

    def test_files_frame_schema_without_reading_files(self):
        from sparkdl_tpu.image.imageIO import filesToDF

        df = filesToDF(["/nonexistent/zzz.bin"], numPartitions=1)
        assert df.columns == ["filePath", "fileData"]  # no open()
        with pytest.raises(Exception):
            df.collect()

    def test_reader_hint_schema_matches_loaded(self, tmp_path):
        # the hint path must produce EXACTLY the loaded path's schema,
        # through the full decode plans of both readers
        from PIL import Image

        from sparkdl_tpu.image import imageIO

        rng = np.random.default_rng(0)
        for i in range(2):
            Image.fromarray(
                rng.integers(0, 255, (16, 20, 3), dtype=np.uint8),
                "RGB").save(tmp_path / f"i{i}.png")
        for df in (imageIO.readImages(str(tmp_path), numPartitions=2),
                   imageIO.readImagesPacked(str(tmp_path), (8, 8),
                                            numPartitions=2)):
            assert df.schema == df.collect().schema


class TestRechunkChaos:
    """Interaction coverage: the re-chunk stream phase composed with
    TRANSIENT failures injected into every stage kind at once — random
    partition layouts (empties included), an upstream host stage, the
    re-chunked device stage, and a pooled downstream host stage, all
    failing intermittently with retryable errors. Row identity, order,
    and values must come out exact; retries must not double-apply."""

    def test_random_layouts_with_transient_failures(self):
        import pyarrow as pa

        from sparkdl_tpu.data.engine import LocalEngine
        from sparkdl_tpu.data.frame import Source, Stage

        rng = np.random.default_rng(7)
        for trial in range(4):
            sizes = [int(s) for s in
                     rng.integers(0, 9, size=int(rng.integers(3, 9)))]
            n = sum(sizes)
            if n == 0:
                sizes.append(5)
                n = 5
            batches, lo = [], 0
            for s in sizes:
                batches.append(pa.RecordBatch.from_pydict(
                    {"rid": pa.array(np.arange(lo, lo + s))}))
                lo += s
            # failure schedule keyed on batch CONTENT (first rid), not
            # call order — pool interleaving must not shift which call
            # fails, and a retried batch recomputes the same key so it
            # fails exactly ONCE per (stage, batch) and then succeeds
            # within max_retries. Guarded: concurrent first attempts of
            # different batches share the set.
            lock = threading.Lock()
            failed_once: set = set()

            def flaky(kind, batch, transform):
                key = (kind, batch.column(0)[0].as_py()
                       if batch.num_rows else -1)
                with lock:
                    fresh = key not in failed_once
                    failed_once.add(key)
                if fresh:
                    raise OSError(f"transient {kind} {key}")
                return transform(batch)

            def add_col(b, name, fn):
                vals = fn(np.asarray(b.column(0).to_pylist(),
                                     np.float64))
                return b.append_column(name, pa.array(vals))

            plan = [
                Stage(lambda b: flaky(
                    "pre", b, lambda x: add_col(x, "a",
                                                lambda v: v * 2.0)),
                      kind="host", name="pre"),
                Stage(lambda b: flaky(
                    "dev", b, lambda x: add_col(x, "d",
                                                lambda v: v + 0.5)),
                      kind="device", name="dev", batch_hint=4),
                Stage(lambda b: flaky(
                    "post", b, lambda x: add_col(x, "p",
                                                 lambda v: -v)),
                      kind="host", name="post"),
            ]
            sources = [Source((lambda bb=bb: bb), bb.num_rows)
                       for bb in batches]
            eng = LocalEngine(num_workers=3, max_retries=2)
            out = list(eng.execute(sources, plan))
            table = pa.Table.from_batches(
                [b for b in out if b.num_rows] or out[:1])
            assert table.num_rows == n, (trial, sizes)
            rid = np.asarray(table.column("rid").to_pylist(), np.float64)
            np.testing.assert_array_equal(rid, np.arange(n))
            np.testing.assert_allclose(
                np.asarray(table.column("a").to_pylist()), rid * 2.0)
            np.testing.assert_allclose(
                np.asarray(table.column("d").to_pylist()), rid + 0.5)
            np.testing.assert_allclose(
                np.asarray(table.column("p").to_pylist()), -rid)
            assert failed_once, "schedule never injected a failure"


def test_pooled_downstream_quiesces_on_error():
    """review r5: a failing pooled EFFECTFUL stage downstream of a
    re-chunked device stage must DRAIN its in-flight siblings before
    the error reaches the caller — a straggler completing after the
    caller's cleanup (write_parquet sweeping its staging dir) corrupts
    the cleanup's outcome."""
    import time

    from sparkdl_tpu.data.engine import LocalEngine
    from sparkdl_tpu.data.frame import Stage

    eng = LocalEngine(num_workers=4, max_inflight=2, max_retries=0)
    batches = []
    for lo in range(0, 24, 4):
        batches.append(pa.RecordBatch.from_pydict(
            {"rid": pa.array(np.arange(lo, lo + 4))}))
    effects = []

    def host_fn(batch):
        chunk = int(batch.column(0)[0].as_py()) // 4
        if chunk == 0:
            raise ValueError("boom")
        time.sleep(0.2)
        effects.append(time.perf_counter())
        return batch

    plan = [Stage(lambda b: b, kind="device", name="dev", batch_hint=4),
            Stage(host_fn, kind="host", name="fx", effectful=True)]
    sources = [Source((lambda bb=bb: bb), bb.num_rows)
               for bb in batches]
    with pytest.raises(ValueError, match="boom"):
        for _ in eng.execute(sources, plan):
            pass
    t_err = time.perf_counter()
    time.sleep(0.5)  # stragglers would land in this window
    assert all(t <= t_err for t in effects), (effects, t_err)


def test_effectful_source_load_quiesces_on_error():
    """ADVICE r5: cache_to_disk spill sources WRITE IPC files inside
    Source.load — the quiesce gate must consider SOURCE effectfulness,
    not just stage effectfulness, so an error drains in-flight sibling
    loads before control returns (a straggler load completing after
    the tuning-cleanup rmtree would re-create spill files)."""
    import time

    from sparkdl_tpu.data.engine import LocalEngine
    from sparkdl_tpu.data.frame import Source, Stage

    eng = LocalEngine(num_workers=4, max_inflight=8, max_retries=0)
    effects = []

    def make_load(lo, fail=False):
        def _load():
            if fail:
                raise ValueError("boom")
            time.sleep(0.2)
            effects.append(time.perf_counter())  # the spill write
            return pa.RecordBatch.from_pydict(
                {"rid": pa.array(np.arange(lo, lo + 2))})
        return _load

    sources = [Source(make_load(0, fail=True), 2, effectful=True)] + [
        Source(make_load(i * 2), 2, effectful=True)
        for i in range(1, 6)]
    plan = [Stage(lambda b: b, kind="host", name="id")]
    with pytest.raises(ValueError, match="boom"):
        for _ in eng.execute(sources, plan):
            pass
    t_err = time.perf_counter()
    time.sleep(0.5)  # stragglers would land in this window
    assert all(t <= t_err for t in effects), (effects, t_err)


def test_cache_to_disk_sources_marked_effectful():
    """cache_to_disk's spill sources must carry the effectful flag —
    it is what routes them through the drain above."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        df = DataFrame.from_table(
            pa.table({"x": np.arange(8.0)}), 2).cache_to_disk(d)
        assert all(s.effectful for s in df._sources)


def test_concurrent_transforms_of_one_frame():
    """Spark delegated concurrent-job safety to its scheduler; here the
    engine owns it: several threads transforming the SAME frame through
    the SAME ModelFunction (shared jit cache, shared device lock,
    per-call re-chunk bookkeeping) must all get exact, order-preserved
    results."""
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.transformers.tensor_transform import (
        TensorTransformer,
    )

    rng = np.random.default_rng(0)
    X = rng.normal(size=(200, 4)).astype(np.float32)
    b = pa.RecordBatch.from_pydict({"rid": pa.array(np.arange(200))})
    b = append_tensor_column(b, "x", X)
    df = DataFrame.from_table(pa.Table.from_batches([b]), 8)
    mf = ModelFunction(lambda p, i: {"y": i["x"] * 3.0}, params={},
                       input_signature={"x": ((4,), np.float32)},
                       output_names=["y"])
    t = TensorTransformer(modelFunction=mf, inputMapping={"x": "x"},
                          outputMapping={"y": "y"}, batchSize=16)
    results: dict = {}
    errors: list = []
    # barrier: without it the millisecond transforms can run serially
    # and the test would pass without ever overlapping
    gate = threading.Barrier(4)

    def work(i):
        try:
            gate.wait(timeout=10)
            out = t.transform(df).collect()
            results[i] = (np.asarray(out.column("rid").to_pylist()),
                          arrow_to_tensor(out.column("y")))
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(e)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors, errors
    assert len(results) == 4
    for rid, y in results.values():
        np.testing.assert_array_equal(rid, np.arange(200))
        np.testing.assert_allclose(y, X * 3.0, atol=1e-6)


def test_zero_max_inflight_is_not_explicit():
    """max_inflight=0 is a falsy sentinel, not an explicit window:
    treating it as explicit disabled the adaptive load-ahead widening
    while the 0 itself was discarded (review r5 high #5)."""
    from sparkdl_tpu.data.engine import LocalEngine

    eng = LocalEngine(num_workers=4, max_inflight=0)
    assert eng.max_inflight == 8  # the default window
    assert not eng._explicit_inflight
    explicit = LocalEngine(num_workers=4, max_inflight=3)
    assert explicit.max_inflight == 3 and explicit._explicit_inflight


def test_pure_plan_abandonment_does_not_drain():
    """The drain is gated on effectful stages: take(1) on a pure
    decode-heavy plan must return without waiting for the in-flight
    wave of sibling partitions (review r5 high #2). Structural proof:
    partition 0 is fast, siblings slow — siblings must still be
    RUNNING when take returns (with the old unconditional drain, no
    load ever completes after the return)."""
    import time

    from sparkdl_tpu.data.engine import LocalEngine
    from sparkdl_tpu.data.frame import DataFrame, Source

    done = []

    def make_load(lo, seconds):
        def _load():
            time.sleep(seconds)
            done.append(time.perf_counter())
            return pa.RecordBatch.from_pydict(
                {"rid": pa.array(np.arange(lo, lo + 2))})
        return _load

    eng = LocalEngine(num_workers=4, max_inflight=8)
    sources = [Source(make_load(0, 0.05), 2)] + [
        Source(make_load(i * 2, 0.6), 2) for i in range(1, 6)]
    df = DataFrame(sources, engine=eng)
    rows = df.take(1)
    t_ret = time.perf_counter()
    assert len(rows) == 1
    time.sleep(1.0)  # let the abandoned siblings finish
    late = [t for t in done if t > t_ret]
    assert late, "take(1) blocked until every sibling load finished"


def test_interrupted_commit_keeps_refusal_evidence(tmp_path,
                                                   monkeypatch):
    """A write_parquet that fails mid-commit (after some parts moved
    into place) must leave the _tmp.* staging remnant so read_parquet
    refuses the PARTIAL dataset — sweeping it would downgrade the
    failure to 'foreign writer, warn-and-serve' (review r5 finding)."""
    import os

    import sparkdl_tpu.data.frame as fmod

    df = _df(40, 4)
    out = str(tmp_path / "pq")
    orig = os.replace
    calls = {"n": 0}

    def flaky(src, dst, *a, **k):
        if dst.endswith(".parquet") and "_tmp." not in dst:
            calls["n"] += 1
            if calls["n"] == 2:
                raise OSError("simulated commit failure")
        return orig(src, dst, *a, **k)

    monkeypatch.setattr(fmod.os, "replace", flaky)
    with pytest.raises(OSError, match="simulated"):
        df.write_parquet(out)
    assert calls["n"] == 2
    # one part landed, no _SUCCESS, staging remnant kept as evidence
    import glob
    assert glob.glob(os.path.join(out, "*.parquet"))
    assert glob.glob(os.path.join(out, "_tmp.*"))
    with pytest.raises(FileNotFoundError, match="PARTIAL"):
        DataFrame.read_parquet(out)


def test_write_parquet_row_group_cap(tmp_path):
    """row_group_rows caps parquet row-group size so range readers
    (repartition's spill) fetch only overlapping groups, not files."""
    import pyarrow.parquet as pq

    df = _df(40, 2)  # 20 rows per part
    out = str(tmp_path / "pq")
    df.write_parquet(out, row_group_rows=8)
    import glob
    files = sorted(glob.glob(out + "/*.parquet"))
    assert files
    for f in files:
        md = pq.ParquetFile(f).metadata
        assert md.num_row_groups == 3  # ceil(20/8)
        assert max(md.row_group(g).num_rows
                   for g in range(md.num_row_groups)) <= 8


class TestRechunkComposition:
    """Plans with several device stages / interleaved host stages all
    flow through the stream phase correctly."""

    def _mf(self, width, k):
        from sparkdl_tpu.graph.function import ModelFunction

        def apply_fn(params, inputs):
            return {"y": inputs["x"] * k}

        return ModelFunction(apply_fn, params={},
                             input_signature={"x": ((width,),
                                                    np.float32)},
                             output_names=["y"])

    def test_two_chained_device_stages_different_batches(self):
        from sparkdl_tpu.transformers.tensor_transform import (
            TensorTransformer,
        )
        rng = np.random.default_rng(11)
        n = 60
        feats = rng.normal(size=(n, 3)).astype(np.float32)
        b = pa.RecordBatch.from_pydict({"rid": pa.array(np.arange(n))})
        b = append_tensor_column(b, "x", feats)
        df = DataFrame.from_table(pa.Table.from_batches([b]), 12)

        t1 = TensorTransformer(modelFunction=self._mf(3, 2.0),
                               inputMapping={"x": "x"},
                               outputMapping={"y": "x2"}, batchSize=16)
        t2 = TensorTransformer(modelFunction=self._mf(3, -1.0),
                               inputMapping={"x2": "x"},
                               outputMapping={"y": "x3"}, batchSize=7)
        out = t2.transform(t1.transform(df)).collect()
        np.testing.assert_array_equal(out.column("rid").to_numpy(),
                                      np.arange(n))
        np.testing.assert_allclose(arrow_to_tensor(out.column("x3")),
                                   feats * -2.0, atol=1e-6)
        assert t1.metrics.batches == 4   # ceil(60/16)
        assert t2.metrics.batches == 9   # ceil(60/7)

    def test_device_stage_after_filter_after_device_stage(self):
        from sparkdl_tpu.transformers.tensor_transform import (
            TensorTransformer,
        )
        n = 40
        feats = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
        b = pa.RecordBatch.from_pydict({"rid": pa.array(np.arange(n))})
        b = append_tensor_column(b, "x", feats)
        df = DataFrame.from_table(pa.Table.from_batches([b]), 8)

        t1 = TensorTransformer(modelFunction=self._mf(2, 3.0),
                               inputMapping={"x": "x"},
                               outputMapping={"y": "x3"}, batchSize=16)
        stage1 = t1.transform(df)
        kept = stage1.filter(lambda bb: pa.array(
            bb.column(bb.schema.get_field_index("rid")).to_numpy() % 4
            == 0))
        t2 = TensorTransformer(modelFunction=self._mf(2, 10.0),
                               inputMapping={"x3": "x"},
                               outputMapping={"y": "x30"}, batchSize=4)
        out = t2.transform(kept).collect()
        assert out.num_rows == 10
        np.testing.assert_allclose(
            arrow_to_tensor(out.column("x30")),
            feats[::4] * 30.0, atol=1e-5)


class TestRechunkFuzz:
    """Randomized layouts through the re-chunker: any partition-size
    mix × any batch hint must preserve row identity and order and
    dispatch ceil(N/hint) chunks."""

    def test_random_layouts(self):
        from sparkdl_tpu.graph.function import ModelFunction
        from sparkdl_tpu.transformers.tensor_transform import (
            TensorTransformer,
        )
        rng = np.random.default_rng(123)
        for trial in range(6):
            sizes = rng.integers(0, 9, size=rng.integers(2, 9)).tolist()
            n = int(sum(sizes))
            if n == 0:
                sizes.append(3)
                n = 3
            hint = int(rng.integers(2, 12))
            feats = rng.normal(size=(n, 2)).astype(np.float32)
            batches, off = [], 0
            for s in sizes:
                b = pa.RecordBatch.from_pydict(
                    {"rid": pa.array(np.arange(off, off + s))})
                b = append_tensor_column(b, "x", feats[off:off + s])
                batches.append(b)
                off += s
            df = DataFrame([Source((lambda bb=bb: bb), bb.num_rows)
                            for bb in batches])

            def apply_fn(params, inputs):
                return {"y": inputs["x"] * 0.5}

            mf = ModelFunction(apply_fn, params={},
                               input_signature={"x": ((2,), np.float32)},
                               output_names=["y"])
            t = TensorTransformer(modelFunction=mf,
                                  inputMapping={"x": "x"},
                                  outputMapping={"y": "y"},
                                  batchSize=hint)
            table = t.transform(df).collect()
            ctx = (trial, sizes, hint)
            assert table.num_rows == n, ctx
            np.testing.assert_array_equal(
                table.column("rid").to_numpy(), np.arange(n), err_msg=str(ctx))
            np.testing.assert_allclose(
                arrow_to_tensor(table.column("y")), feats * 0.5,
                atol=1e-6, err_msg=str(ctx))
            assert t.metrics.batches == -(-n // hint), ctx

    def test_pooled_downstream_stage_preserves_order_under_jitter(self):
        """Host stages after the device stage run pooled; ordered
        emission must hold even when later partitions finish first."""
        import time

        from sparkdl_tpu.graph.function import ModelFunction
        from sparkdl_tpu.transformers.tensor_transform import (
            TensorTransformer,
        )
        n = 24
        b = pa.RecordBatch.from_pydict({"rid": pa.array(np.arange(n))})
        b = append_tensor_column(b, "x",
                                 np.ones((n, 2), np.float32))
        df = DataFrame.from_table(pa.Table.from_batches([b]), 8)

        def apply_fn(params, inputs):
            return {"y": inputs["x"]}

        mf = ModelFunction(apply_fn, params={},
                           input_signature={"x": ((2,), np.float32)},
                           output_names=["y"])
        t = TensorTransformer(modelFunction=mf, inputMapping={"x": "x"},
                              outputMapping={"y": "y"}, batchSize=5)
        rng = np.random.default_rng(0)

        def jitter(batch):
            time.sleep(float(rng.uniform(0, 0.01)))
            return batch.append_column(
                "tag", pa.array([1] * batch.num_rows))

        out = t.transform(df).map_batches(jitter, name="jitter")
        rids = []
        for bb in out.stream():
            rids.extend(bb.column(0).to_pylist())
        assert rids == list(range(n))


def _carry_counters():
    from sparkdl_tpu.obs import default_registry
    snap = default_registry().snapshot()
    return np.array([snap.get(k, 0) for k in (
        "ship.boundary_carried", "ship.boundary_cold",
        "ship.carry_dropped")], dtype=int)


class TestLookAheadAcrossPartitions:
    """``_stream_rechunk`` hands a device stage the NEXT block with the
    current one, and the runner launches its first chunks under this
    block's last steps (runtime/runner.py::BoundaryCarry). Counts and
    orders of events, never speeds."""

    @staticmethod
    def _frame(sizes, load=None, width=3):
        rng = np.random.default_rng(7)
        batches, lo = [], 0
        for s in sizes:
            b = pa.RecordBatch.from_pydict(
                {"rid": pa.array(np.arange(lo, lo + s))})
            batches.append(append_tensor_column(
                b, "x", rng.normal(size=(s, width)).astype(np.float32)))
            lo += s
        load = load or (lambda i, b: b)
        sources = [Source((lambda i=i, b=b: load(i, b)), b.num_rows)
                   for i, b in enumerate(batches)]
        feats = np.concatenate([arrow_to_tensor(b.column("x"))
                                for b in batches])
        return sources, feats

    @staticmethod
    def _transformer(batch_size, use_mesh=False, width=3):
        from sparkdl_tpu.graph.function import ModelFunction
        from sparkdl_tpu.transformers.tensor_transform import (
            TensorTransformer,
        )
        mf = ModelFunction(lambda p, i: {"y": i["x"] * 3.0 + 1.0},
                           params={},
                           input_signature={"x": ((width,), np.float32)},
                           output_names=["y"])
        return TensorTransformer(modelFunction=mf,
                                 inputMapping={"x": "x"},
                                 outputMapping={"y": "y"},
                                 batchSize=batch_size, useMesh=use_mesh)

    @staticmethod
    def _check(table, feats):
        np.testing.assert_array_equal(table.column("rid").to_numpy(),
                                      np.arange(len(feats)))
        np.testing.assert_allclose(arrow_to_tensor(table.column("y")),
                                   feats * 3.0 + 1.0, atol=1e-6)

    # partitions that are a multiple of the batch, not a multiple,
    # shorter than the window (one chunk, fewer chunks than
    # max_inflight), smaller than a batch, and empty
    @pytest.mark.parametrize("sizes", [
        [16, 16, 16, 16], [14, 9, 21, 5], [4, 4, 4, 4, 4], [8, 4, 16],
        [3] * 11, [16, 0, 16], [0, 0, 5], [16], [0]])
    def test_rows_equal_whatever_the_partitions(self, sizes,
                                                loaded_ahead):
        sources, feats = self._frame(sizes)
        t = self._transformer(4)
        before = _carry_counters()
        table = t.transform(DataFrame(sources)).collect()
        self._check(table, feats)
        assert t.metrics.rows == sum(sizes)
        assert t.metrics.batches == -(-sum(sizes) // 4)
        carried, cold, dropped = _carry_counters() - before
        assert dropped == 0
        assert carried == t.metrics.boundary_carried
        assert cold == t.metrics.boundary_cold

    def test_every_boundary_of_a_pass_is_carried(self, loaded_ahead):
        from sparkdl_tpu.obs import tracer
        sources, feats = self._frame([16] * 4)
        t = self._transformer(4)
        before = _carry_counters()
        tr = tracer()
        tr.arm()
        tr.clear()
        try:
            table = t.transform(DataFrame(sources)).collect()
            spans = tr.spans()
        finally:
            tr.arm_from_env()
            tr.clear()
        self._check(table, feats)
        # partitions - 1 carried, none cold, nothing thrown away
        assert tuple(_carry_counters() - before) == (3, 0, 0)
        runs = sorted((s for s in spans if s.name == "runner.run"),
                      key=lambda s: s.start)
        assert len(runs) == 4
        for k, run in enumerate(runs[:-1]):
            mine = [s for s in spans if s.parent_id == run.span_id]
            ahead = [s for s in mine if s.name == "dispatch"
                     and s.attrs.get("whose") == "next"]
            last_get = max(s.end for s in mine
                           if s.name == "device_get")
            # the next partition's first dispatch begins before this
            # partition's last readback ends, inside this run's span
            assert len(ahead) == 2
            assert min(s.start for s in ahead) < last_get
            assert max(s.end for s in ahead) <= run.end
            # and that partition dispatches only what is left of it
            nxt = [s for s in spans if s.name == "dispatch"
                   and s.parent_id == runs[k + 1].span_id
                   and s.attrs.get("whose") != "next"]
            assert len(nxt) == 2

    def test_a_slow_load_never_holds_the_partition_before_it(self):
        """Partition 2's load sleeps: partition 1 is yielded before
        that load ends (the look never waits), and that one boundary
        counts cold."""
        import time
        released = threading.Event()
        ended = {}

        def load(i, b):
            if i == 2:
                released.wait(timeout=30)
                ended["t"] = time.perf_counter()
            return b
        sources, feats = self._frame([16] * 4, load=load)
        t = self._transformer(4)
        before = _carry_counters()
        got, stamps = [], []
        stream = t.transform(DataFrame(sources)).stream()
        for out in stream:
            stamps.append(time.perf_counter())
            got.append(out)
            if len(got) == 2:
                assert "t" not in ended     # still loading
                released.set()
        assert stamps[1] < ended["t"]
        self._check(pa.Table.from_batches(got), feats)
        carried, cold, dropped = _carry_counters() - before
        assert cold >= 1 and carried + cold == 3 and dropped == 0

    def test_a_fault_at_the_drain_drops_the_carry_and_the_retry_is_right(
            self, monkeypatch, loaded_ahead):
        from sparkdl_tpu.obs import default_registry
        from sparkdl_tpu.resilience.faults import InjectedFault
        from sparkdl_tpu.runtime import runner as rmod
        drains = []

        def fail_third_drain(site):
            if site == "ship.drain":
                drains.append(site)
                if len(drains) == 3:    # the first with a carry aloft
                    raise InjectedFault("ship.drain")
        monkeypatch.setattr(rmod, "maybe_fail", fail_third_drain)
        sources, feats = self._frame([16] * 3)
        t = self._transformer(4)
        before = _carry_counters()
        retries = default_registry().snapshot().get("engine.retries", 0)
        table = t.transform(DataFrame(sources)).collect()
        self._check(table, feats)
        assert default_registry().snapshot()["engine.retries"] \
            == retries + 1
        # the failed run's carry went; its retry began cold (a run of
        # the runner came before it) and carried again
        assert tuple(_carry_counters() - before) == (2, 1, 1)
        # the failed attempt's rows were never counted
        assert t.metrics.rows == 48

    def test_closing_a_stream_with_a_carry_in_flight(self, monkeypatch,
                                                     loaded_ahead):
        """take(1) / close() with the next partition's chunks in
        flight: nothing stays pending, and the frame transforms again."""
        from sparkdl_tpu.obs import default_registry
        from sparkdl_tpu.transformers import utils as tutils
        runners = []
        real = tutils.make_runner

        def spy(*a, **k):
            runners.append(real(*a, **k))
            return runners[-1]
        monkeypatch.setattr(tutils, "make_runner", spy)
        sources, feats = self._frame([16] * 4)
        t = self._transformer(4)
        before = _carry_counters()
        out = t.transform(DataFrame(sources))
        stream = out.stream()
        first = next(stream)
        assert first.num_rows == 16
        assert runners[0]._carry.in_flight == 2
        stream.close()
        assert runners[0]._carry.in_flight == 0
        assert default_registry().snapshot()["ship.inflight"] == 0
        assert tuple(_carry_counters() - before) == (0, 0, 1)
        assert len(out.take(1)) == 1     # the same plan, abandoned again
        assert runners[0]._carry.in_flight == 0
        self._check(out.collect(), feats)                   # same plan
        self._check(t.transform(DataFrame(sources)).collect(), feats)
        assert all(r._carry.in_flight == 0 for r in runners)
        assert not any(r._staging_lock.locked() for r in runners)

    def test_take_one_loads_no_more_partitions_than_before(
            self, loaded_ahead):
        """The look takes a finished load, it never submits one: with a
        window of two, first() has had two partitions loaded, as on
        the parent."""
        loaded = []

        def load(i, b):
            loaded.append(i)
            return b
        sources, _ = self._frame([16] * 6, load=load)
        eng = LocalEngine(num_workers=2, max_inflight=2)
        try:
            t = self._transformer(4)
            stream = t.transform(DataFrame(sources, engine=eng)).stream()
            assert next(stream).num_rows == 16
            stream.close()
            assert sorted(loaded) == [0, 1]
        finally:
            eng.shutdown()

    def test_a_copied_column_is_still_the_announced_memory(
            self, loaded_ahead):
        """A float64 column into a float32 model is copied by
        to_tensors; the copy made for the look ahead is the one run."""
        from sparkdl_tpu.graph.function import ModelFunction
        from sparkdl_tpu.transformers.tensor_transform import (
            TensorTransformer,
        )
        x = np.random.default_rng(3).normal(size=(48, 2))
        b = append_tensor_column(pa.RecordBatch.from_pydict(
            {"rid": pa.array(np.arange(48))}), "x", x)
        df = DataFrame.from_table(pa.Table.from_batches([b]), 3)
        mf = ModelFunction(lambda p, i: {"y": i["x"] + i["k"]},
                           params={},
                           input_signature={"x": ((2,), np.float32),
                                            "k": ((2,), np.float32)},
                           output_names=["y"])
        t = TensorTransformer(modelFunction=mf, inputMapping={"x": "x"},
                              outputMapping={"y": "y"}, batchSize=4,
                              tfHParams={"k": [1.0, 2.0]})
        before = _carry_counters()
        y = t.transform(df).tensor("y")
        np.testing.assert_allclose(y, x.astype(np.float32) + [1.0, 2.0],
                                   atol=1e-6)
        assert tuple(_carry_counters() - before) == (2, 0, 0)

    def test_one_transformed_frame_collected_by_several_threads(self):
        """One plan, so ONE runner, under several consumers at once:
        right rows in every thread; every device run but the first
        began carried or cold, none lost."""
        import sys
        sources, feats = self._frame([16, 9, 16, 4, 12, 16])
        t = self._transformer(4)
        out = t.transform(DataFrame(sources))
        tables, errors = {}, []
        gate = threading.Barrier(4)

        def work(i):
            try:
                gate.wait(timeout=30)
                tables[i] = out.collect()
            except Exception as e:  # pragma: no cover - reporting
                errors.append(e)
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(4)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
        finally:
            sys.setswitchinterval(interval)
        assert not errors and not any(th.is_alive() for th in threads)
        for i in range(4):
            self._check(tables[i], feats)
        m = t.metrics
        assert m.rows == 4 * len(feats)
        assert m.boundary_cold >= 3     # each other thread's first run
