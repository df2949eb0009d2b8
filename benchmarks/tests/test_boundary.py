"""``boundary.py`` on made-up spans and on a pair recorded on a TPU v5e
(tests/record_boundary.py: the program's real ``TensorTransformer`` over TestNet, two
passes of three partitions of four device batches, host tracer off, the program's
tracer armed), and ``traced.py`` at both cells' rehearsal sizes."""

import json
import os

import pytest

from benchmarks import boundary, traced, tracing
from benchmarks.readers import boundary_part, span_count, span_median_ms, trace_named_share

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE = os.path.join(DATA, "trace_boundary.xplane.pb")
CELLS = ["inceptionv3_featurize_stream", "inceptionv3_featurize_stream_x4"]


def _span(name, start, end, id, parent=0):
    return {"name": name, "start": start, "end": end, "id": id, "parent": parent}


def _two_runs(dispatch=True, second_run="runner.run"):
    """Two runs of one step each; the device idle from 1.000 to 1.200."""
    spans = [_span("runner.run", 0.500, 1.010, 1),
             _span("dispatch", 0.501, 0.503, 2, parent=1),
             _span(second_run, 1.012, 1.700, 3)]
    if dispatch:
        spans.append(_span("dispatch", 1.014, 1.017, 4, parent=3))
    return sorted(spans, key=lambda s: s["start"])


WINDOW = (0.25, 2.25)  # on the trace's clock; gaps count from its start
GAP = (0.75, 0.95)  # 1.000 to 1.200 on the trace's clock


def test_a_gap_is_cut_at_four_readings_and_the_parts_sum_to_it():
    (g,) = boundary.boundary_gaps([GAP], _two_runs(), WINDOW)
    assert g["drain_tail"] == pytest.approx(0.010)
    assert g["handoff"] == pytest.approx(0.002)
    assert g["refill_host"] == pytest.approx(0.005)
    assert g["first_step_lag"] == pytest.approx(0.183)
    assert sum(g[p] for p in boundary.PARTS) == pytest.approx(g["gap"]) == pytest.approx(0.2)
    parts = boundary.boundary_parts([g])
    assert parts["gaps"] == 1 and parts["gap"] == pytest.approx(0.2)
    assert boundary.boundary_parts([]) is None


def test_the_sharded_runs_span_marks_a_boundary_too():
    (g,) = boundary.boundary_gaps([GAP], _two_runs(second_run="runner.run_sharded"), WINDOW)
    assert g["run"] == 3


def test_a_gap_in_which_no_run_begins_is_not_a_boundary():
    spans = _two_runs()
    inside = (0.30, 0.31)  # 0.55 s on the trace's clock: within the first run
    assert boundary.boundary_gaps([inside], spans, WINDOW) == []
    assert len(boundary.boundary_gaps([inside, GAP], spans, WINDOW)) == 1


def test_a_gap_cut_by_the_windows_edge_is_left_out():
    spans = _two_runs()
    assert boundary.boundary_gaps([(0.0, 0.95)], spans, WINDOW) == []
    assert boundary.boundary_gaps([(0.75, 2.0)], spans, WINDOW) == []


def test_a_run_without_a_dispatch_span_raises():
    with pytest.raises(ValueError, match="no dispatch span"):
        boundary.boundary_gaps([GAP], _two_runs(dispatch=False), WINDOW)


def test_the_clocks_may_disagree_by_a_fifth_of_a_millisecond_and_no_more():
    spans = _two_runs()
    spans[0]["end"] = 0.99990  # the run ends 0.1 ms before the device's last operation
    (g,) = boundary.boundary_gaps([GAP], spans, WINDOW)
    assert g["drain_tail"] == 0.0
    spans[0]["end"] = 0.990
    with pytest.raises(ValueError, match="drain_tail is -10.000 ms"):
        boundary.boundary_gaps([GAP], spans, WINDOW)


def test_a_device_that_starts_before_the_enqueue_returns_has_no_lag():
    spans = _two_runs()
    spans[-1]["end"] = 1.300  # the enqueue returns 100 ms after the device began
    (g,) = boundary.boundary_gaps([GAP], spans, WINDOW)
    assert g["first_step_lag"] == 0.0
    assert g["refill_host"] == pytest.approx(0.188)
    assert sum(g[p] for p in boundary.PARTS) == pytest.approx(g["gap"])


def test_blocks_hold_the_named_device_time():
    seconds = {"fusion.1": 0.30, "fusion.2": 0.20, "fusion.3": 0.25, "copy.4": 0.05, "convert.5": 0.20}
    scopes = {"fusion.1": "Net/BlockA_0/ConvBN_0/Conv_0", "fusion.2": "Net/BlockA_0/ConvBN_1",
              "fusion.3": "Net/BlockB_0/ConvBN_0", "convert.5": "Net"}
    blocks = boundary.device_blocks(seconds, scopes)
    assert blocks == [["Net/BlockA_0", pytest.approx(0.5)], ["Net/BlockB_0", 0.25], ["Net", 0.20]]
    share = boundary.named_share(seconds, scopes)
    assert share == pytest.approx(95.0)
    assert sum(s for _, s in blocks) == pytest.approx(sum(seconds.values()) * share / 100)
    assert boundary.device_blocks(seconds, scopes, levels=1) == [["Net", pytest.approx(0.95)]]
    assert boundary.named_share({}, scopes) is None


def test_a_stalled_pass_names_its_longest_spans_with_their_parents():
    passes = [(0.0, 1.0), (1.0, 2.0), (2.0, 3.9), (3.9, 4.9)]
    spans = [_span("stage:apply(m)", 2.01, 3.89, 1), _span("runner.run", 2.02, 3.88, 2, parent=1),
             _span("device_get", 2.50, 3.80, 3, parent=2), _span("dispatch", 2.03, 2.04, 4, parent=2)]
    lines = boundary.slowest_pass_spans(spans, passes)
    assert lines[0].startswith("slow pass 2: 1.9000 s against a median of 1.0000 s")
    assert "device_get 1.3000 s" in lines[3] and "under runner.run < stage:apply(m)" in lines[3]
    assert boundary.slowest_pass_spans(spans, passes[:2] + [(2.0, 3.2), (3.2, 4.2)]) == []


def test_readers_find_nothing_in_a_view_without_the_programs_spans():
    """What ``run.py`` hands a reader today, and what the parent's program gives."""
    view = {"observed": {}, "trace": None}
    assert boundary_part.read(view, {"part": "gap"}) is None
    assert span_median_ms.read(view, {"span": "dispatch", "after_trace": True}) is None
    assert span_count.read(view, {"span": "compile"}) is None
    assert trace_named_share.read(view, {}) is None


def test_readers_over_spans_alone():
    program = {"spans": _two_runs() + [_span("compile", -3.0, -1.0, 9), _span("compile", 1.5, 1.6, 10)],
               "window": (0.25, 0.9), "boundaries": None, "device": None}
    view = {"program": program}
    # only the second run's dispatch begins after the profiler stopped
    assert span_median_ms.read(view, {"span": "dispatch", "after_trace": True}) == pytest.approx(3.0)
    assert span_median_ms.read(view, {"span": "dispatch"}) == pytest.approx(2.5)
    assert span_count.read(view, {"span": "compile"}) == 1  # set-up's compile is before the zero
    assert boundary_part.read(view, {"part": "gap"}) is None
    assert trace_named_share.read(view, {}) is None


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_boundary.program_spans.json")) as f:
        kept = json.load(f)
    summary = tracing.reduce_trace(TRACE, kept["window"], [])
    return kept, summary


def test_recorded_boundaries_are_the_partition_and_pass_boundaries(recorded):
    kept, summary = recorded
    gaps = boundary.boundary_gaps(summary.devices[0].gaps, kept["spans"], kept["window"])
    # two passes of three partitions: five boundaries inside the window, the first
    # partition's gap being cut by the window's start
    assert len(gaps) == 5
    runs = [s for s in kept["spans"] if s["name"] == "runner.run" and s["start"] >= 0]
    assert [g["run"] for g in gaps] == [r["id"] for r in runs[1:]]
    for g in gaps:
        assert sum(g[p] for p in boundary.PARTS) == pytest.approx(g["gap"], abs=0.5e-3)
        assert all(g[p] >= 0.0 for p in boundary.PARTS)
    # every other gap of the device lies inside a partition and is no boundary
    assert len(summary.devices[0].gaps) > len(gaps) + 2
    parts = boundary.boundary_parts(gaps)
    assert parts["gaps"] == 5
    assert sum(parts[p] for p in boundary.PARTS) == pytest.approx(parts["gap"], rel=0.25)


def test_recorded_spans_without_their_dispatch_raise(recorded):
    kept, summary = recorded
    spans = [s for s in kept["spans"] if s["name"] != "dispatch"]
    with pytest.raises(ValueError, match="no dispatch span"):
        boundary.boundary_gaps(summary.devices[0].gaps, spans, kept["window"])


def test_recorded_blocks_sum_to_the_programs_named_device_time(recorded):
    kept, summary = recorded
    program, seconds, total = boundary.instruction_seconds(TRACE, kept["window"])
    assert program == "jit_TestNet_featurize"
    assert total == pytest.approx(sum(seconds.values()))
    assert total <= summary.busy_s * (1 + 1e-6)
    scopes = kept["programs"][program]
    share = boundary.named_share(seconds, scopes)
    blocks = boundary.device_blocks(seconds, scopes)
    assert 0.0 < share <= 100.0
    assert sum(s for _, s in blocks) == pytest.approx(total * share / 100)
    assert {b for b, _ in blocks} >= {"TestNet/ConvBN_0", "TestNet/ConvBN_1"}


def test_recorded_view_feeds_every_reader(recorded):
    kept, summary = recorded
    program = traced.program_view(kept, summary, TRACE)
    view = {"program": program}
    parts = {p: boundary_part.read(view, {"part": p}) for p in ("gap",) + boundary.PARTS}
    assert all(v is not None and v >= 0.0 for v in parts.values())
    assert trace_named_share.read(view, {}) == pytest.approx(
        boundary.named_share(program["device"]["seconds_by_instruction"], program["device"]["scopes"]))
    assert span_count.read(view, {"span": "compile"}) == 0
    assert span_median_ms.read(view, {"span": "dispatch"}) > 0.0


@pytest.mark.parametrize("cell", CELLS)
def test_both_cells_rehearse_with_the_metrics_that_need_no_device_plane(capsys, cell):
    assert traced.main(["--workload", cell, "--seed", str(2**31 + 26), "--seconds", "1",
                        "--rehearsal", "1"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    suffix = "batch" if cell == CELLS[0] else "mesh"
    assert result["correct"] is True, result["compared"]
    assert all(k.startswith("cpu_rehearsal.") for k in result["metrics"])
    assert result["metrics"][f"cpu_rehearsal.runner.dispatch_ms.{suffix}"]["value"] > 0.0
    assert result["metrics"][f"cpu_rehearsal.model.compiles_in_window.{suffix}"]["value"] == 0.0
    # the CPU's trace has no device plane: no gap, no device time, and no line for them
    assert not any("boundary_gap" in k or "named_share" in k or "_lag_" in k for k in result["metrics"])
    assert any(line.startswith("program spans: ") for line in out)
    # the recorders are put back, so a run after this one records nothing
    from sparkdl_tpu.obs import compile_log, tracer
    assert not tracer().armed and not compile_log().armed
