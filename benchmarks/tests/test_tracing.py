"""The reducer against a trace recorded on a TPU v5e (tests/record_trace.py): two
passes of three steps of a small convolution, a 20 ms pause after each pass."""

import json
import os

import pytest

from benchmarks import tracing

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE = os.path.join(DATA, "trace_small.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    # the window and the spans reach the reducer as harness.Tracer hands them over
    with open(os.path.join(DATA, "trace_small.spans.json")) as f:
        kept = json.load(f)
    return tracing.reduce_trace(TRACE, kept["window"], kept["spans"])


def test_window_and_busy_time(summary):
    assert summary.window_s == pytest.approx(0.110839874)
    assert list(summary.devices) == [0]
    # six steps of about 0.368 ms each, nothing else on the device
    assert summary.busy_s == pytest.approx(6 * 0.3684e-3, rel=0.01)
    assert 0 < summary.busy_s < summary.window_s


def test_step_durations_come_from_the_dominant_program(summary):
    steps = summary.step_durations()
    assert len(steps) == 6
    assert all(s == pytest.approx(0.3684e-3, rel=0.01) for s in steps)
    assert list(summary.devices[0].modules) == ["jit_step"]


def test_top_ops_have_short_names_and_sum_to_busy(summary):
    ops = summary.top_ops(10)
    assert ops[0][0] == "convert_reduce_fusion_f32_64_64"
    assert sum(seconds for _, seconds in ops) == pytest.approx(summary.busy_s, rel=1e-6)


def test_gaps_are_named_by_what_the_host_was_doing(summary):
    gaps = summary.top_gaps(10)
    names = [name for name, _ in gaps]
    # the longest gap holds the pause between the passes and the next pass's start
    assert gaps[0][0] == "at_pass_boundary" and gaps[0][1] == pytest.approx(0.0319, rel=0.02)
    assert names.count("at_partition_boundary") == 4
    assert "trace_start" in names and "trace_end" in names
    busy_and_idle = summary.busy_s + sum(b - a for a, b in summary.devices[0].gaps)
    assert busy_and_idle == pytest.approx(summary.window_s, rel=1e-6)


def test_short_op_name():
    long = ("%fusion.26 = bf16[1024,71,71,192]{3,2,1,0:T(8,128)(2,1)} fusion(bf16[1024,71,71,192] "
            "%x), kind=kOutput, calls=%fused_computation")
    assert tracing.short_op_name(long) == "fusion.26_bf16_1024_71_71_192"
    assert tracing.short_op_name("%copy-start = (f32[3,3]{1,0}, u32[]) copy-start(%w)") \
        == "copy-start_f32_3_3"


def test_a_directory_without_a_trace_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        tracing.find_trace_file(str(tmp_path))
