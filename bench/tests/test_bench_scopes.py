"""What a later change to the trace loader must keep: the device-trace
readers' values on the recorded trace, and gap labels that name the
program's own ``repro.*`` spans once the loader keeps them."""
import json
import types
from pathlib import Path

import pytest

from bench import harness
from bench import trace as tr

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_small.json"
PEAKS = json.loads((harness.BENCH / "peaks.json").read_text())["chips"]


def _read(name, ctx):
    path = harness.BENCH / "metrics" / f"{name}.py"
    return harness.load_module(path).read(ctx)


@pytest.mark.parametrize("name,value", [
    ("device_idle_share.grid", 0.6820503820288981),
    ("ra_aggregate_time_share.grid", 0.16985983783261369),
    ("ra_aggregate_roofline.grid", 47.26376866385192),
    ("run_gap_ms.grid", None),                  # one run: no gap between
])
def test_device_trace_readers_read_the_recorded_trace_as_before(name, value):
    recorded = json.loads(FIXTURE.read_text())
    # The recorded run: 3 kernel-calling dispatch groups of 2 scenarios,
    # 5 rounds, the CNN's 412 segments of 1024.
    calls = [(2, 10, 412, 1024, mode) for mode in
             ("ra_normalized", "substitution", "ra_normalized")
             for _ in range(5)]
    ctx = types.SimpleNamespace(kind="grid", chips=1, trace=recorded,
                                peaks=PEAKS["TPU v5 lite"],
                                kernel_calls=calls)
    got = _read(name, ctx)
    assert got == (None if value is None
                   else pytest.approx(value, rel=1e-12))


def test_a_gap_inside_a_program_span_is_labelled_by_it():
    """``label_gap`` names the innermost span: a gap during the collect
    of a `GridRunner.run` call is the program's, not the benchmark's."""
    host = [["bench.window", 0.0, 1000.0], ["bench.run", 100.0, 800.0],
            ["repro.grid/run", 110.0, 780.0],
            ["repro.grid/dispatch", 200.0, 300.0],
            ["repro.grid/collect", 600.0, 280.0]]
    assert tr.label_gap(host, (650.0, 700.0)) == "repro.grid/collect"
    assert tr.label_gap(host, (520.0, 560.0)) == "repro.grid/run"
    assert tr.label_gap(host, (895.0, 899.0)) == "bench.run"
