"""The trace reduction: union of overlapping device ops, idle gaps, self
times and the kernel's summed time, on a hand-built trace and on a small
trace recorded on a TPU v5e (cnn_fmnist.grid, one `GridRunner.run`)."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr
from bench.metrics import _shared

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_small.json"

# Two chips' worth of ops on one line, nested and overlapping, in ns.
HAND = [
    ["%while.1 = (s32[]) while(...)", 100.0, 400.0],          # 100-500
    ["%fusion.2 = f32[8] fusion(...)", 120.0, 80.0],          # 120-200
    ["%_ra_call.4 = f32[2,10,512,1024] custom-call(...)", 250.0, 100.0],
    ["%copy.3 = f32[8] copy(...)", 450.0, 100.0],             # 450-550
    ["%fusion.9 = f32[8] fusion(...)", 700.0, 50.0],          # 700-750
]


def test_merge_unions_overlaps():
    spans = tr.merge((s, s + d) for _, s, d in HAND)
    assert spans == [(100.0, 550.0), (700.0, 750.0)]


def test_busy_and_idle_inside_a_window():
    assert tr.busy_ns(HAND, 0.0, 1000.0) == 450.0 + 50.0
    assert tr.busy_ns(HAND, 300.0, 720.0) == 250.0 + 20.0
    assert tr.idle_gaps(HAND, 0.0, 1000.0) == [
        (0.0, 100.0), (550.0, 700.0), (750.0, 1000.0)]


def test_self_times_add_up_to_busy_time():
    own = tr.self_times(HAND, 0.0, 1000.0)
    assert own["while.1"] == 400.0 - 80.0 - 100.0 - 50.0
    assert own["fusion.2"] == 80.0 and own["_ra_call.4"] == 100.0
    # copy.3 overruns its parent by 50 ns: the parent loses only the
    # overlap, so self times still add up to the busy time.
    assert own["copy.3"] == 100.0
    assert sum(own.values()) == tr.busy_ns(HAND, 0.0, 1000.0)


def test_kernel_events_and_time():
    hits = tr.matching(HAND, _shared.KERNEL, 0.0, 1000.0)
    assert [h[0].split(" ")[0] for h in hits] == ["%_ra_call.4"]
    assert tr.op_name(hits[0][0]) == "_ra_call.4"


def test_gap_labels_name_the_innermost_span():
    host = [["bench.window", 0.0, 1000.0], ["bench.run", 90.0, 470.0],
            ["bench.grid_build", 560.0, 120.0]]
    assert tr.label_gap(host, (550.0, 700.0)) == "bench.grid_build"
    assert tr.label_gap(host, (750.0, 1000.0)).startswith("host:")


@pytest.fixture(scope="module")
def recorded():
    return json.loads(FIXTURE.read_text())


def _naive_busy(events, t0, t1, samples=2000):
    """Busy time by sampling the window at ``samples`` evenly spaced
    instants (independent of the interval arithmetic under test)."""
    step = (t1 - t0) / samples
    t = t0 + (np.arange(samples) + 0.5) * step
    s = np.array([ev[1] for ev in events])
    e = s + np.array([ev[2] for ev in events])
    hit = ((s[None, :] <= t[:, None]) & (t[:, None] < e[None, :])).any(axis=1)
    return float(hit.sum()) * step


def test_recorded_trace_reduces_consistently(recorded):
    t0, t1 = tr.window(recorded)
    (plane,) = recorded["devices"]
    events = recorded["devices"][plane]
    busy = tr.busy_ns(events, t0, t1)
    idle = sum(e - s for s, e in tr.idle_gaps(events, t0, t1))
    assert busy > 0 and abs(busy + idle - (t1 - t0)) < 1e-6 * (t1 - t0)
    assert busy == pytest.approx(_naive_busy(events, t0, t1), rel=0.02)
    own = tr.self_times(events, t0, t1)
    assert sum(own.values()) == pytest.approx(busy, rel=1e-6)
    kernel = tr.matching(events, _shared.KERNEL, t0, t1)
    assert kernel and all(tr.op_name(e[0]).startswith("_ra_call")
                          for e in kernel)
    # Each ra / ra-substitution / aayg dispatch group calls the kernel once
    # per round (5 rounds); ideal C-FL never does.
    runs = tr.spans(recorded["host"], "bench.run")
    assert len(kernel) == 3 * 5 * len(runs)
