"""The reduction from a trace to numbers: interval arithmetic on a hand-made
case, then a trace recorded on the chip (kept as the reduction's own JSON)."""

import json
import os

import pytest

from benchmarks.harness import trace_reduce as tr

FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "fixtures", "trace_lr_tb.train_packed.json",
)
MS = 1e6  # ns


def test_union_clip_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.length([(0, 3), (5, 8)]) == 6
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert tr.subtract([(0, 2)], []) == [(0, 2)]


def hand_made() -> tr.Trace:
    """Two devices over a window of [0, 100) ms.

    device 0: fusion.1 [0,30) and fusion.2 [20,40) overlap -> busy [0,40);
              all-reduce.3 [40,60) with fusion.4 [50,70) hiding its second
              half; idle [70,100) but for copy.5 [90,95).
    device 1: one op [0,90): idle only [90,100).
    host: ``epoch`` spans the window, ``steps`` covers [0, 80).
    """
    d0 = [
        ("fusion.1", 0, 30 * MS), ("fusion.2", 20 * MS, 20 * MS),
        ("all-reduce.3", 40 * MS, 20 * MS), ("fusion.4", 50 * MS, 20 * MS),
        ("copy.5", 90 * MS, 5 * MS),
    ]
    d1 = [("fusion.1", 0, 90 * MS)]
    spans = [("epoch", 0, 100 * MS), ("steps", 0, 80 * MS)]
    return tr.Trace({0: d0, 1: d1}, spans)


def test_hand_made_case():
    got = tr.reduce(
        hand_made(), (0, 100 * MS), steps=4,
        labels={"epoch": "epoch_boundary", "steps": "in_epoch"},
    )
    assert got["window_s"] == pytest.approx(0.1)
    assert got["busy_s_by_device"] == {0: pytest.approx(0.075), 1: pytest.approx(0.09)}
    assert got["busy_s"] == pytest.approx(0.0825)  # the mean over devices
    assert got["device_idle_frac"] == pytest.approx(0.25)  # the worst device
    assert got["busy_s_per_step"] == pytest.approx(0.0825 / 4)
    # device 0's collective: 20 ms, the first 10 of them with nothing else on
    assert got["collective_s"] == pytest.approx(0.02)
    assert got["collective_exposed_s"] == pytest.approx(0.01)
    # fusion.1 ran 30 ms on device 0 and 90 on device 1: 60 ms a device
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(0.06)]
    assert {n for n, _ in got["device_ops"][1:4]} == {"all-reduce.3", "fusion.2", "fusion.4"}
    assert got["device_ops"][4] == ["copy.5", pytest.approx(0.0025)]
    # device 0 idles [70,90) — [70,80) is inside ``steps`` by its midpoint
    # rule, the gap's midpoint 80 is not — and [95,100)
    gaps = dict((n, s) for n, s in got["idle_gaps"] if n.startswith("total:"))
    assert gaps == {"total:epoch_boundary": pytest.approx(0.025)}
    assert got["idle_gaps"][1] == ["gap:epoch_boundary", pytest.approx(0.02)]
    assert got["idle_s_by_label"] == {"epoch_boundary": pytest.approx(0.025)}


def test_a_window_clips_operations_that_cross_it():
    got = tr.reduce(hand_made(), (10 * MS, 50 * MS))
    assert got["busy_s_by_device"][0] == pytest.approx(0.04)
    assert got["device_idle_frac"] == pytest.approx(0.0)
    assert got["collective_s"] == pytest.approx(0.01)


def test_json_round_trip_and_cut():
    trace = hand_made()
    again = tr.Trace.from_json(json.loads(json.dumps(trace.to_json())))
    assert again.devices == {
        d: [(n, float(s), float(e)) for n, s, e in ops] for d, ops in trace.devices.items()
    }
    assert tr.reduce(again, (0, 100 * MS)) == tr.reduce(trace, (0, 100 * MS))
    head = trace.cut(0, 45 * MS)
    assert [n for n, _, _ in head.devices[0]] == ["fusion.1", "fusion.2", "all-reduce.3"]
    assert tr.span_window(head, "epoch") == (0, 100 * MS)


def test_spans_must_be_unique():
    trace = hand_made()
    trace.spans.append(("epoch", 0, 1))
    with pytest.raises(ValueError):
        tr.span_window(trace, "epoch")


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_trace_recorded_on_the_chip():
    """The start of ``lr_tb.train_packed``'s traced epoch on a TPU v5e (PR
    22): 4.7 s in which the device waits for the loaders, then four steps of
    407 ms.  Device intervals under XLA's names, the harness's spans."""
    with open(FIXTURE) as f:
        doc = json.load(f)
    trace = tr.Trace.from_json(doc)
    assert trace.source == "device_planes" and list(trace.devices) == [0]
    # the host runs ahead of the device: it had called the program at least
    # as often as the device had run it
    assert len([s for s in trace.spans if s[0] == "dispatch"]) >= doc["steps"]
    window = tuple(doc["window"])
    got = tr.reduce(
        trace, window, steps=doc["steps"],
        labels={"epoch": "epoch_boundary", "steps": "in_epoch"},
        default_label="epoch_boundary",
    )
    for key, want in doc["expected"].items():
        assert got[key] == pytest.approx(want, rel=1e-9), key
    assert got["busy_s_per_step"] == pytest.approx(0.4072, rel=1e-3)
    assert got["device_idle_frac"] == pytest.approx(0.7427, abs=1e-3)
    assert got["idle_gaps"][0] == ["total:epoch_boundary", pytest.approx(4.7, rel=1e-3)]
    assert got["idle_gaps"][1][0] == "total:in_epoch" and got["idle_gaps"][1][1] < 1e-3
    # the per-layer metric that tells the epoch's start from its steps
    from benchmarks.layer_metrics import epoch_boundary_idle_s

    assert epoch_boundary_idle_s.read({"trace": got}) == pytest.approx(4.7, rel=1e-3)
    assert got["busy_s"] <= got["window_s"]
    assert got["collective_s"] == 0.0  # one chip
    assert len(got["device_ops"]) == 10 and got["device_ops"][0][1] > 0
    # the operations of one program, the same ones step after step
    names = {n for n, _, _ in trace.devices[0]}
    assert len(trace.devices[0]) >= doc["steps"] * len(names) * 0.5
