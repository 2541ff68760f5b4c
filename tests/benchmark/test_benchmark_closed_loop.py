"""``traffic_kinds/closed_loop``: the loop, the window and the sample that
moved out of ``mux_saturated`` read recorded stamps as the old module did,
and the per-frame comparison counts each fault a frame can have."""

import gzip
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.traffic_kinds import closed_loop, mux_saturated  # noqa: E402

with gzip.open(os.path.join(os.path.dirname(__file__), "fixtures",
                            "closed_loop.recorded.json.gz"), "rt") as f:
    RECORDED = json.load(f)  # its ``source`` says which run of which commit


def recorded_result() -> closed_loop.Result:
    """The recorded run as the loop leaves it: stamps, as many labels and
    logits rows as came back, a pool of frames that say where they sit."""
    rec = RECORDED
    streams = len(rec["push_ns"])
    res = closed_loop.Result(streams, 2, mux_saturated.PTS_STEP)
    res.t0_ns, res.t1_ns = rec["t0_ns"], rec["t1_ns"]
    res.push_ns, res.sink_ns = rec["push_ns"], rec["sink_ns"]
    res.labels = [[(0, 0.0, k * res.pts_step) for k in range(n)]
                  for n in rec["labels"]]
    res.rows = [np.full((streams, 3), k, np.float32) for k in range(rec["rows"])]
    res.frames = np.arange(streams * rec["pool"]).reshape(streams, rec["pool"], 1)
    return res


def test_the_window_of_recorded_stamps_is_the_old_modules():
    res = recorded_result()
    closed_loop.close_window(res)
    assert res.window == RECORDED["window"]
    assert res.window["attempted"] == res.window["arrived"] > 1000


@pytest.mark.parametrize("case", sorted(RECORDED["samples"]))
def test_the_sample_for_a_seed_is_the_old_modules(case):
    seed, want = (int(x) for x in case.split("/"))
    res = recorded_result()
    frames, program, picks = closed_loop.sample(res, {"check_frames": want}, seed)
    assert [list(p) for p in picks] == RECORDED["samples"][case]
    assert len(picks) == want
    # the frames and the rows handed on are the picks' own
    pool = RECORDED["pool"]
    assert frames.reshape(-1).tolist() == [s * pool + k % pool for s, k in picks]
    assert program[:, 0].tolist() == [float(k) for _, k in picks]


def test_the_camera_kind_hands_on_the_loops_comparison():
    assert mux_saturated.sample is closed_loop.sample
    assert mux_saturated.per_frame_faults is closed_loop.per_frame_faults


def faulty(fault):
    """Two streams, four rounds, every frame inside the window and right;
    then one fault planted."""
    res = closed_loop.Result(2, 2, 10)
    res.t0_ns, res.t1_ns = 0, 1000
    res.push_ns = [[100 * k + s for k in range(4)] for s in range(2)]
    res.logits = [np.array([[0.1, 0.9 + k, 0.2], [0.7 + k, 0.1, 0.3]], np.float32)
                  for k in range(4)]
    res.labels = [[(1 - s, float(res.logits[k][s].max()), 10 * k)
                   for k in range(4)] for s in range(2)]
    if fault == "missing":
        res.labels[1].pop()
    elif fault == "missing_row":
        res.logits.pop()
    elif fault == "order":
        res.labels[0][1], res.labels[0][2] = res.labels[0][2], res.labels[0][1]
    elif fault == "label":      # stream 1's label is stream 0's
        res.labels[1][2] = (1, res.labels[1][2][1], 20)
    elif fault == "score":
        res.labels[0][3] = (1, res.labels[0][3][1] + 1e-3, 30)
    elif fault == "outside":    # a wrong label on a frame pushed after t1
        res.push_ns[0][3] = 1000
        res.labels[0][3] = (2, 0.0, 30)
    return res


@pytest.mark.parametrize("fault,notes", [
    (None, {}), ("missing", {"missing": 1}), ("missing_row", {"missing": 2}),
    ("order", {"order": 2}), ("label", {"label": 1}), ("score", {"score": 1}),
    ("outside", {}),
])
def test_per_frame_faults_counts_each_fault_a_frame_can_have(fault, notes):
    res = faulty(fault)
    closed_loop.per_frame_faults(res)
    want = {"missing": 0, "order": 0, "label": 0, "score": 0, **notes}
    assert res.fail_notes == want and res.failed == sum(want.values())
    assert len(res.rows) == len(res.logits)


def test_the_stop_line_stops_every_client_at_the_same_count():
    res = closed_loop.Result(3, 2, 10)
    res.line.pushed[:] = [5, 7, 6]
    assert res.line.stop_at is None
    assert res.line.close() == 7 == res.line.stop_at
    # every waiting client is woken to see the line: one permit more each
    assert all(g.acquire(blocking=False) for g in res.gates for _ in range(3))
    assert not any(g.acquire(blocking=False) for g in res.gates)
