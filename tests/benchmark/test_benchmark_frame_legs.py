"""The reader ``frame_legs`` on hand-built ring records (as
``test_benchmark_program_spans.py`` builds them): two populations of waits
give a midpoint p50 and a by-pad note, what the program's legs fail to
cover of the harness's latency, the longest stall of the window, and the
rings that read as nothing."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark.layer_metrics import frame_legs as fl  # noqa: E402
from nnstreamer_tpu.obs import collector, spans  # noqa: E402

MAN = manifest.load_manifest(ROOT)
FIVE = ["frame_wait_ms_p50", "frame_wait_ms_p95", "frame_return_ms_p50",
        "frame_unaccounted_pct", "host_stall_ms_max"]
STEP = 1000.0         # the device's step, ms
MS = 1_000_000


class Ring:
    """Two closed-loop clients into one mux, in ``obs/flight.py``'s
    layout, times given in ms.  Client 0 carries every round: its push
    completes the batch, the other's frame has waited a round by then."""

    def __init__(self):
        self.records, self.ids = [], 0
        self.push_ns = [[], []]
        self.sink_ns = [[], []]

    def sid(self):
        self.ids += 1
        return self.ids

    def rec(self, ph, name, cat, tid, start, dur, trace, sid, parent, args):
        self.records.append((ph, round(start * MS), round(dur * MS), tid, name,
                             cat, trace, sid, parent, args))

    def push(self, s, at):
        """Client ``s`` pushes at ``at``: the harness's stamp, then the
        program's instant 0.01 ms later; returns the frame's context."""
        trace, span = 1000 + self.sid(), self.sid()
        self.push_ns[s].append(round(at * MS))
        self.rec("i", f"client{s}.push", "source", f"src:client{s}",
                 at + 0.01, 0, trace, span, 0, None)
        return trace, span, at + 0.02  # on its pad 0.02 ms after the stamp

    def round(self, k, frames, booked, wait=True):
        """Round ``k`` takes ``frames`` (one context a client) at
        ``booked``: waits, coalesce, 1 ms of forward work, the step, 2 ms
        back to client 0's sink and 1 more to client 1's; each sink's
        callback stamps 0.05 ms before its span ends."""
        lead = frames[0][0]
        merged = self.sid()
        if wait:
            for s, (trace, span, arrived) in enumerate(frames):
                self.rec("X", "mux.pad_wait", "wait", "src:client0", arrived,
                         booked - arrived, trace, self.sid(), span,
                         {"pad": f"sink_{s}", "ticket": k - 1})
        self.rec("i", "mux", "coalesce", "src:client0", booked, 0, lead,
                 merged, frames[0][1],
                 {"ticket": k - 1,
                  "parents": [f"{t:x}/{s:x}" for t, s, _ in frames]})
        t0 = booked + 1.0
        self.rec("X", "f.invoke", "stage", "src:client0", t0, 0.5, lead,
                 self.sid(), 0, {"round": k})
        self.rec("X", "device_exec", "device", "device:tpu", t0, STEP, lead,
                 self.sid(), merged, {"element": "f", "round": k})
        done = t0 + STEP
        for s in range(len(frames)):
            end = done + 2.0 + s
            self.rec("X", f"out{s}", "dispatch", "src:client0", end - 0.5,
                     0.5, lead, self.sid(), 0, {"element": "TensorSink"})
            self.sink_ns[s].append(round((end - 0.05) * MS))
        return done + 2.0, done + 3.0  # when each client has its answer


def closed_loop(rounds, wait=True):
    """Two frames in flight a client.  Client 1's frame ``k`` waits on its
    pad while round ``k`` runs; client 0 pushes when its answer is back
    and completes the next round at once."""
    ring = Ring()
    mine, theirs = ring.push(0, 0.0), ring.push(1, 0.1)
    queued = ring.push(1, 0.2)
    for k in range(1, rounds + 1):
        booked = mine[2] + 0.03
        back0, back1 = ring.round(k, [mine, theirs], booked, wait)
        mine, theirs = ring.push(0, back0 + 0.1), queued
        queued = ring.push(1, back1 + 0.1)
    res = SimpleNamespace(push_ns=ring.push_ns, sink_ns=ring.sink_ns,
                          t0_ns=round(1500.0 * MS),
                          t1_ns=round(6500.0 * MS))
    return ring, res


def summary(records, res):
    return fl.window_legs(records, res, collector.frame_legs)


def ctx_for(records, res, monkeypatch, dropped=0):
    monkeypatch.setattr(spans, "snapshot", lambda: list(records))
    monkeypatch.setattr(spans, "recorder_stats", lambda: {"dropped": dropped})
    return SimpleNamespace(result=res, notes={})


def test_two_populations_give_a_midpoint_and_the_pads_say_which(monkeypatch):
    ring, res = closed_loop(8)
    ctx = ctx_for(ring.records, res, monkeypatch)
    # rounds of 1003.15 ms; the window holds five of each client's pushes;
    # client 1 pushes 3.12 ms into a round and is taken at the next's end
    assert fl.frame_wait_ms_p50(ctx) == pytest.approx(
        (0.03 + 1002.18) / 2, abs=0.01)
    assert fl.frame_wait_ms_p95(ctx) == pytest.approx(1002.18, abs=0.01)
    assert ctx.notes["frames_joined"] == 10
    pads = ctx.notes["frame_wait_ms_by_pad"]
    assert pads["mux.sink_0"] == pytest.approx(0.03, abs=0.01)
    assert pads["mux.sink_1"] == pytest.approx(1002.18, abs=0.01)
    assert ctx.notes["frame_device_ms_p50"] == pytest.approx(STEP)
    assert ctx.notes["frame_forward_ms_p50"] == pytest.approx(1.01, abs=0.02)
    # one round and 2 ms back for the carrier, two rounds and 3 for the other
    assert ctx.notes["frame_harness_ms_p50"] == pytest.approx(
        (1003.0 + 2006.2) / 2, abs=0.2)
    # 2 ms back for client 0's sink, 3 for client 1's
    assert fl.frame_return_ms_p50(ctx) == pytest.approx(2.5)
    assert fl.host_stall_ms_max(ctx) == 0.0
    assert ctx.notes["host_records"] == []


def test_the_legs_cover_the_harness_latency_but_for_its_own_stamps(monkeypatch):
    ring, res = closed_loop(8)
    ctx = ctx_for(ring.records, res, monkeypatch)
    frames = summary(ring.records, res)["frames"]
    for f in frames:
        legs = f["forward_ns"] + f["wait_ns"] + f["device_ns"] + f["return_ns"]
        # the instant is 0.01 ms after the push stamp, the span ends
        # 0.05 ms after the answer's: the harness reads 0.04 ms less
        assert legs - f["harness_ns"] == pytest.approx(0.04 * MS, abs=2)
    pct = fl.frame_unaccounted_pct(ctx)
    assert pct is not None and -0.01 < pct < 0


def test_an_unlinked_hop_shows_as_frames_that_did_not_join(monkeypatch):
    ring, res = closed_loop(8)
    cut = [r for r in ring.records
           if not (r[5] == "coalesce" and r[9]["ticket"] == 3)]
    ctx = ctx_for(cut, res, monkeypatch)
    assert fl.frame_wait_ms_p50(ctx) is not None
    assert ctx.notes["frames_joined"] == 8  # round 4's two are left out


def test_a_frame_whose_answer_never_came_is_in_the_waits_not_the_share(
        monkeypatch):
    ring, res = closed_loop(8)
    res.sink_ns[1] = res.sink_ns[1][:5]  # its frames 3..7 are the window's
    frames = summary(ring.records, res)["frames"]
    assert sum(1 for f in frames if f["harness_ns"] is None) == 3
    ctx = ctx_for(ring.records, res, monkeypatch)
    assert fl.frame_unaccounted_pct(ctx) is not None
    assert ctx.notes["frames_joined"] == 10


@pytest.mark.parametrize("what", ["dropped", "parent", "empty_window",
                                  "no_frame_legs"])
def test_rings_that_read_as_nothing(monkeypatch, what):
    ring, res = closed_loop(8, wait=(what != "parent"))
    dropped = 0
    if what == "dropped":
        dropped = 3
    elif what == "empty_window":
        res.t0_ns, res.t1_ns = round(90_000 * MS), round(95_000 * MS)
    elif what == "no_frame_legs":
        monkeypatch.delattr(collector, "frame_legs")
    for name in FIVE:
        ctx = ctx_for(ring.records, res, monkeypatch, dropped)
        assert getattr(fl, name)(ctx) is None, name
        assert "frames_joined" not in ctx.notes


def test_unaccounted_is_none_not_zero_when_no_frame_joins(monkeypatch):
    ring, res = closed_loop(8)
    res.sink_ns = [[], []]  # no answer reached the harness
    ctx = ctx_for(ring.records, res, monkeypatch)
    assert fl.frame_wait_ms_p50(ctx) is not None
    assert fl.frame_unaccounted_pct(ctx) is None


def test_the_longest_stall_of_the_window_and_whether_it_hit_a_gap(monkeypatch):
    ring, res = closed_loop(8)
    args = {"cpu_ms": 0.4, "nivcsw": 0, "majflt": 0, "run_delay_ms": 0.1,
            "throttled_ms": 96.0, "nr_throttled": 1, "cause": "throttled"}
    gap = fl.gaps(ring.records)[2]  # device done -> the next enqueue
    assert gap[1] - gap[0] == pytest.approx(3.65 * MS, abs=1000)
    ring.rec("X", "host_stall", "host", "host:beat", gap[0] / MS - 50, 97.0,
             0, ring.sid(), 0, args)                 # ends inside the gap
    ring.rec("X", "host_stall", "host", "host:beat", 4100.0, 30.0, 0,
             ring.sid(), 0, dict(args, cause="unknown"))  # the device runs
    ring.rec("X", "gc_pause", "host", "src:client0", 4200.0, 3.0, 0,
             ring.sid(), 0, {"generation": 2, "collected": 5})
    ring.rec("X", "host_stall", "host", "host:beat", 100.0, 400.0, 0,
             ring.sid(), 0, args)                    # before the window
    ring.records.sort(key=lambda r: r[1])
    ctx = ctx_for(ring.records, res, monkeypatch)
    assert fl.host_stall_ms_max(ctx) == pytest.approx(97.0)
    notes = ctx.notes["host_records"]
    assert [(n["name"], n["in_gap"]) for n in notes] == [
        ("host_stall", False), ("host_stall", True), ("host_stall", False),
        ("gc_pause", False)]
    assert notes[1]["throttled_ms"] == 96.0 and notes[1]["cause"] == "throttled"
    assert notes[3]["generation"] == 2
    assert notes[1]["at_ms"] == pytest.approx(gap[0] / MS - 50 - 1500.0)


def test_the_window_is_counted_by_the_harness_stamp(monkeypatch):
    """A frame stamped just inside ``t1`` whose instant falls after it is
    the window's, as ``arithmetic.window_metrics`` counts it."""
    ring, res = closed_loop(8)
    edge = ring.push_ns[0][5]
    res.t1_ns = edge + 5_000  # between the stamp and the instant
    frames = summary(ring.records, res)["frames"]
    assert edge + 10_000 in [f["push_ns"] for f in frames]


@pytest.mark.parametrize("name", FIVE)
def test_the_five_entries_list_no_cells_and_name_this_reader(name):
    entry = manifest.find(MAN["per_layer"], name, "metric")
    assert "workloads" not in entry and entry["source"] == "program_span"
    assert entry["better"] == "lower"
    spec = manifest.load_layer_metric(name)
    assert spec["reader"] == "frame_legs" and spec["function"] == name
    assert callable(getattr(fl, name))
