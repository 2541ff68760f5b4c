"""Cluster trace collection (ISSUE 10): clock-skew alignment, partial
merges staying valid Perfetto, trace-id joins with dropped records,
metrics federation, the /trace.json endpoint, and the cross-process
nesting acceptance (nnsq_rtt → nnsq_route → nnsq_serve → device_invoke
on one timeline through a live 2-worker fleet)."""

import json
import socket
import time
import urllib.request

import numpy as np
import pytest

from nnstreamer_tpu.elements.query import (
    recv_tensors_ex,
    send_tensors,
)
from nnstreamer_tpu.fleet import FleetWorker, Membership, Router
from nnstreamer_tpu.obs import spans
from nnstreamer_tpu.obs.collector import (
    TraceCollector,
    TraceSource,
    attribute_trace,
    estimate_clock_offset,
    federate_metrics,
    trace_document,
)
from nnstreamer_tpu.obs.export import MetricsServer, render_text
from nnstreamer_tpu.obs.metrics import MetricsRegistry


@pytest.fixture(autouse=True)
def _fresh_spans():
    spans.reset()
    yield
    spans.reset()


def _rec(ts, dur, name, trace_id, span_id, parent=0, tid="t0",
         cat="span", ph=spans.PH_COMPLETE):
    """One flight-recorder tuple (the obs/flight.py layout)."""
    return (ph, ts, dur, tid, name, cat, trace_id, span_id, parent, None)


def _skewed_source(name, records, skew_ns):
    """A source whose process clock runs ``skew_ns`` ahead of ours:
    its records AND its clock reads are shifted by the skew, exactly
    like a worker whose perf_counter epoch differs."""
    shifted = [tuple([r[0], r[1] + skew_ns] + list(r[2:]))
               for r in records]
    return TraceSource(
        name,
        fetch=lambda: {"process": name, "pid": 1, "records": shifted,
                       "clock_ns": spans.now_ns() + skew_ns},
        clock=lambda: spans.now_ns() + skew_ns)


class TestClockAlignment:
    def test_offset_estimate_recovers_known_skew(self):
        skew = 7_000_000_000  # 7 s: way beyond any span duration
        offset, rtt = estimate_clock_offset(
            lambda: spans.now_ns() + skew, samples=5)
        assert abs(offset - skew) < 5_000_000  # within 5 ms on localhost
        assert rtt >= 0

    def test_skewed_worker_spans_nest_after_alignment(self):
        t0 = spans.now_ns()
        trace = 0x42
        client = [_rec(t0, 10_000_000, "nnsq_rtt", trace, 1)]
        # worker clock runs 5 s ahead; its serve span REALLY happened
        # 2 ms into the client's rtt window
        worker = [_rec(t0 + 2_000_000, 6_000_000, "nnsq_serve", trace, 2)]
        c = TraceCollector()
        c.add_source(_skewed_source("client", client, 0))
        c.add_source(_skewed_source("worker", worker, 5_000_000_000))
        collected = c.collect()
        assert not collected["errors"]
        index = c.spans_by_trace(collected)
        by_name = {r[4]: r for r in index[trace]}
        rtt, serve = by_name["nnsq_rtt"], by_name["nnsq_serve"]
        # containment on ONE timeline: serve nests inside rtt
        assert rtt[1] <= serve[1] <= serve[1] + serve[2] <= rtt[1] + rtt[2]
        # ...which only holds because the 5 s skew was estimated out
        assert abs(collected["sources"]["worker"]["offset_ns"]
                   - 5_000_000_000) < 5_000_000

    def test_merged_chrome_trace_has_one_pid_per_process(self):
        t0 = spans.now_ns()
        c = TraceCollector()
        c.add_source(_skewed_source(
            "a", [_rec(t0, 1000, "nnsq_rtt", 1, 1)], 0))
        c.add_source(_skewed_source(
            "b", [_rec(t0, 500, "nnsq_serve", 1, 2)], 1_000_000_000))
        doc = json.loads(json.dumps(c.chrome_trace()))
        names = {ev["args"]["name"]: ev["pid"]
                 for ev in doc["traceEvents"]
                 if ev.get("ph") == "M" and ev["name"] == "process_name"}
        assert names.keys() == {"a", "b"}
        assert len(set(names.values())) == 2


class TestPartialMerge:
    def test_missing_worker_snapshot_keeps_trace_valid(self):
        t0 = spans.now_ns()
        c = TraceCollector()
        c.add_source(_skewed_source(
            "alive", [_rec(t0, 1000, "nnsq_rtt", 9, 1)], 0))

        def dead_fetch():
            raise ConnectionError("worker killed")

        c.add_source(TraceSource("dead", dead_fetch))
        collected = c.collect()
        assert "dead" in collected["errors"]
        assert "alive" in collected["sources"]
        # still a valid (json-serializable, loadable) Perfetto doc with
        # the alive process's events AND a marker naming the hole
        doc = json.loads(json.dumps(c.chrome_trace(collected)))
        assert any(ev.get("name") == "nnsq_rtt"
                   for ev in doc["traceEvents"])
        assert any(ev.get("name") == "source_missing:dead"
                   for ev in doc["traceEvents"])

    def test_dead_clock_probe_is_an_error_not_a_crash(self):
        def dead_clock():
            raise OSError("partitioned")

        src = TraceSource.__new__(TraceSource)
        src.name, src._fetch, src._clock = "p", lambda: {}, dead_clock
        src.offset_ns = src.rtt_ns = 0
        src.probes = 2
        c = TraceCollector()
        c.add_source(src)
        collected = c.collect()
        assert "p" in collected["errors"]


class TestTraceJoin:
    def test_join_with_dropped_client_records(self):
        """Server spans whose client record was lost (open-loop clients
        drop/timeout) still index cleanly; client trace ids with no
        server span simply don't join."""
        t0 = spans.now_ns()
        server = [
            _rec(t0, 5000, "nnsq_serve", 0xA, 1),
            _rec(t0 + 100, 1000, "device_invoke", 0xA, 2, 1, cat="device"),
            _rec(t0, 4000, "nnsq_serve", 0xB, 3),  # client record dropped
        ]
        c = TraceCollector()
        c.add_source(_skewed_source("w0", server, 0))
        index = c.spans_by_trace()
        assert set(index) == {0xA, 0xB}
        client_tids = {0xA, 0xC}  # 0xC: client record, span ring dropped it
        joined = [t for t in client_tids if t in index]
        server_only = [t for t in index if t not in client_tids]
        assert joined == [0xA] and server_only == [0xB]
        legs = attribute_trace(index[0xA])
        assert legs["serve"] == 5000.0 and legs["device"] == 1000.0
        assert legs["dispatch"] == 4000.0  # serve - device

    def test_attribute_trace_full_decomposition(self):
        recs = [
            _rec(0, 100, "nnsq_rtt", 1, 1),
            _rec(5, 80, "nnsq_route", 1, 2),
            _rec(10, 60, "nnsq_serve", 1, 3),
            _rec(12, 20, "sched_wait", 1, 4, cat="sched"),
            _rec(40, 30, "device_invoke", 1, 5, cat="device"),
        ]
        legs = attribute_trace(recs)
        assert legs["wire"] == 20.0          # rtt - route
        assert legs["route_overhead"] == 20.0  # route - serve
        assert legs["queue"] == 20.0
        assert legs["device"] == 30.0
        assert legs["dispatch"] == 10.0      # serve - queue - device
        assert "unattributed" not in legs    # envelope joined: all known

    def test_rtt_without_server_envelope_is_unattributed_not_wire(self):
        """When neither route nor serve joined (ring overflow, a worker
        flight never collected) the RTT gap is UNKNOWN: charging it to
        ``wire`` would send readers chasing a wire problem that is not
        there."""
        recs = [_rec(0, 100, "nnsq_rtt", 1, 1)]
        legs = attribute_trace(recs)
        assert "wire" not in legs
        assert legs["unattributed"] == 100.0
        # inner spans that DID join shrink the residual
        recs += [
            _rec(12, 20, "sched_wait", 1, 2, cat="sched"),
            _rec(40, 30, "device_invoke", 1, 3, cat="device"),
        ]
        legs = attribute_trace(recs)
        assert "wire" not in legs
        assert legs["unattributed"] == 50.0  # rtt - queue - device


class TestMetricsFederation:
    def test_worker_label_injected_and_headers_deduped(self):
        reg_a, reg_b = MetricsRegistry(), MetricsRegistry()
        for reg, n in ((reg_a, 3), (reg_b, 5)):
            reg.counter("nnstpu_x_total", "x", labelnames=("k",)).inc(
                n, k="v")
            reg.histogram("nnstpu_h_ms", "h", buckets=(1.0,)).observe(0.5)
        merged = federate_metrics({"w0": render_text(reg_a),
                                   "w1": render_text(reg_b)})
        assert 'nnstpu_x_total{worker="w0",k="v"} 3' in merged
        assert 'nnstpu_x_total{worker="w1",k="v"} 5' in merged
        # bare-sample labels too (histogram _count has no labels)
        assert 'nnstpu_h_ms_count{worker="w0"} 1' in merged
        assert merged.count("# TYPE nnstpu_x_total counter") == 1
        assert merged.count("# HELP nnstpu_x_total x") == 1
        # exposition contract: all of a metric's samples grouped under
        # its single TYPE header
        lines = merged.splitlines()
        type_idx = lines.index("# TYPE nnstpu_x_total counter")
        samples = [i for i, l in enumerate(lines)
                   if l.startswith("nnstpu_x_total{")]
        between = lines[type_idx + 1:max(samples) + 1]
        assert all(l.startswith("nnstpu_x_total") for l in between)


class TestTraceEndpoint:
    def test_trace_json_served_next_to_healthz(self):
        spans.enable()
        spans.record_span("unit_span", spans.now_ns(), 1000,
                          trace=(0x77, 0))
        with MetricsServer(port=0) as ms:
            url = f"http://127.0.0.1:{ms.port}/trace.json"
            with urllib.request.urlopen(url, timeout=10) as resp:
                doc = json.loads(resp.read().decode())
            assert doc["pid"] > 0 and doc["clock_ns"] > 0
            assert any(r[4] == "unit_span" for r in doc["records"])
            assert doc["recorder"]["records"] >= 1
            with urllib.request.urlopen(url + "?clock=1",
                                        timeout=10) as resp:
                clk = json.loads(resp.read().decode())
            assert "records" not in clk and clk["clock_ns"] > 0

    def test_http_collector_source_aligns_local_server(self):
        spans.enable()
        spans.record_span("http_span", spans.now_ns(), 2000,
                          trace=(0x88, 0))
        with MetricsServer(port=0) as ms:
            c = TraceCollector()
            c.add_http("self", f"127.0.0.1:{ms.port}")
            collected = c.collect()
        assert not collected["errors"]
        src = collected["sources"]["self"]
        # same process: the estimated offset is just probe noise
        assert abs(src["offset_ns"]) < 50_000_000
        assert any(r[4] == "http_span" for r in src["records"])

    def test_trace_document_clock_only(self):
        doc = trace_document(clock_only=True)
        assert "records" not in doc and doc["clock_ns"] > 0


class TestCrossProcess:
    """A REAL second process: its perf_counter epoch differs from ours
    by construction, so this pins the whole HTTP + clock-alignment path
    (the in-process tests can only simulate skew)."""

    def test_subprocess_worker_trace_federates_and_aligns(self):
        import subprocess
        import sys

        from conftest import cpu_subprocess_env

        proc = subprocess.Popen(
            [sys.executable, "-m", "nnstreamer_tpu.fleet", "worker",
             "--name", "xw0", "--port", "0", "--health-port", "0",
             "--spans", "--platform", "cpu"],
            stdout=subprocess.PIPE, text=True, env=cpu_subprocess_env())
        try:
            ports = json.loads(proc.stdout.readline())
            addr = f"127.0.0.1:{ports['health_port']}"
            tid = 0xC0FFEE
            t0 = spans.now_ns()
            s = socket.create_connection(
                ("127.0.0.1", ports["port"]), timeout=15)
            try:
                send_tensors(s, (np.ones((2, 4), np.float32),), 0,
                             trace=(tid, 1), tenant="xproc")
                recv_tensors_ex(s)
            finally:
                s.close()
            t1 = spans.now_ns()

            c = TraceCollector()
            src = c.add_http("xw0", addr)
            collected = c.collect()
            assert not collected["errors"], collected["errors"]
            entry = collected["sources"]["xw0"]
            assert entry["process"] == "xw0"  # --spans names the process
            index = c.spans_by_trace(collected)
            serve = next(r for r in index[tid] if r[4] == "nnsq_serve")
            # ALIGNED onto our clock: the worker's serve span must land
            # inside our observed request window (epochs differ by the
            # process start delta — seconds — without alignment)
            assert t0 <= serve[1] <= serve[1] + serve[2] <= t1 + 5_000_000
            assert src.rtt_ns > 0
            # its /metrics endpoint scrapes clean (a bare worker has no
            # registered series yet — federation label injection is
            # pinned in TestMetricsFederation)
            with urllib.request.urlopen(f"http://{addr}/metrics",
                                        timeout=10) as resp:
                assert resp.status == 200
        finally:
            proc.terminate()
            proc.wait(timeout=30)


class TestFleetNesting:
    """The acceptance chain: a live request through router + 2 workers
    renders client nnsq_rtt → router nnsq_route → worker nnsq_serve →
    device_invoke, nested by containment on one merged timeline."""

    def test_rtt_route_serve_device_nest_on_one_timeline(self):
        spans.enable()
        membership = Membership(heartbeat_s=30.0)
        workers = [FleetWorker(name=f"cw{i}",
                               model=lambda x: x * 2.0).start()
                   for i in range(2)]
        for w in workers:
            membership.add("127.0.0.1", w.query_port, probe=w.probe,
                           worker_id=w.name)
        router = Router(membership, port=0, name="c-router").start()
        try:
            tid = spans.new_trace_id()
            tok = spans.span_begin(tid, 0)
            s = socket.create_connection(("127.0.0.1", router.port),
                                         timeout=10)
            try:
                send_tensors(s, (np.ones((2, 4), np.float32),), 0,
                             trace=(tid, tok[0]), tenant="acceptance")
                outs, _, _, _ = recv_tensors_ex(s)
            finally:
                spans.span_end(tok, "nnsq_rtt", "query")
                s.close()
            np.testing.assert_allclose(outs[0], 2.0)

            collector = TraceCollector()
            collector.add_local("inproc")
            chain = ["nnsq_rtt", "nnsq_route", "nnsq_serve",
                     "device_invoke"]
            # worker and router record their spans BEFORE sending each
            # reply, so once the client's recv returned the whole chain
            # is already in the flight recorders — no poll
            index = collector.spans_by_trace()
            by_name = {}
            for r in index.get(tid, ()):
                by_name.setdefault(r[4], r)
            assert set(chain) <= set(by_name), sorted(by_name)
            for outer, inner in zip(chain, chain[1:]):
                o, i = by_name[outer], by_name[inner]
                # start containment is exact; end containment gets wide
                # slack because an inner span_end (worker thread, post-
                # reply) can be descheduled past the outer thread's end
                assert o[1] <= i[1] <= o[1] + o[2], (outer, inner)
                assert i[1] + i[2] <= o[1] + o[2] + 50_000_000, \
                    (outer, inner)
            # parent links cross the wire: route's parent is the rtt
            # span id, serve's parent is the route span id
            assert by_name["nnsq_route"][8] == tok[0]
            assert by_name["nnsq_serve"][8] == by_name["nnsq_route"][7]
            # and the merged doc is valid Perfetto with the chain present
            doc = json.loads(json.dumps(collector.chrome_trace()))
            names = {ev["name"] for ev in doc["traceEvents"]}
            assert set(chain) <= names
        finally:
            router.stop()
            membership.stop()
            for w in workers:
                w.stop()
