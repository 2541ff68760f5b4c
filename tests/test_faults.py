"""Fault injection & self-healing: chaos engine determinism, restart
policies, watchdog escalation, resilient NNSQ clients, breaker tripping,
and backend CPU degradation."""

import socket
import struct
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu import Pipeline, faults
from nnstreamer_tpu.backends.jax_backend import JaxModel
from nnstreamer_tpu.buffer import Event, Frame
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.query import (
    QueryServer,
    QuerySessionBrokenError,
    QueryTimeoutError,
    QueryUnavailableError,
    TensorQueryClient,
    recv_tensors,
    send_tensors,
)
from nnstreamer_tpu.elements.queue import Queue
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.faults import ChaosEngine, InjectedFault, parse_spec
from nnstreamer_tpu.graph.node import SourceNode
from nnstreamer_tpu.graph.pipeline import PipelineError, RestartPolicy
from nnstreamer_tpu.obs.watchdog import PipelineWatchdog
from nnstreamer_tpu.sched.breaker import BreakerOpenError, CircuitBreaker, \
    trip_all
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

F32 = np.float32
VEC4 = TensorsSpec.of(TensorSpec(dtype=F32, shape=(4,)))


@pytest.fixture(autouse=True)
def _clean_chaos():
    yield
    faults.deactivate()


def _frames(n):
    return [Frame.of(np.full(4, float(i), F32), pts=i) for i in range(n)]


# -- spec grammar + determinism --------------------------------------------


class TestSpecGrammar:
    def test_parse_kinds_targets_params(self):
        seed, rules = parse_spec(
            "seed=7;invoke_raise@f:every=5;socket_drop@server:rate=0.1,"
            "count=3;queue_wedge@q0:after=10,ms=250")
        assert seed == 7
        assert [(r.kind, r.target) for r in rules] == [
            ("invoke_raise", "f"), ("socket_drop", "server"),
            ("queue_wedge", "q0")]
        assert rules[1].rate == 0.1 and rules[1].count == 3
        assert rules[2].after == 10 and rules[2].ms == 250

    def test_bare_after_is_single_shot(self):
        _, (rule,) = parse_spec("invoke_raise:after=3")
        assert rule.count == 1

    def test_bad_specs_raise(self):
        with pytest.raises(ValueError):
            parse_spec("not_a_kind:rate=0.1")
        with pytest.raises(ValueError):
            parse_spec("invoke_raise:bogus=1")
        with pytest.raises(ValueError):
            parse_spec("invoke_raise")  # no trigger param
        with pytest.raises(ValueError):
            parse_spec("invoke_raise:rate=1.5")

    def test_target_mismatch_consumes_no_opportunity(self):
        eng = ChaosEngine("invoke_raise@f:every=2")
        for _ in range(10):
            assert eng.decide("backend_invoke", "other") is None
        assert eng.rules[0].opportunities == 0

    def test_identical_seed_identical_sequence(self):
        spec = ("seed=42;invoke_raise@f:rate=0.2;"
                "invoke_delay@f:rate=0.3,ms=1;socket_drop:rate=0.15")
        a, b = ChaosEngine(spec), ChaosEngine(spec)
        for eng in (a, b):
            for i in range(300):
                eng.decide("backend_invoke", "f")
                eng.decide("nnsq_send", "nnsq.server")
        assert a.log and a.log == b.log
        assert a.injections == b.injections
        # a different seed produces a different sequence
        c = ChaosEngine(spec.replace("seed=42", "seed=43"))
        for i in range(300):
            c.decide("backend_invoke", "f")
            c.decide("nnsq_send", "nnsq.server")
        assert c.log != a.log

    def test_every_is_deterministic_without_rng(self):
        eng = ChaosEngine("invoke_raise@f:every=4,after=2")
        fired = [bool(eng.decide("backend_invoke", "f"))
                 for _ in range(14)]
        assert [i + 1 for i, f in enumerate(fired) if f] == [6, 10, 14]


# -- restart policies in the graph runtime ---------------------------------


class TestRestartPolicies:
    def test_restart_policy_absorbs_injected_raises(self):
        n = 20
        eng = faults.install("invoke_raise@f:every=5")
        got = []
        p = Pipeline(name="faults_restart")
        src = p.add(DataSrc(data=_frames(n)))
        filt = p.add(TensorFilter(framework="custom", model=lambda x: x * 2,
                                  name="f"))
        sink = p.add(TensorSink(name="out"))
        sink.connect("new-data",
                     lambda fr: got.append(float(np.asarray(fr.tensor(0))[0])))
        p.link_chain(src, filt, sink)
        p.set_restart_policy("f", mode="restart", backoff_ms=1,
                             backoff_cap_ms=5, max_restarts=100)
        p.run(timeout=120)
        raises = eng.injections["invoke_raise"]
        assert raises == 4  # every=5 over 20 frames
        assert len(got) == n - raises
        rec = p.recovery_stats()
        assert rec["actions"]["restart_node"] == raises
        assert rec["shed_total"] == raises
        assert p.state == "STOPPED" and p._error is None

    def test_quarantine_passthrough(self):
        n = 12
        eng = faults.install("invoke_raise@f:after=5")  # one-shot at opp 6
        got = []
        p = Pipeline(name="faults_quarantine")
        src = p.add(DataSrc(data=_frames(n)))
        filt = p.add(TensorFilter(framework="custom", model=lambda x: x + 1,
                                  name="f"))
        sink = p.add(TensorSink(name="out"))
        sink.connect("new-data",
                     lambda fr: got.append(float(np.asarray(fr.tensor(0))[0])))
        p.link_chain(src, filt, sink)
        p.set_restart_policy("f", mode="quarantine-passthrough")
        p.run(timeout=120)
        assert eng.injections["invoke_raise"] == 1
        # frames 0-4 processed (+1), frame 5 shed, 6-11 pass through RAW
        assert got == [float(i + 1) for i in range(5)] + \
            [float(i) for i in range(6, n)]
        rec = p.recovery_stats()
        assert rec["actions"]["quarantine"] == 1
        assert rec["shed_total"] == 1
        assert rec["quarantined"] == ["f"]
        assert filt._quarantined and filt._quarantine_passthrough

    def test_restart_storm_escalates_to_pipeline_failure(self):
        faults.install("invoke_raise@f:every=1")  # every frame faults
        p = Pipeline(name="faults_storm")
        src = p.add(DataSrc(data=_frames(10)))
        filt = p.add(TensorFilter(framework="custom", model=lambda x: x,
                                  name="f"))
        p.link_chain(src, filt, p.add(TensorSink(name="out")))
        p.set_restart_policy("f", mode="restart", backoff_ms=1,
                             backoff_cap_ms=2, max_restarts=3, window_s=60)
        with pytest.raises(PipelineError):
            p.run(timeout=120)
        rec = p.recovery_stats()
        assert rec["actions"]["restart_node"] == 3  # budget, then escalate
        assert p.state == "STOPPED"  # full teardown ran from ERROR

    def test_source_restart_policy_reenters_frames(self):
        class FlakySrc(SourceNode):
            def __init__(self):
                super().__init__("flaky")
                self.runs = 0

            def output_spec(self):
                return VEC4

            def frames(self):
                self.runs += 1
                if self.runs == 1:
                    yield Frame.of(np.zeros(4, F32), pts=0)
                    raise RuntimeError("camera hiccup")
                for i in range(1, 4):
                    yield Frame.of(np.full(4, float(i), F32), pts=i)

        got = []
        p = Pipeline(name="faults_src_restart")
        src = p.add(FlakySrc())
        sink = p.add(TensorSink(name="out"))
        sink.connect("new-data", lambda fr: got.append(fr.pts))
        p.link(src, sink)
        p.set_restart_policy("flaky", mode="restart", backoff_ms=1)
        p.run(timeout=120)
        assert got == [0, 1, 2, 3]
        assert p.recovery_stats()["actions"]["restart_source"] == 1

    def test_restart_reinstalls_fused_transforms(self):
        """A restarted filter must re-run its commit phase: with transform
        fusion the pre-transform (typecast) lives INSIDE the filter's
        compiled program, so a bare stop()+start() would leave the backend
        mis-reconciling raw uint8 frames against its float32 model spec
        (found by driving the videotestsrc topology under chaos)."""
        eng = faults.install("invoke_raise@f:every=4")
        from nnstreamer_tpu import make

        model = JaxModel(
            apply=lambda p_, x: x.reshape(-1).sum()[None],
            input_spec=TensorsSpec.of(
                TensorSpec(dtype=F32, shape=(8, 8, 3))))
        got = []
        p = Pipeline(name="faults_fused_restart")
        src = p.add(make("videotestsrc", num_buffers=10, width=8, height=8))
        conv = p.add(make("tensor_converter", name="c"))
        tr = p.add(make("tensor_transform", name="t", mode="arithmetic",
                        option="typecast:float32,div:255.0"))
        filt = p.add(TensorFilter(framework="jax", model=model, name="f"))
        sink = p.add(TensorSink(name="out"))
        sink.connect("new-data", lambda fr: got.append(fr.pts))
        p.link_chain(src, conv, tr, filt, sink)
        p.set_restart_policy("f", mode="restart", backoff_ms=1,
                             max_restarts=50)
        p.run(timeout=120)
        raises = eng.injections["invoke_raise"]
        assert raises == 2  # every=4 over 10 frames (fusion: 1 opp/frame)
        assert len(got) == 10 - raises
        assert p.recovery_stats()["actions"]["restart_node"] == raises
        assert p._error is None

    def test_conf_default_policy_and_env_spec(self, monkeypatch):
        monkeypatch.setenv("NNSTPU_FAULTS", "seed=5;invoke_raise@f:every=4")
        monkeypatch.setenv("NNSTPU_RECOVERY_POLICY", "restart")
        monkeypatch.setenv("NNSTPU_RECOVERY_BACKOFF_MS", "1")
        got = []
        p = Pipeline(name="faults_conf")
        src = p.add(DataSrc(data=_frames(8)))
        filt = p.add(TensorFilter(framework="custom", model=lambda x: x,
                                  name="f"))
        sink = p.add(TensorSink(name="out"))
        sink.connect("new-data", lambda fr: got.append(fr.pts))
        p.link_chain(src, filt, sink)
        p.run(timeout=120)  # no explicit policy: conf supplies "restart"
        eng = faults.engine()
        assert eng is not None and eng.injections["invoke_raise"] == 2
        assert len(got) == 6
        assert p.recovery_stats()["actions"]["restart_node"] == 2


# -- post_error teardown (satellite regression) ----------------------------


class TestErrorTeardown:
    def test_stop_after_post_error_joins_threads_and_transitions(self):
        def boom(x):
            if x[0] >= 10:  # negotiation probes with zeros: let those pass
                raise RuntimeError("model exploded")
            return x

        p = Pipeline(name="faults_teardown")
        src = p.add(DataSrc(data=_frames(50)))
        q = p.add(Queue(max_size_buffers=4, name="q"))
        filt = p.add(TensorFilter(framework="custom", model=boom, name="f"))
        p.link_chain(src, q, filt, p.add(TensorSink(name="out")))
        with pytest.raises(PipelineError):
            p.run(timeout=120)
        assert p.state == "STOPPED"
        assert not p.threads  # joined and cleared, no leaked PLAYING threads
        for t in threading.enumerate():
            assert not t.name.startswith("src:"), t
            assert t.name != "queue:q", t
        assert not src._started  # every node ran its STOPPED transition


# -- watchdog escalation ---------------------------------------------------


class TestWatchdogRecovery:
    def test_restarts_stalled_source(self):
        class OneStallSrc(SourceNode):
            def __init__(self):
                super().__init__("cam")
                self.runs = 0

            def output_spec(self):
                return VEC4

            def frames(self):
                self.runs += 1
                yield Frame.of(np.zeros(4, F32), pts=0)
                if self.runs == 1:
                    self._stop_evt.wait()  # stall until restarted
                    return
                for i in range(1, 5):
                    yield Frame.of(np.full(4, float(i), F32), pts=i)

        got = []
        p = Pipeline(name="faults_wd_src")
        src = p.add(OneStallSrc())
        sink = p.add(TensorSink(name="out"))
        sink.connect("new-data", lambda fr: got.append(fr.pts))
        p.link(src, sink)
        wd = p.attach_tracer(PipelineWatchdog(
            interval_s=0.05, stall_s=0.2, recover=True))
        p.start()
        assert p.wait(timeout=60)
        p.stop()
        assert src.runs == 2  # the watchdog restarted the source
        assert 1 in got and 4 in got  # the restarted stream flowed
        assert p.recovery_stats()["actions"]["restart_source"] >= 1
        assert wd.summary()["recoveries"] >= 1

    def test_drains_wedged_queue(self):
        n = 40
        faults.install("queue_wedge@qw:after=1,ms=1500")  # one-shot wedge
        got = []
        p = Pipeline(name="faults_wd_queue")
        src = p.add(DataSrc(data=_frames(n)))
        q = p.add(Queue(max_size_buffers=200, name="qw"))
        sink = p.add(TensorSink(name="out"))
        sink.connect("new-data", lambda fr: got.append(fr.pts))
        p.link_chain(src, q, sink)
        p.attach_tracer(PipelineWatchdog(
            interval_s=0.05, stall_s=0.2, recover=True))
        p.start()
        assert p.wait(timeout=60)
        p.stop()
        rec = p.recovery_stats()
        assert rec["actions"].get("drain_queue", 0) >= 1
        # frame accounting balances: delivered + typed sheds == offered
        assert len(got) + rec["shed_total"] == n
        assert rec["shed_total"] > 0

    def test_overdue_device_trips_breakers(self):
        br = CircuitBreaker(failure_threshold=3, reset_timeout_s=60)
        assert br.state == "closed"
        n = trip_all(reason="test")
        assert n >= 1
        assert br.state == "open" and br.forced_trips == 1
        with pytest.raises(BreakerOpenError):
            br.allow()
        # re-tripping while open restarts the timeout, no double count
        br.trip()
        assert br.trips == 1 and br.forced_trips == 2


# -- resilient NNSQ client -------------------------------------------------


def _silent_server():
    """Accepts, reads, never replies.  Returns (sock, port, stop)."""
    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]
    conns = []
    stop = threading.Event()

    def run():
        while not stop.is_set():
            try:
                c, _ = srv.accept()
            except OSError:
                return
            conns.append(c)

    t = threading.Thread(target=run, daemon=True)
    t.start()

    def shutdown():
        stop.set()
        srv.close()
        for c in conns:
            try:
                c.close()
            except OSError:
                pass

    return port, shutdown


class TestResilientClient:
    def test_request_timeout_raises_typed(self):
        port, shutdown = _silent_server()
        try:
            cli = TensorQueryClient(host="127.0.0.1", port=port,
                                    out_spec=VEC4, request_timeout=0.3,
                                    name="cli_t")
            cli.start()
            t0 = time.monotonic()
            with pytest.raises(QueryTimeoutError):
                cli.process(None, Frame.of(np.zeros(4, F32), pts=0))
            assert time.monotonic() - t0 < 5.0  # bounded, not forever
            assert cli._sock is None  # the socket was dropped, not reused
        finally:
            shutdown()

    def test_torn_frame_detected_not_misparsed(self):
        srv = socket.create_server(("127.0.0.1", 0))
        port = srv.getsockname()[1]

        class _Buf:
            def __init__(self):
                self.data = b""

            def sendall(self, b):
                self.data += b

        buf = _Buf()
        send_tensors(buf, (np.arange(4, dtype=F32),), 0)

        def serve_half():
            c, _ = srv.accept()
            recv_tensors(c)  # consume the request
            c.sendall(buf.data[: len(buf.data) // 2])  # torn reply
            c.close()

        t = threading.Thread(target=serve_half, daemon=True)
        t.start()
        try:
            cli = TensorQueryClient(host="127.0.0.1", port=port,
                                    out_spec=VEC4, request_timeout=5.0,
                                    name="cli_torn")
            cli.start()
            with pytest.raises(ConnectionError, match="mid-message"):
                cli.process(None, Frame.of(np.zeros(4, F32), pts=0))
        finally:
            srv.close()

    def test_retry_reconnects_through_injected_drops(self):
        eng = faults.install("socket_drop@server:every=3,count=2")
        with QueryServer(framework="custom", model=lambda x: x * 2.0) as srv:
            cli = TensorQueryClient(
                host="127.0.0.1", port=srv.port, out_spec=VEC4,
                request_timeout=10.0, retries=2, retry_backoff_ms=5,
                name="cli_retry")
            cli.start()
            for i in range(8):
                out = cli.process(
                    None, Frame.of(np.full(4, float(i), F32), pts=i))
                np.testing.assert_allclose(np.asarray(out.tensor(0)), 2.0 * i)
            assert eng.injections["socket_drop"] == 2
            assert cli.retries_total == 2
            assert cli.reconnects >= 2

    def test_stateful_session_fails_fast_never_replays(self):
        eng = faults.install("socket_drop@server:every=1,count=1")
        with QueryServer(framework="custom", model=lambda x: x) as srv:
            cli = TensorQueryClient(
                host="127.0.0.1", port=srv.port, out_spec=VEC4,
                request_timeout=10.0, retries=5, stateful=True,
                name="cli_state")
            cli.start()
            with pytest.raises(QuerySessionBrokenError):
                cli.process(None, Frame.of(np.zeros(4, F32), pts=0))
            assert cli.retries_total == 0  # fail fast, no silent replay
            assert eng.injections["socket_drop"] == 1

    def test_typed_server_errors_are_not_retried(self):
        from nnstreamer_tpu.sched import AdmissionController, Scheduler

        # each (4,) request costs 4 admission tokens: burst=4 admits one,
        # the near-zero refill rate sheds the second with a typed frame
        sch = Scheduler("fifo",
                        admission=AdmissionController(max_queue=8, rate=0.001,
                                                      burst=4),
                        name="faults_tight")
        with QueryServer(framework="custom", model=lambda x: x,
                         scheduler=sch) as srv:
            cli = TensorQueryClient(
                host="127.0.0.1", port=srv.port, out_spec=VEC4,
                retries=3, retry_backoff_ms=5, name="cli_typed")
            cli.start()
            # first request drains the burst token; the second is shed
            cli.process(None, Frame.of(np.zeros(4, F32), pts=0))
            from nnstreamer_tpu.elements.query import QueryOverloadError

            with pytest.raises(QueryOverloadError):
                cli.process(None, Frame.of(np.zeros(4, F32), pts=1))
            assert cli.retries_total == 0  # typed shed != connection failure
        sch.close()

    def test_decode_server_failure_is_typed_unavailable(self):
        from nnstreamer_tpu.serving import ContinuousBatcher, DecodeServer

        eng = ContinuousBatcher(capacity=2, t_max=8, d_in=4, n_out=2,
                                d_model=8, n_heads=2, n_layers=1)
        with DecodeServer(eng) as srv:
            eng.stop()  # the engine dies under the serving edge
            s = socket.create_connection(("127.0.0.1", srv.port))
            try:
                send_tensors(s, (np.zeros(4, F32),), 0)
                with pytest.raises(QueryUnavailableError):
                    recv_tensors(s)
            finally:
                s.close()


# -- queue recovery (unit) -------------------------------------------------


class TestQueueRecover:
    def test_drains_frames_preserves_events_respawns_worker(self):
        q = Queue(max_size_buffers=32, name="qr")
        q._ensure_queue()
        for i in range(5):
            q._q.push(Frame.of(np.zeros(2, F32), pts=i))
        q._q.push(Event.eos())
        drained, threads = q.recover()
        assert drained == 5
        assert q.dropped == 5
        assert len(q._q) == 1  # the EOS survived, in place
        assert len(threads) == 1  # no live worker: a fresh one is handed back
        q._q.shutdown()


# -- backend degradation ---------------------------------------------------


class TestDegradedBackend:
    def test_compile_failure_degrades_to_cpu_when_asked(self, monkeypatch):
        from nnstreamer_tpu.obs.export import degraded_snapshot

        monkeypatch.setenv("NNSTPU_RECOVERY_CPU_FALLBACK", "true")  # opt in
        eng = faults.install("compile_raise:count=1")
        model = JaxModel(apply=lambda p_, x: x * 3.0, input_spec=VEC4,
                         name="degrade_me")
        got = []
        p = Pipeline(name="faults_degrade")
        src = p.add(DataSrc(data=_frames(5)))
        filt = p.add(TensorFilter(framework="jax", model=model, name="f"))
        sink = p.add(TensorSink(name="out"))
        sink.connect("new-data",
                     lambda fr: got.append(float(np.asarray(fr.tensor(0))[0])))
        p.link_chain(src, filt, sink)
        backend = filt.backend
        p.start()
        try:
            assert p.wait(timeout=120)
            assert got == [3.0 * i for i in range(5)]  # served through it
            assert eng.injections["compile_raise"] == 1
            assert backend._degraded is not None
            snap = degraded_snapshot()
            assert any("degrade_me" in k or "degrade_me" in v
                       for k, v in snap.items()), snap
        finally:
            p.stop()
        # close() withdrew the degraded reason: /healthz is clean again
        assert not degraded_snapshot()

    def test_compile_failure_fails_the_pipeline_by_default(self):
        """No env, no ini: a compile that raises takes the stream down —
        nothing is served from the CPU behind a healthy /healthz."""
        from nnstreamer_tpu.conf import DEFAULTS
        from nnstreamer_tpu.obs.export import degraded_snapshot

        assert DEFAULTS["recovery"]["cpu_fallback"] == "false"
        eng = faults.install("compile_raise:count=1")
        model = JaxModel(apply=lambda p_, x: x, input_spec=VEC4)
        got = []
        p = Pipeline(name="faults_nodegrade")
        src = p.add(DataSrc(data=_frames(2)))
        filt = p.add(TensorFilter(framework="jax", model=model, name="f"))
        sink = p.add(TensorSink(name="out"))
        sink.connect("new-data", got.append)
        p.link_chain(src, filt, sink)
        with pytest.raises((PipelineError, InjectedFault)):
            p.start()
            p.wait(timeout=60)
        p.stop()
        assert eng.injections["compile_raise"] == 1
        assert got == []
        assert filt.backend._degraded is None
        assert not degraded_snapshot()


# -- restart policy object -------------------------------------------------


class TestPolicyObject:
    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError):
            RestartPolicy("reboot-the-universe")

    def test_pipeline_policy_lookup_order(self):
        p = Pipeline(name="faults_lookup")
        p.set_restart_policy("*", mode="quarantine-passthrough")
        p.set_restart_policy("f", mode="restart")
        assert p.restart_policy_for("f").mode == "restart"
        assert p.restart_policy_for("g").mode == "quarantine-passthrough"
