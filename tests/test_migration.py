"""Live decode-session migration (ISSUE 12): checkpoint/restore of
ContinuousBatcher slots, the MIGRATE/RESUME wire ops, the router's
zero-downtime drain handoff, and its chaos degradation paths.

The acceptance contract: a planned drain completes every in-flight
session on another worker with TOKEN-IDENTICAL output; anything that
cannot migrate (old peers on the version-gated wire path, no target,
an injected ``migrate_abort``) degrades to today's typed ``[SESSION]``
verdict with the source slot freed — never a hang, never a duplicate
step.
"""

import socket
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu import faults
from nnstreamer_tpu.elements.query import (
    MIGRATE_PTS,
    RESUME_PTS,
    QueryMigratingError,
    QuerySessionBrokenError,
    pack_session_control,
    recv_tensors,
    send_tensors,
)
from nnstreamer_tpu.fleet import DRAINING, FleetWorker, Membership, Router
from nnstreamer_tpu.fleet.repo import TensorRepoServer
from nnstreamer_tpu.serving import (
    ContinuousBatcher,
    DecodeServer,
    pack_session_snapshot,
    unpack_session_snapshot,
)

ENGINE_CFG = dict(capacity=2, t_max=8, d_in=4, n_out=4, d_model=16,
                  n_heads=2, n_layers=1)


def _wait_for(fn, timeout=15.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return fn()


def _prompt(seed=0, t=3, d=4):
    return np.random.RandomState(seed).rand(t, d).astype(np.float32)


def _steps(n, d=4, base=10):
    return [np.random.RandomState(base + i).rand(d).astype(np.float32)
            for i in range(n)]


def _control_run(prompt, steps, **over):
    """Reference transcript: one unmigrated session end to end."""
    cfg = dict(ENGINE_CFG)
    cfg.update(over)
    with ContinuousBatcher(**cfg) as eng:
        sess = eng.open_session()
        sess.prefill(prompt)
        out = [sess.get(timeout=10)]
        for s in steps:
            sess.feed(s)
            out.append(sess.get(timeout=10))
        sess.close()
    return out


@pytest.fixture(scope="module")
def engines():
    """Two same-geometry engines (source + target) shared by the
    engine-level tests; sessions are cheap, engines are not."""
    a = ContinuousBatcher(**ENGINE_CFG)
    b = ContinuousBatcher(**ENGINE_CFG)
    yield a, b
    a.stop()
    b.stop()


# -- engine checkpoint / restore --------------------------------------------


class TestSnapshotRestore:
    def test_token_identical_across_engines(self, engines):
        """The headline contract: prefill + 3 steps on A, snapshot,
        restore on B, 3 more steps — byte-for-byte equal to an
        unmigrated control run."""
        a, b = engines
        prompt, steps = _prompt(), _steps(6)
        ctl = _control_run(prompt, steps)
        sa = a.open_session()
        sa.prefill(prompt)
        out = [sa.get(timeout=10)]
        for s in steps[:3]:
            sa.feed(s)
            out.append(sa.get(timeout=10))
        snap = sa.snapshot()
        sa.close()
        sb = b.restore_session(unpack_session_snapshot(
            pack_session_snapshot(snap)))
        for s in steps[3:]:
            sb.feed(s)
            out.append(sb.get(timeout=10))
        sb.close()
        for i, (x, y) in enumerate(zip(ctl, out)):
            np.testing.assert_array_equal(x, y, err_msg=f"output {i}")
        assert a.stats()["sessions_migrated_out"] >= 1
        assert b.stats()["sessions_migrated_in"] >= 1

    def test_a_snapshot_right_behind_a_restore_carries_the_restored_state(
            self, engines):
        """A session restored on B and snapshotted again before B's engine
        thread has gathered (a handoff onto a worker that is drained
        meanwhile; under load the thread loses the race for the lock): the
        second snapshot is the first one's state, not what the slot's
        arrays held before, and the stream stays token-identical on A."""
        a, b = engines
        prompt, steps = _prompt(seed=5), _steps(4, base=50)
        ctl = _control_run(prompt, steps)
        # B's slots hold another stream's state from before
        other = b.open_session()
        other.prefill(_prompt(seed=6))
        other.get(timeout=10)
        other.close()
        sa = a.open_session()
        sa.prefill(prompt)
        out = [sa.get(timeout=10)]
        for s in steps[:2]:
            sa.feed(s)
            out.append(sa.get(timeout=10))
        snap = sa.snapshot()
        sa.close()
        with b._cv:  # B's engine thread cannot gather in between
            sb = b.restore_session(snap)
            again = b.snapshot_session(sb, timeout=5)
        sb.close()
        assert again["pos"] == snap["pos"]
        np.testing.assert_array_equal(again["cache"], snap["cache"])
        sa = a.restore_session(again)
        for s in steps[2:]:
            sa.feed(s)
            out.append(sa.get(timeout=10))
        sa.close()
        for i, (x, y) in enumerate(zip(ctl, out)):
            np.testing.assert_array_equal(x, y, err_msg=f"output {i}")

    def test_snapshot_mid_prefill_restores_position_t(self, engines):
        """A pending (not yet applied) prefill rides the snapshot's
        queue; an APPLIED prefill rides as cache+pos — both continue
        from position T on the target."""
        a, b = engines
        prompt, steps = _prompt(seed=3), _steps(2, base=40)
        ctl = _control_run(prompt, steps)
        # applied prefill: consume its output, snapshot at pos T
        sa = a.open_session()
        sa.prefill(prompt)
        out = [sa.get(timeout=10)]
        snap = sa.snapshot()
        assert snap["pos"] == prompt.shape[0]
        sa.close()
        sb = b.restore_session(snap)
        assert sb.pos == prompt.shape[0]
        for s in steps:
            sb.feed(s)
            out.append(sb.get(timeout=10))
        sb.close()
        for x, y in zip(ctl, out):
            np.testing.assert_array_equal(x, y)
        # pending prefill: snapshot BEFORE the engine applied it (the
        # session is gated first, so the queued item must travel)
        sa = a.open_session()
        sa._gated = True  # freeze gathers for this slot deterministically
        sa.prefill(prompt)
        snap2 = a.snapshot_session(sa)
        assert len(snap2["pending_in"]) == 1
        assert snap2["pending_in"][0][0] == "prefill"
        sa.close()
        sb = b.restore_session(unpack_session_snapshot(
            pack_session_snapshot(snap2)))
        got = [sb.get(timeout=10)]
        for s in steps:
            sb.feed(s)
            got.append(sb.get(timeout=10))
        sb.close()
        for x, y in zip(ctl, got):
            np.testing.assert_array_equal(x, y)

    def test_pending_outputs_redeliver_in_order(self, engines):
        """Outputs computed but not yet consumed at snapshot time arrive
        FIRST on the restored session — no token lost, none duplicated."""
        a, b = engines
        prompt, steps = _prompt(seed=5), _steps(3, base=60)
        ctl = _control_run(prompt, steps)
        sa = a.open_session()
        sa.prefill(prompt)
        sa.feed(steps[0])
        # wait until both outputs are computed, consume NEITHER
        assert _wait_for(lambda: sa._q_out.qsize() >= 2, 10)
        snap = sa.snapshot()
        assert len(snap["pending_out"]) == 2
        sa.close()
        sb = b.restore_session(unpack_session_snapshot(
            pack_session_snapshot(snap)))
        got = [sb.get(timeout=10), sb.get(timeout=10)]
        for s in steps[1:]:
            sb.feed(s)
            got.append(sb.get(timeout=10))
        sb.close()
        for x, y in zip(ctl, got):
            np.testing.assert_array_equal(x, y)

    def test_abort_snapshot_rearms_in_place(self, engines):
        """A failed handoff BEFORE the point of no return re-queues the
        drained items and the session keeps serving where it was."""
        a, _ = engines
        prompt, steps = _prompt(seed=7), _steps(2, base=80)
        ctl = _control_run(prompt, steps)
        sa = a.open_session()
        sa.prefill(prompt)
        out = [sa.get(timeout=10)]
        sa.feed(steps[0])  # in the queue or in flight
        snap = a.snapshot_session(sa)
        assert sa._gated
        a.abort_snapshot(sa, snap)
        assert not sa._gated
        out.append(sa.get(timeout=10))
        sa.feed(steps[1])
        out.append(sa.get(timeout=10))
        sa.close()
        for x, y in zip(ctl, out):
            np.testing.assert_array_equal(x, y)

    def test_geometry_mismatch_typed_refused(self, engines):
        """Wrong-shaped state is refused with a clear error, never
        silently served."""
        a, _ = engines
        sa = a.open_session()
        snap = sa.snapshot()
        sa.close()
        for key, val in (("d_in", 8), ("t_max", 16), ("window", True)):
            bad = dict(snap)
            bad[key] = val
            with pytest.raises(ValueError, match="geometry mismatch"):
                a.restore_session(bad)
        bad = dict(snap)
        bad["cache"] = np.zeros((2, 2, 8, 16), np.float32)  # wrong L
        with pytest.raises(ValueError, match="geometry mismatch"):
            a.restore_session(bad)
        # the refusals must not leak slots
        s1 = a.open_session(timeout=1)
        s2 = a.open_session(timeout=1)
        s1.close()
        s2.close()

    def test_restore_across_mesh_widths(self):
        """Slot state snapshotted from an unsharded engine restores onto
        a mesh-sharded one (and back) — re-placed under the target's
        sharding, token-identical."""
        prompt, steps = _prompt(seed=9), _steps(4, base=90)
        cfg = dict(ENGINE_CFG)
        ctl = _control_run(prompt, steps)
        with ContinuousBatcher(**cfg) as plain, \
                ContinuousBatcher(devices=2, **cfg) as meshed:
            sa = plain.open_session()
            sa.prefill(prompt)
            out = [sa.get(timeout=10)]
            for s in steps[:2]:
                sa.feed(s)
                out.append(sa.get(timeout=10))
            snap = sa.snapshot()
            sa.close()
            sb = meshed.restore_session(snap)
            sb.feed(steps[2])
            out.append(sb.get(timeout=10))
            # and back: mesh -> unsharded
            snap2 = sb.snapshot()
            sb.close()
            sc = plain.restore_session(snap2)
            sc.feed(steps[3])
            out.append(sc.get(timeout=10))
            sc.close()
        for i, (x, y) in enumerate(zip(ctl, out)):
            np.testing.assert_allclose(x, y, rtol=0, atol=1e-6,
                                       err_msg=f"output {i}")

    def test_pack_unpack_validation(self, engines):
        a, _ = engines
        sa = a.open_session()
        snap = sa.snapshot()
        sa.close()
        packed = pack_session_snapshot(snap)
        rt = unpack_session_snapshot(packed)
        assert rt["pos"] == snap["pos"] and rt["t_max"] == snap["t_max"]
        np.testing.assert_array_equal(rt["cache"], snap["cache"])
        # tampered framing is refused
        with pytest.raises(ValueError):
            unpack_session_snapshot(packed[:2])
        bad = (np.array([99], np.int64),) + packed[1:]
        with pytest.raises(ValueError):
            unpack_session_snapshot(bad)
        # pathological pending queue refuses to pack (falls back typed)
        over = dict(snap)
        over["pending_in"] = [np.zeros(4, np.float32)] * 13
        with pytest.raises(RuntimeError, match="pending"):
            pack_session_snapshot(over)


# -- the MIGRATE/RESUME wire ops --------------------------------------------


class RawClient:
    def __init__(self, port, host="127.0.0.1", timeout=15.0):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.settimeout(timeout)

    def request(self, arrays, pts=0):
        send_tensors(self.sock, arrays, pts)
        return recv_tensors(self.sock)

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass


class TestWireOps:
    def test_migrate_then_resume_across_servers(self):
        """Drive the control ops directly: snapshot off server A into
        the repo, resume on server B, finish the stream token-identical;
        frames racing the completed migrate get the typed [MIGRATING]
        'not applied' verdict on the old connection."""
        prompt, steps = _prompt(seed=11), _steps(4, base=110)
        ctl = _control_run(prompt, steps)
        ea = ContinuousBatcher(**ENGINE_CFG)
        eb = ContinuousBatcher(**ENGINE_CFG)
        sa = DecodeServer(ea, port=0).start()
        sb = DecodeServer(eb, port=0).start()
        repo = TensorRepoServer(port=0).start()
        try:
            c = RawClient(sa.port)
            out = [np.asarray(c.request((prompt,))[0][0])]
            for s in steps[:2]:
                out.append(np.asarray(c.request((s,))[0][0]))
            ctl_frame = pack_session_control(
                f"127.0.0.1:{repo.port}", 77, 5000)
            acks, _ = c.request(ctl_frame, pts=MIGRATE_PTS)
            assert int(np.asarray(acks[0])[0]) == 1
            assert ea.stats()["active_sessions"] == 0  # slot freed
            # the old connection answers [MIGRATING], state untouched
            with pytest.raises(QueryMigratingError):
                c.request((steps[2],))
            c.close()
            c2 = RawClient(sb.port)
            acks, _ = c2.request(ctl_frame, pts=RESUME_PTS)
            assert int(np.asarray(acks[0])[0]) == 1
            for s in steps[2:]:
                out.append(np.asarray(c2.request((s,))[0][0]))
            c2.close()
            for x, y in zip(ctl, out):
                np.testing.assert_array_equal(x, y)
            assert sa.stats()["sessions_migrated"] == 1
            assert sb.stats()["sessions_restored"] == 1
        finally:
            sa.stop()
            sb.stop()
            repo.stop()
            ea.stop()
            eb.stop()

    def test_migration_disabled_answers_plain_error(self):
        """The version gate: a server without the migration ops (old
        peer emulation) answers the control frame with a PLAIN error —
        exactly what the router reads as 'cannot migrate, fall back'."""
        eng = ContinuousBatcher(**ENGINE_CFG)
        srv = DecodeServer(eng, port=0, migration=False).start()
        repo = TensorRepoServer(port=0).start()
        try:
            c = RawClient(srv.port)
            c.request((np.zeros(4, np.float32),))  # live session
            ctl_frame = pack_session_control(
                f"127.0.0.1:{repo.port}", 5, 2000)
            with pytest.raises(RuntimeError) as ei:
                c.request(ctl_frame, pts=MIGRATE_PTS)
            # plain error, not a typed migration/session verdict
            assert not isinstance(
                ei.value, (QueryMigratingError, QuerySessionBrokenError))
            # ...and the session is untouched: it keeps stepping
            outs, _ = c.request((np.zeros(4, np.float32),))
            assert outs[0].shape == (4,)
            c.close()
        finally:
            srv.stop()
            repo.stop()
            eng.stop()

    def test_resume_refusals_are_typed(self):
        eng = ContinuousBatcher(**ENGINE_CFG)
        srv = DecodeServer(eng, port=0).start()
        repo = TensorRepoServer(port=0).start()
        try:
            c = RawClient(srv.port)
            # nothing published in the slot: typed refusal, bounded wait
            ctl_frame = pack_session_control(
                f"127.0.0.1:{repo.port}", 9, 300)
            with pytest.raises(QueryMigratingError):
                c.request(ctl_frame, pts=RESUME_PTS)
            # a connection already holding a session refuses a resume
            c.request((np.zeros(4, np.float32),))
            with pytest.raises(QueryMigratingError):
                c.request(ctl_frame, pts=RESUME_PTS)
            c.close()
        finally:
            srv.stop()
            repo.stop()
            eng.stop()


# -- router-coordinated handoff ---------------------------------------------


class _MigFleet:
    """Two in-process decode workers + repo + stateful migrating router."""

    def __init__(self, n=2, migrate=True, router_kwargs=None):
        self.repo_srv = TensorRepoServer(port=0).start()
        self.membership = Membership(heartbeat_s=30.0, suspect_misses=2,
                                     death_misses=4, breaker_failures=2,
                                     breaker_reset_s=0.2)
        self.workers = []
        for i in range(n):
            w = FleetWorker(name=f"m{i}", engine=dict(ENGINE_CFG)).start()
            self.workers.append(w)
            self.membership.add("127.0.0.1", w.decode_port, probe=w.probe,
                                worker_id=w.name)
        self.membership.sweep()
        rk = dict(request_timeout=15.0, connect_timeout=5.0,
                  migrate_check_s=0.05, drain_deadline_s=3.0)
        rk.update(router_kwargs or {})
        self.router = Router(
            self.membership, port=0, stateful=True,
            repo_addr=f"127.0.0.1:{self.repo_srv.port}",
            migrate=migrate, **rk).start()

    def worker(self, name):
        return next(w for w in self.workers if w.name == name)

    def pinned(self):
        return next(w.name for w in self.workers
                    if self.router.session_count(w.name))

    def close(self):
        self.router.stop()
        self.membership.stop()
        self.repo_srv.stop()
        for w in self.workers:
            try:
                w.stop()
            except Exception:  # noqa: BLE001
                pass


@pytest.fixture
def mig_fleet():
    f = _MigFleet()
    yield f
    f.close()


class TestRouterHandoff:
    def _stream(self, client, prompt, steps):
        out = [np.asarray(client.request((prompt,))[0][0])]
        for s in steps:
            out.append(np.asarray(client.request((s,))[0][0]))
        return out

    def test_drain_migrates_token_identical_ledger_exact(self, mig_fleet):
        """ISSUE 12 acceptance: a drain of the session-hosting worker
        migrates every live session; each completes on its new worker
        token-identical to an unmigrated control run; the session ledger
        stays exact; the obs counters record the handoff."""
        f = mig_fleet
        from nnstreamer_tpu.obs.export import render_text

        prompt, steps = _prompt(seed=13), _steps(6, base=130)
        ctl = _control_run(prompt, steps)
        c1 = RawClient(f.router.port)
        c2 = RawClient(f.router.port)
        out1 = self._stream(c1, prompt, steps[:3])
        out2 = self._stream(c2, prompt, steps[:3])
        victim = f.pinned()
        # both sessions round-robined onto DIFFERENT workers; drain the
        # one hosting c1's session (or both if colocated — still exact)
        broken = f.router.drain_worker(victim, deadline_s=5.0)
        assert broken == 0, "a migrating drain must not force-break"
        for s in steps[3:]:
            out1.append(np.asarray(c1.request((s,))[0][0]))
            out2.append(np.asarray(c2.request((s,))[0][0]))
        for x, y1, y2 in zip(ctl, out1, out2):
            np.testing.assert_array_equal(x, y1)
            np.testing.assert_array_equal(x, y2)
        st = f.router.stats()
        assert st["sessions_migrated"] >= 1
        assert st["sessions_broken"] == 0
        assert st["session_ledger_exact"], st
        # nothing lives on the drained worker anymore
        assert f.router.session_count(victim) == 0
        assert f.worker(victim).engine.stats()["active_sessions"] == 0
        after = render_text()
        assert 'nnstpu_session_migrations_total{result="ok"}' in after
        assert "nnstpu_session_migration_seconds" in after
        c1.close()
        c2.close()

    def test_self_draining_worker_auto_migrates(self, mig_fleet):
        """The rolling-restart path: the WORKER announces its drain
        (SIGTERM analog); membership maps it to DRAINING and the
        router's monitor moves the sessions off — the worker-side drain
        then completes clean, the client never sees an error."""
        f = mig_fleet
        prompt, steps = _prompt(seed=17), _steps(5, base=170)
        ctl = _control_run(prompt, steps)
        c = RawClient(f.router.port)
        out = self._stream(c, prompt, steps[:2])
        victim = f.pinned()
        w = f.worker(victim)
        done = {}

        def drain():
            done["clean"] = w.drain(timeout=8.0)

        t = threading.Thread(target=drain)
        t.start()
        assert _wait_for(lambda: w.probe() == "draining", 5)
        f.membership.sweep()
        assert f.membership.get(victim).state == DRAINING
        # the monitor (migrate_check_s=0.05) picks it up
        assert _wait_for(
            lambda: f.router.sessions_migrated >= 1
            and f.router.session_count(victim) == 0, 10), \
            f.router.stats()
        for s in steps[2:]:
            out.append(np.asarray(c.request((s,))[0][0]))
        t.join(timeout=15)
        assert done.get("clean") is True, "drain should finish clean"
        for x, y in zip(ctl, out):
            np.testing.assert_array_equal(x, y)
        assert f.router.sessions_broken == 0
        c.close()

    def test_migrate_abort_degrades_typed_session_slot_freed(self):
        """An injected ``migrate_abort`` at the restore phase lands
        AFTER the point of no return: the client gets today's typed
        [SESSION] (never a hang, never a duplicate step), the source
        slot is freed, the ledger stays exact, and the abort is
        visible in stats."""
        f = _MigFleet()
        try:
            faults.install("migrate_abort@restore:every=1", seed=3)
            prompt, steps = _prompt(seed=19), _steps(3, base=190)
            c = RawClient(f.router.port)
            self._stream(c, prompt, steps[:1])
            victim = f.pinned()
            t0 = time.monotonic()
            broken = f.router.drain_worker(victim, deadline_s=4.0)
            assert time.monotonic() - t0 < 4.0, "abort must not hang"
            assert broken == 0  # broken during the handoff, not after
            with pytest.raises(QuerySessionBrokenError):
                c.request((steps[1],))
            st = f.router.stats()
            assert st["sessions_migrated"] == 0
            assert st["sessions_broken"] == 1
            assert st["migration_aborts"].get("restore", 0) >= 1
            assert f.worker(victim).engine.stats()["active_sessions"] == 0
            eng = faults.engine()
            assert eng.injections.get("migrate_abort", 0) >= 1
            c.close()
            # a fresh session immediately works on the survivor
            c2 = RawClient(f.router.port)
            outs, _ = c2.request((np.zeros(4, np.float32),))
            assert outs[0].shape == (4,)
            c2.close()
            st = f.router.stats()
            assert st["session_ledger_exact"] or \
                st["sessions_active"] >= 1  # c2 still open
        finally:
            faults.deactivate()
            f.close()

    def test_target_death_mid_handoff(self, mig_fleet):
        """The restore leg dials a corpse: typed [SESSION] to the
        client, source slot freed, no hang."""
        f = mig_fleet
        prompt, steps = _prompt(seed=23), _steps(2, base=230)
        c = RawClient(f.router.port)
        self._stream(c, prompt, steps[:1])
        victim = f.pinned()
        other = next(w for w in f.workers if w.name != victim)
        other.kill()  # membership hasn't noticed: pick() still returns it
        t0 = time.monotonic()
        f.router.drain_worker(victim, deadline_s=3.0)
        assert time.monotonic() - t0 < 10.0
        with pytest.raises(QuerySessionBrokenError):
            c.request((steps[1],))
        assert f.router.sessions_migrated == 0
        assert f.router.sessions_broken == 1
        assert f.worker(victim).engine.stats()["active_sessions"] == 0
        c.close()

    def test_handoff_onto_a_worker_drained_meanwhile_is_moved_on(self):
        """Two drains at once (a fleet scaling 3 → 1): the first one's
        handoff has picked the second victim as its target when that
        victim's own drain starts.  The session must end on the survivor,
        token-identical, not stranded on (or broken with) a drained
        worker."""
        f = _MigFleet(n=3)
        try:
            prompt, steps = _prompt(seed=29), _steps(4, base=290)
            ctl = _control_run(prompt, steps)
            c = RawClient(f.router.port)
            out = self._stream(c, prompt, steps[:2])
            first = f.pinned()
            second, survivor = (w.name for w in f.workers
                                if w.name != first)
            real_pick = f.membership.pick
            second_drain = []

            def pick_then_drain_the_target(exclude=()):
                if not second_drain:  # the handoff's own pick
                    t = threading.Thread(
                        target=lambda: second_drain.append(
                            f.router.drain_worker(second, deadline_s=5.0)))
                    second_drain.append(t)
                    t.start()
                    time.sleep(0.2)  # the other drain is under way
                    return f.membership.get(second)
                return real_pick(exclude)

            f.membership.pick = pick_then_drain_the_target
            assert f.router.drain_worker(first, deadline_s=5.0) == 0
            second_drain[0].join(timeout=15)
            assert second_drain[1:] == [0], "second drain broke a session"
            for s in steps[2:]:
                out.append(np.asarray(c.request((s,))[0][0]))
            for x, y in zip(ctl, out):
                np.testing.assert_array_equal(x, y)
            assert f.router.session_count(survivor) == 1
            assert f.router.session_count(first) == 0
            assert f.router.session_count(second) == 0
            st = f.router.stats()
            assert st["sessions_broken"] == 0
            assert st["sessions_migrated"] == 2
            assert st["session_ledger_exact"], st
            c.close()
        finally:
            f.close()

    def test_old_worker_falls_back_to_typed_session(self):
        """Version gate end to end: workers whose DecodeServer predates
        the migration ops answer the control frame with a plain error —
        the router falls back to the legacy drain (wait, then [SESSION])
        and never corrupts anything."""
        f = _MigFleet(router_kwargs=dict(drain_deadline_s=0.5))
        try:
            for w in f.workers:
                w.decode_server.migration = False  # old-peer emulation
            prompt, steps = _prompt(seed=29), _steps(2, base=290)
            c = RawClient(f.router.port)
            self._stream(c, prompt, steps[:1])
            victim = f.pinned()
            broken = f.router.drain_worker(victim, deadline_s=0.5)
            assert broken == 1  # the legacy force-break path
            with pytest.raises(QuerySessionBrokenError):
                c.request((steps[1],))
            st = f.router.stats()
            assert st["sessions_migrated"] == 0
            assert st["migration_aborts"], "fallback must be visible"
            c.close()
        finally:
            f.close()

    def test_migration_disabled_keeps_legacy_drain(self):
        f = _MigFleet(migrate=False,
                      router_kwargs=dict(drain_deadline_s=0.3))
        try:
            prompt = _prompt(seed=31)
            c = RawClient(f.router.port)
            c.request((prompt,))
            victim = f.pinned()
            broken = f.router.drain_worker(victim)
            assert broken == 1
            assert f.router.sessions_migrated == 0
            c.close()
        finally:
            f.close()


# -- migration observability --------------------------------------------------


class TestMigrationObservability:
    def test_handoff_spans_render_phases(self, mig_fleet):
        from nnstreamer_tpu.obs import spans

        f = mig_fleet
        spans.enable()
        try:
            prompt = _prompt(seed=37)
            c = RawClient(f.router.port)
            c.request((prompt,))
            victim = f.pinned()
            assert f.router.drain_worker(victim, deadline_s=5.0) == 0
            c.close()
            names = [r[4] for r in spans.snapshot()]
            assert "session_migrate" in names
            for phase in ("migrate_quiesce", "migrate_snapshot",
                          "migrate_restore", "migrate_resume"):
                assert phase in names, (phase, names)
            # worker-side op spans joined the same handoff trace
            mig = [r for r in spans.snapshot()
                   if r[4] == "session_migrate"]
            assert mig and mig[0][9]["result"] == "ok"
        finally:
            spans.reset()

    def test_engine_stats_surface_slots(self, engines):
        a, _ = engines
        sess = a.open_session()
        sess.prefill(_prompt())
        sess.get(timeout=10)
        st = a.stats()
        slot = st["slots"][sess.slot]
        assert slot["occupied"] and slot["pos"] == 3
        sess.close()


# -- hardened remote repo -----------------------------------------------------


class TestRepoHardening:
    def test_idempotent_ops_retry_through_drops(self):
        """Injected socket drops on the repo wire: idempotent ops
        reconnect and retry transparently; the fault log proves the
        drops actually fired."""
        from nnstreamer_tpu.fleet.repo import RemoteTensorRepo

        with TensorRepoServer(port=0) as srv:
            repo = RemoteTensorRepo("127.0.0.1", srv.port)
            try:
                # every=3 lands drops on requests AND replies across the
                # run (every=2 would deterministically kill every retry)
                faults.install("socket_drop@repo:every=3", seed=5)
                for _ in range(6):
                    repo.prepare(3)   # idempotent: survives the drops
                    repo.clear(3)
                assert faults.engine().injections.get("socket_drop", 0) >= 2
                assert repo.retries_total >= 1
            finally:
                faults.deactivate()
                repo.close()

    def test_non_idempotent_ops_fail_typed(self):
        from nnstreamer_tpu.buffer import Frame
        from nnstreamer_tpu.fleet.repo import (
            RemoteRepoError,
            RemoteTensorRepo,
        )

        # a refused dial: non-idempotent ops fail typed IMMEDIATELY (no
        # blind retry that could double-publish), idempotent ops exhaust
        # their budget and then fail typed too
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        dead_port = probe.getsockname()[1]
        probe.close()
        repo = RemoteTensorRepo("127.0.0.1", dead_port,
                                retry_backoff_s=0.01)
        with pytest.raises(RemoteRepoError):
            repo.set_buffer(1, Frame.of(np.zeros(4, np.float32), pts=0))
        with pytest.raises(RemoteRepoError):
            repo.prepare(1)
        repo.close()

    def test_close_closes_cached_sockets_no_redial(self):
        from nnstreamer_tpu.fleet.repo import (
            RemoteRepoError,
            RemoteTensorRepo,
        )

        with TensorRepoServer(port=0) as srv:
            repo = RemoteTensorRepo("127.0.0.1", srv.port)
            seen = []

            def worker():
                repo.prepare(7)
                seen.append(getattr(repo._tls, "sock", None))

            ths = [threading.Thread(target=worker) for _ in range(4)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            assert len(repo._socks) == 4  # one cached socket per thread
            repo.close()
            assert repo._socks == []
            for s in seen:
                assert s is not None and s.fileno() == -1  # really closed
            # a use-after-close is typed, and never re-dials (fd leak)
            with pytest.raises(RemoteRepoError):
                repo.prepare(7)

    def test_reset_keeps_socket_list_bounded(self):
        """Churny transport failures must not accumulate dead sockets in
        the close() list across a soak."""
        from nnstreamer_tpu.fleet.repo import RemoteTensorRepo

        with TensorRepoServer(port=0) as srv:
            repo = RemoteTensorRepo("127.0.0.1", srv.port)
            try:
                faults.install("socket_drop@repo:every=1", seed=7)
                for _ in range(6):
                    try:
                        repo.set_eos(2)
                    except ConnectionError:
                        pass
                assert len(repo._socks) <= 1, \
                    "dead sockets must leave the tracked list"
            finally:
                faults.deactivate()
                repo.close()
