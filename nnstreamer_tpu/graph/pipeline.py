"""Pipeline: node container, spec negotiation, and the streaming scheduler.

The analog of a GStreamer pipeline bin + its state machine, rebuilt as an
explicit graph object:

- :meth:`Pipeline.add` / :meth:`Pipeline.link` build the graph.
- :meth:`Pipeline.start` opens resources, runs **topological two-phase spec
  negotiation** (the analog of PAUSED-state caps negotiation,
  ``tensor_filter.c:666-839``), then spawns one streaming thread per source
  (GStreamer gives every source its own task thread, ``README.md:41-44``).
- EOS from every leaf marks completion; :meth:`Pipeline.wait` blocks on it.
- An exception in any node's chain posts an error and halts the graph
  (``GST_ELEMENT_ERROR`` semantics, ``tensor_filter.c:413-435``).

Cycles are allowed in the *link* graph only through repo slots
(reposrc/reposink pairs share a slot out-of-band, §3.4 of the survey), so
the negotiation pass always sees a DAG.
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Dict, List, Optional, Union

from ..buffer import Event, Frame
from ..obs import hooks as _hooks
from .node import NegotiationError, Node, Pad, SourceNode


class PipelineError(Exception):
    pass


RESTART_MODES = ("restart", "quarantine-passthrough", "fail-pipeline")


class RestartPolicy:
    """Per-node supervision policy (the GStreamer world has no analog —
    an element error is always fatal there; a streaming system that must
    play through flaky sources needs supervision, Erlang-style):

    - ``restart``: stop()+start() the faulting node, drop the offending
      frame, and keep streaming — with capped exponential backoff and a
      restart-storm budget (``max_restarts`` within ``window_s``; the
      budget exhausting escalates to pipeline failure).
    - ``quarantine-passthrough``: sideline the node — subsequent frames
      bypass its ``process()`` (passing through unchanged when the
      in/out specs line up, shed otherwise, both counted).
    - ``fail-pipeline``: the legacy terminal behavior (default).
    """

    __slots__ = ("mode", "max_restarts", "window_s", "backoff_ms",
                 "backoff_cap_ms")

    def __init__(self, mode: str = "restart", max_restarts: int = 5,
                 window_s: float = 30.0, backoff_ms: float = 50.0,
                 backoff_cap_ms: float = 2000.0):
        if mode not in RESTART_MODES:
            raise ValueError(
                f"unknown restart policy {mode!r} (known: {RESTART_MODES})")
        self.mode = mode
        self.max_restarts = int(max_restarts)
        self.window_s = float(window_s)
        self.backoff_ms = float(backoff_ms)
        self.backoff_cap_ms = float(backoff_cap_ms)

    @classmethod
    def from_conf(cls) -> Optional["RestartPolicy"]:
        """The conf'd default policy (``[recovery] policy`` /
        ``NNSTPU_RECOVERY_POLICY``); None means fail-pipeline."""
        from ..conf import conf

        mode = (conf.get("recovery", "policy", "") or "").strip()
        if not mode or mode == "fail-pipeline":
            return None
        return cls(
            mode,
            max_restarts=conf.get_int("recovery", "max_restarts", 5),
            window_s=conf.get_float("recovery", "window_s", 30.0),
            backoff_ms=conf.get_float("recovery", "backoff_ms", 50.0),
            backoff_cap_ms=conf.get_float("recovery", "backoff_cap_ms",
                                          2000.0),
        )


class Pipeline:
    def __init__(self, name: str = "pipeline"):
        self.name = name
        self.nodes: Dict[str, Node] = {}
        self.auto_fuse = True  # fold transforms into XLA filters on start
        # whole-segment compilation (graph/segments.py): None defers to
        # [segment] enabled; True/False pins it for this pipeline
        self.segment_compile: Optional[bool] = None
        self._segment_undos: List = []
        self.state = "NULL"  # NULL → PLAYING → STOPPED
        self.threads: List[threading.Thread] = []
        self._eos_leaves: set = set()
        self._leaves: set = set()
        self._done = threading.Event()
        self._error: Optional[BaseException] = None
        self._error_node: Optional[str] = None
        self._lock = threading.Lock()
        self._xplane_tracing = False
        self._tracers: List = []  # attached obs tracers (GST_TRACERS analog)
        # the span and device lanes this run started by itself (a hook-bus
        # listener at start(), or profiling on); stopped with the run and
        # never part of stats()
        self._lanes_started: List = []
        # supervised recovery (restart policies + watchdog escalation)
        self._restart_policies: Dict[str, RestartPolicy] = {}
        self._conf_policy: Optional[RestartPolicy] = None
        self._recovery_lock = threading.Lock()
        self._restart_log: Dict[str, List[float]] = {}   # node -> timestamps
        self._recovery_counts: Dict[str, int] = {}       # action -> count
        self._shed_frames: Dict[str, int] = {}           # node -> frames shed
        # compile-ahead warmup (graph/warmup.py): report of the last run
        self.warmup_report: Optional[dict] = None
        # dispatcher-lane runtime (graph/lanes.py); None = the legacy
        # thread-per-element scheduler ([dispatch] lanes = 0)
        self._lanes = None

    # -- graph construction -------------------------------------------------

    def add(self, *nodes: Node) -> Union[Node, tuple]:
        for node in nodes:
            if node.name in self.nodes:
                raise ValueError(f"duplicate node name {node.name!r}")
            self.nodes[node.name] = node
            node.pipeline = self
        return nodes[0] if len(nodes) == 1 else nodes

    def __getitem__(self, name: str) -> Node:
        return self.nodes[name]

    def get_by_name(self, name: str) -> Node:
        """Named-element lookup (``gst_bin_get_by_name`` analog)."""
        return self.nodes[name]

    def _resolve(self, ref: Union[Node, str]) -> (Node, Optional[str]):
        """Resolve 'node' or 'node.pad' references."""
        if isinstance(ref, Node):
            return ref, None
        if "." in ref:
            node_name, _, pad_name = ref.partition(".")
            return self.nodes[node_name], pad_name
        return self.nodes[ref], None

    def link(self, src: Union[Node, str], dst: Union[Node, str]) -> None:
        """Link src's src pad to dst's sink pad; 'name.pad' selects pads."""
        src_node, src_pad = self._resolve(src)
        dst_node, dst_pad = self._resolve(dst)
        src_node.get_src_pad(src_pad).link(dst_node.get_sink_pad(dst_pad))

    def link_chain(self, *nodes: Union[Node, str]) -> None:
        for a, b in zip(nodes, nodes[1:]):
            self.link(a, b)

    # -- supervised recovery ------------------------------------------------

    def set_restart_policy(self, node: Union[Node, str] = "*",
                           mode: str = "restart",
                           max_restarts: int = 5, window_s: float = 30.0,
                           backoff_ms: float = 50.0,
                           backoff_cap_ms: float = 2000.0) -> RestartPolicy:
        """Install a supervision policy for one node (``"*"`` = every
        node without a specific one).  See :class:`RestartPolicy`."""
        name = node.name if isinstance(node, Node) else str(node)
        pol = RestartPolicy(mode, max_restarts=max_restarts,
                            window_s=window_s, backoff_ms=backoff_ms,
                            backoff_cap_ms=backoff_cap_ms)
        self._restart_policies[name] = pol
        return pol

    def restart_policy_for(self, name: str) -> Optional[RestartPolicy]:
        """Node-specific policy, else the ``"*"`` default, else the
        conf'd ``[recovery] policy`` (resolved at start); None means
        fail-pipeline."""
        pol = self._restart_policies.get(name)
        if pol is None:
            pol = self._restart_policies.get("*")
        return pol if pol is not None else self._conf_policy

    def _bump(self, action: str) -> None:
        with self._recovery_lock:
            self._recovery_counts[action] = \
                self._recovery_counts.get(action, 0) + 1

    def _count_shed_frame(self, node: Node) -> None:
        """One frame shed by recovery (restart drop / quarantine shed /
        queue drain) — the typed-loss side of the frame-accounting
        ledger the chaos soak balances."""
        with self._recovery_lock:
            self._shed_frames[node.name] = \
                self._shed_frames.get(node.name, 0) + 1

    @staticmethod
    def _specs_passthrough(node: Node) -> bool:
        """Quarantine passthrough is only sound when the frames this node
        would have produced have the same spec as the ones it receives."""
        sinks = [p.spec for p in node.sink_pads.values() if p.peer is not None]
        srcs = [p.spec for p in node.src_pads.values() if p.peer is not None]
        return (len(sinks) == 1 and bool(srcs)
                and all(s == sinks[0] for s in srcs))

    def _restart_budget_ok(self, node: Node,
                           pol: RestartPolicy) -> Optional[int]:
        """Charge one restart against the node's storm budget; returns the
        restart ordinal (for backoff) or None when the budget is spent."""
        now = time.monotonic()
        with self._recovery_lock:
            log = self._restart_log.setdefault(node.name, [])
            log[:] = [t for t in log if now - t <= pol.window_s]
            if len(log) >= pol.max_restarts:
                return None
            log.append(now)
            return len(log)

    def _attempt_restart(self, node: Node, exc: BaseException,
                         pol: RestartPolicy, action: str) -> bool:
        from ..obs import recovery as _recovery

        n = self._restart_budget_ok(node, pol)
        if n is None:
            # restart storm: stop resuscitating, escalate to pipeline
            # failure (the caller falls through to post_error)
            _recovery.record(self.name, action, "storm", node.name,
                             repr(exc))
            return False
        backoff_s = min(pol.backoff_cap_ms,
                        pol.backoff_ms * (2 ** (n - 1))) / 1e3
        if backoff_s > 0:
            time.sleep(backoff_s)
        try:
            node.stop()
            node.start()
            # restore negotiated state: re-run the commit phase against
            # the current pad specs — a fresh-started filter must
            # re-install its fused wrapper and recompile for the stream
            # it is actually on, not rediscover it from raw frames
            # (fusion folds pre-transforms INTO the filter, so the raw
            # spec alone would mis-reconcile)
            in_specs = {p.name: p.spec for p in node.sink_pads.values()
                        if p.peer is not None and p.spec is not None}
            if in_specs:
                node.configure(in_specs)
        except Exception as rexc:  # noqa: BLE001 — restart itself failed
            _recovery.record(self.name, action, "error", node.name,
                             repr(rexc))
            return False
        self._bump(action)
        _recovery.record(self.name, action, "ok", node.name, repr(exc))
        return True

    def _node_fault(self, node: Node, exc: BaseException) -> bool:
        """A node's ``process()`` raised: consult its restart policy.
        True = handled (frame dropped, node restarted or quarantined);
        False = propagate to ``post_error`` as before."""
        if self.state != "PLAYING":
            return False
        pol = self.restart_policy_for(node.name)
        if pol is None or pol.mode == "fail-pipeline":
            return False
        from ..obs import recovery as _recovery

        if pol.mode == "quarantine-passthrough":
            node._quarantine_passthrough = self._specs_passthrough(node)
            node._quarantined = True
            self._bump("quarantine")
            self._count_shed_frame(node)  # the offending frame is shed
            _recovery.record(self.name, "quarantine", "ok", node.name,
                             repr(exc))
            return True
        if not self._attempt_restart(node, exc, pol, "restart_node"):
            return False
        self._count_shed_frame(node)
        return True

    def _source_fault(self, node: SourceNode, exc: BaseException) -> bool:
        """A source's ``frames()`` raised: only ``restart`` applies (a
        quarantined source is just a dead stream).  Restarting re-enters
        ``frames()`` from scratch — right for live sources; a finite data
        source replays (document, don't surprise)."""
        pol = self.restart_policy_for(node.name)
        if pol is None or pol.mode != "restart":
            return False
        return self._attempt_restart(node, exc, pol, "restart_source")

    def restart_source(self, name: str) -> bool:
        """Watchdog escalation: replace a stalled source's streaming
        thread.  The stuck thread is joined briefly, then abandoned with
        a bumped epoch (it exits on unblock instead of double-pushing);
        the source restarts and streams on a fresh thread."""
        from ..obs import recovery as _recovery

        node = self.nodes.get(name)
        if not isinstance(node, SourceNode) or self.state != "PLAYING":
            return False
        node._epoch += 1
        node.request_stop()
        interrupt = getattr(node, "interrupt", None)
        if interrupt is not None:
            try:
                interrupt()
            except Exception:  # noqa: BLE001
                pass
        for t in [t for t in self.threads if t.name == f"src:{name}"]:
            t.join(timeout=2.0)
            self.threads.remove(t)
        if self._lanes is not None:
            # lane analog of the join above: wait out the stale task's
            # executor before re-arming the stop event below
            self._lanes.retire_source(name)
        node._stop_evt.clear()
        try:
            node.stop()
            node.start()
        except Exception as exc:  # noqa: BLE001
            _recovery.record(self.name, "restart_source", "error", name,
                             repr(exc))
            return False
        self._bump("restart_source")
        if _hooks.enabled:
            _hooks.emit("source_spawn", self, node)
        if self._lanes is not None:
            # lane mode: the stale task exits on the bumped epoch; a
            # fresh pull task takes over (graph/lanes.py)
            self._lanes.respawn_source(node)
        else:
            t = threading.Thread(
                target=self._source_loop, args=(node,), name=f"src:{name}",
                daemon=True,
            )
            self.threads.append(t)
            t.start()
        _recovery.record(self.name, "restart_source", "ok", name)
        return True

    def source_alive(self, name: str) -> bool:
        """Is the source's execution vehicle still live — its streaming
        thread (thread mode) or its lane task / promoted helper (lane
        mode)?  The watchdog keys stalled-source detection on this."""
        if self._lanes is not None:
            return self._lanes.source_alive(name)
        return any(t.name == f"src:{name}" and t.is_alive()
                   for t in self.threads)

    def recover_queue(self, name: str) -> int:
        """Watchdog escalation: drain a wedged queue (shed its backlog
        with typed accounting, preserving in-band events) and respawn its
        worker if the thread died.  Returns frames drained, -1 when the
        node cannot recover."""
        from ..obs import recovery as _recovery

        node = self.nodes.get(name)
        rec = getattr(node, "recover", None)
        if rec is None:
            _recovery.record(self.name, "drain_queue", "error", name,
                             "node has no recover()")
            return -1
        try:
            drained, new_threads = rec()
        except Exception as exc:  # noqa: BLE001
            _recovery.record(self.name, "drain_queue", "error", name,
                             repr(exc))
            return -1
        for t in new_threads:
            t.daemon = True
            self.threads.append(t)
            t.start()
        with self._recovery_lock:
            if drained:
                self._shed_frames[name] = \
                    self._shed_frames.get(name, 0) + drained
        self._bump("drain_queue")
        _recovery.record(self.name, "drain_queue", "ok", name,
                         f"drained={drained}")
        return drained

    def recovery_stats(self) -> dict:
        """Self-healing ledger: actions taken, frames shed per node (the
        typed-loss side of delivered + shed == offered), quarantined
        nodes."""
        with self._recovery_lock:
            out: dict = {}
            if self._recovery_counts:
                out["actions"] = dict(self._recovery_counts)
            if self._shed_frames:
                out["shed_frames"] = dict(self._shed_frames)
                out["shed_total"] = sum(self._shed_frames.values())
        quarantined = [n.name for n in self.nodes.values() if n._quarantined]
        if quarantined:
            out["quarantined"] = quarantined
        return out

    # -- negotiation --------------------------------------------------------

    def negotiate(self) -> None:
        """Topological two-phase spec negotiation over the whole graph."""
        pending = set(self.nodes.values())
        configured: set = set()

        def linked_sinks(node: Node) -> List[Pad]:
            return [p for p in node.sink_pads.values() if p.peer is not None]

        progress = True
        while pending and progress:
            progress = False
            for node in list(pending):
                sinks = linked_sinks(node)
                if any(p.spec is None for p in sinks):
                    continue
                in_specs = {}
                for pad in sinks:
                    template = node.sink_spec(pad.name)
                    merged = template.intersect(pad.spec)
                    if merged is None:
                        raise NegotiationError(
                            f"{pad.full_name}: upstream spec {pad.spec} not accepted "
                            f"(template {template})"
                        )
                    in_specs[pad.name] = merged
                out_specs = node.configure(in_specs)
                for pad_name, pad in node.src_pads.items():
                    if pad.peer is None:
                        continue
                    spec = out_specs.get(pad_name)
                    if spec is None:
                        raise NegotiationError(
                            f"{node.name}: configure() returned no spec for linked "
                            f"src pad {pad_name!r}"
                        )
                    pad.spec = spec
                    pad.peer.spec = spec
                pending.discard(node)
                configured.add(node)
                progress = True
        if pending:
            names = ", ".join(sorted(n.name for n in pending))
            raise NegotiationError(
                f"negotiation stalled (cycle or dangling inputs): {names}"
            )

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "Pipeline":
        if self.state == "PLAYING":
            return self
        self._done.clear()
        self._error = None
        self._eos_leaves.clear()
        self._conf_policy = RestartPolicy.from_conf()
        with self._recovery_lock:
            self._restart_log.clear()
            self._recovery_counts.clear()
            self._shed_frames.clear()
        for node in self.nodes.values():
            node._quarantined = False
            node._quarantine_passthrough = False
        # conf-driven chaos activation (NNSTPU_FAULTS), same posture as
        # the tracers below: a bad spec must fail loudly at start, not
        # silently run without its faults
        from ..faults import ensure_configured as _faults_configure

        _faults_configure()
        fuse_undos = []
        if self.auto_fuse:
            from .optimize import fuse_transforms

            fuse_undos = fuse_transforms(self)
            # whole-segment compilation ([segment] enabled or
            # pipeline.segment_compile): fold converter pre-ops and
            # decoder device heads into the filter program too.  Undos
            # ride on self._segment_undos (stop() restores the user's
            # graph for renegotiation); the failure path below runs them
            # via restore_segments so they never fire twice.
            from .segments import fuse_segments

            fuse_segments(self)
        for node in self.nodes.values():
            for pad in list(node.sink_pads.values()) + list(node.src_pads.values()):
                pad.eos = False
                pad.sig = None
        started = []
        try:
            # leaves depend only on link topology (known before caps), so
            # they are computed up front: tracers need them at install
            self._leaves = {
                n.name
                for n in self.nodes.values()
                if not any(p.peer is not None for p in n.src_pads.values())
            }
            if not self._leaves:
                raise PipelineError("pipeline has no leaf (sink) nodes")
            # tracers/metrics attach BEFORE negotiation: an element whose
            # configure() talks to a remote peer (tensor_query_client's
            # probe) must see span tracing active to negotiate trace
            # propagation on the wire.  Failures stay warnings — same
            # contract as _post_negotiate_hooks.
            try:
                self._attach_observability()
            except Exception as exc:  # noqa: BLE001
                import warnings

                warnings.warn(f"observability hooks failed: {exc!r}",
                              stacklevel=2)
            for node in self.nodes.values():
                node.start()
                started.append(node)
            # every compile before PLAYING is warmup-phase: negotiation
            # compiles and the explicit warmup walk both land on the
            # "warmup" Perfetto track and the phase="warmup" series of
            # nnstpu_compile_seconds — never inside the first frame's
            # trace (obs/device.py set_compile_phase)
            from ..obs.device import set_compile_phase
            from .warmup import run_warmup

            set_compile_phase("warmup")
            try:
                self.negotiate()
                # compile-ahead: AOT-compile every negotiated (spec,
                # bucket) geometry — dynbatch ladders, mesh buckets —
                # before PLAYING ([compile] warmup / NNSTPU_COMPILE_WARMUP)
                run_warmup(self)
            finally:
                set_compile_phase(None)
        except BaseException:
            for node in started:
                try:
                    node.stop()
                except Exception:
                    pass
            self._stop_tracers()  # failed start: no hook may stay connected
            from .segments import restore_segments

            restore_segments(self)
            for undo in reversed(fuse_undos):
                undo()
            raise
        self.state = "PLAYING"
        self._post_negotiate_hooks()
        if _hooks.enabled:
            _hooks.emit("state_change", self, "NULL", "PLAYING")
        # Scheduling substrate: with [dispatch] lanes > 0, queue drains
        # and source pulls become lane tasks (graph/lanes.py); lanes=0
        # keeps the legacy thread-per-element spawn below byte-for-byte.
        from . import lanes as _lanes

        nlanes = _lanes.configured_lanes()
        if nlanes > 0:
            self._lanes = _lanes.LaneRuntime(self, nlanes)
            self._lanes.start()
        # Spawn worker threads requested by nodes (queues), then sources.
        for node in self.nodes.values():
            if self._lanes is not None \
                    and getattr(node, "lane_task", None) is not None:
                self._lanes.add_element(node)
                continue
            spawn = getattr(node, "spawn_threads", None)
            if spawn is not None:
                for t in spawn():
                    t.daemon = True
                    self.threads.append(t)
                    t.start()
        # Sources start last-added first.  Of the sources that feed one
        # collect element the last to start completes the first round, and
        # the thread that completes a round carries it to the sinks, so it
        # is back first and completes the next one as well: started in this
        # order, the carrier of every round is the source on the element's
        # first pad.  A demux hands that stream its answer first, so every
        # other stream has its answer, and sends its next frame, after the
        # carrier's (in the other order the first pad's stream sends its
        # next frame within microseconds of the round's last answer, before
        # it in one run and after it in the next).
        for node in reversed(list(self.nodes.values())):
            if isinstance(node, SourceNode):
                if _hooks.enabled:
                    _hooks.emit("source_spawn", self, node)
                if self._lanes is not None:
                    self._lanes.add_source(node)
                    continue
                t = threading.Thread(
                    target=self._source_loop, args=(node,), name=f"src:{node.name}",
                    daemon=True,
                )
                self.threads.append(t)
                t.start()
        return self

    def _source_loop(self, node: SourceNode) -> None:
        epoch = node._epoch
        while True:
            try:
                for frame in node.frames():
                    if (node.stopped or node._epoch != epoch
                            or self.state != "PLAYING"):
                        break
                    if _hooks.enabled:
                        # pre-chain: the latency tracer stamps frame
                        # identity here, before the first pad push
                        _hooks.emit("source_push", self, node, frame)
                    node.push(frame)
                if node._epoch != epoch:
                    return  # superseded by restart_source: not our EOS
                for pad in node.src_pads.values():
                    pad.push(Event.eos())
                return
            except BaseException as exc:  # noqa: BLE001 - any node failure
                if node._epoch != epoch:
                    return  # a replacement thread owns this source now
                if (self.state == "PLAYING" and not node.stopped
                        and self._source_fault(node, exc)):
                    continue  # restarted: re-enter frames() fresh
                self.post_error(node, exc)
                return

    def post_error(self, node: Node, exc: BaseException) -> None:
        with self._lock:
            first = self._error is None
            if first:
                self._error = exc
                self._error_node = node.name if node else None
        if first and self.state == "PLAYING":
            # flip to ERROR so every source loop (they poll the state per
            # frame) stops feeding a dead graph; stop() still runs the
            # full STOPPED teardown from here (threads joined, nodes
            # stopped, tracers detached)
            self.state = "ERROR"
            if _hooks.enabled:
                _hooks.emit("state_change", self, "PLAYING", "ERROR")
        if _hooks.enabled:
            _hooks.emit("error", self, node, exc)
        traceback.print_exception(type(exc), exc, exc.__traceback__)
        if first:
            # crash forensics: the graph as it died (GST_DEBUG_DUMP_DOT_DIR
            # writes an error dot the same way) + the span flight recorder
            self._dump_dot("ERROR")
            self._dump_flight("error")
        self._done.set()

    def _node_eos(self, node: Node) -> None:
        """Called by a node whose every sink pad saw EOS and which has no
        linked src pads (a leaf)."""
        if any(p.peer is not None for p in node.src_pads.values()):
            return
        with self._lock:
            self._eos_leaves.add(node.name)
            if self._leaves and self._eos_leaves >= self._leaves:
                self._done.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until EOS on all leaves (or error).  Returns True on EOS,
        raises on error, False on timeout."""
        finished = self._done.wait(timeout)
        if self._error is not None:
            raise PipelineError(
                f"error in node {self._error_node!r}: {self._error!r}"
            ) from self._error
        return finished

    def stop(self) -> None:
        if self.state not in ("PLAYING", "ERROR"):
            self.state = "STOPPED"
            return
        # an errored pipeline (post_error flipped PLAYING → ERROR) takes
        # the FULL teardown: source threads are joined and every node runs
        # its STOPPED transition — a graph that died early must not leak
        # streaming threads behind the PipelineError its waiter sees
        prev = self.state
        self.state = "STOPPED"
        if _hooks.enabled:
            _hooks.emit("state_change", self, prev, "STOPPED")
        # dot dump on EVERY transition (tracers are still connected here,
        # so the STOPPED dump carries final frame counts / queue depths)
        self._dump_dot("STOPPED")
        for node in self.nodes.values():
            if isinstance(node, SourceNode):
                node.request_stop()
            interrupt = getattr(node, "interrupt", None)
            if interrupt is not None:
                interrupt()
        leaked = []
        for t in self.threads:
            t.join(timeout=5.0)
            if t.is_alive():
                leaked.append(t.name)
        if self._lanes is not None:
            leaked.extend(self._lanes.stop(timeout=5.0))
            self._lanes = None
        if leaked:
            import warnings

            warnings.warn(
                f"pipeline {self.name!r}: {len(leaked)} worker thread(s) did "
                f"not exit within 5s and were abandoned (wedged backend "
                f"invoke?): {', '.join(leaked)}",
                RuntimeWarning,
                stacklevel=2,
            )
        self.threads.clear()
        for node in self.nodes.values():
            node.stop()
        # segment folds are per-run: restore the user's graph so the next
        # start renegotiates (and re-plans) from the original topology —
        # the renegotiation half of the segment undo contract.  (Transform
        # fusion predates this and stays folded across stop, its
        # long-standing observable behavior.)
        from .segments import restore_segments

        restore_segments(self)
        # detach tracers from the hook bus (accumulated data stays readable
        # through stats(); a re-start reconnects them)
        self._stop_tracers()
        if self._xplane_tracing:
            self._xplane_tracing = False
            # the deep-profiling lane owns the stop/parse/bank half too:
            # the summary lands in the capture gallery, failures surface
            # through the health hook + degraded registry (never raises)
            from ..obs import profiler as _profiler

            _profiler.stop_whole_run(self)

    def _stop_tracers(self) -> None:
        for tracer in self._tracers:
            tracer.stop()
        for lane in self._lanes_started:
            lane.stop()  # joins the reaper: every completion is recorded
        self._lanes_started.clear()

    def run(self, timeout: Optional[float] = None) -> None:
        """start() + wait() + stop() — convenience for finite streams."""
        self.start()
        try:
            if not self.wait(timeout):
                raise PipelineError(f"pipeline did not finish within {timeout}s")
        finally:
            self.stop()

    # -- introspection ------------------------------------------------------

    def _post_negotiate_hooks(self) -> None:
        """Conf-driven observability at PLAYING: xplane trace + dot dump
        (the GST_DEBUG_DUMP_DOT_DIR analog, ``tools/debugging/``)."""
        import warnings

        from ..conf import conf

        # observability must never take the pipeline down: any failure here
        # (bad conf values included) is a warning, not an error.
        try:
            trace_dir = conf.get_path("common", "xplane_trace_dir", "")
            if trace_dir:
                # device-level xplane trace (jax.profiler) for the whole
                # PLAYING interval — SURVEY §5's HawkTracer/GstShark analog,
                # run through the deep-profiling lane (obs/profiler.py):
                # one start/stop implementation, raw artifacts under the
                # user's trace_dir, parsed summary in the capture gallery,
                # /profile answers a typed 409 while this trace holds the
                # window; stopped (and flushed to disk) in stop()
                from ..obs import profiler as _profiler

                self._xplane_tracing = _profiler.start_whole_run(
                    self, trace_dir)
            self._dump_dot("PLAYING")
        except Exception as exc:  # noqa: BLE001
            warnings.warn(f"observability hooks failed: {exc!r}", stacklevel=2)

    def _attach_observability(self) -> None:
        """Conf-driven tracer activation (``NNSTPU_TRACERS=latency;stats``)
        + the Prometheus scrape endpoint (``NNSTPU_METRICS_PORT``) — the
        GST_TRACERS analog, resolved at every start(), before
        negotiation (see the note in :meth:`start`)."""
        from ..conf import conf
        from ..obs import (
            configured_metrics_port,
            configured_tracers,
            ensure_server,
        )
        from ..utils import profiling

        if conf.get_bool("common", "enable_profiling", False):
            profiling.enable(True)
        # someone listens on the hook bus already (a harness's callback, an
        # operator's probe): this run records stage spans and device
        # completions to the flight recorder, for the listener to read
        # from obs.spans after stop()
        listened = _hooks.enabled
        attached = {t.name for t in self._tracers}
        for name in configured_tracers():
            if name not in attached:
                self.attach_tracer(name)
                attached.add(name)
        for tracer in self._tracers:
            tracer.start(self)
        if listened and "spans" not in attached:
            from ..obs.spans import SpanTracer

            self._lanes_started.append(SpanTracer(flows=False))
        if (listened or profiling.enabled()) and "device" not in attached:
            # the device lane is also what feeds the per-node latencies of
            # stats() under profiling: enqueue -> done, nothing blocks
            from ..obs.device import DeviceTracer
            from ..obs.metrics import MetricsRegistry

            self._lanes_started.append(
                DeviceTracer(registry=MetricsRegistry()))
        if listened:
            # the witness of a stall of the whole host, beside the reaper:
            # a beat that notes what the kernel knows when it wakes late
            from ..obs.hoststall import HostBeat

            self._lanes_started.append(HostBeat())
        for lane in self._lanes_started:
            lane.start(self)
        port = configured_metrics_port()
        if port is not None:
            ensure_server(port)
        # structured twin of the scrape endpoint: this pipeline's stats()
        # joins the merged /stats.json document
        from ..obs.export import register_stats

        register_stats(self.name, self.stats)

    def warmup(self) -> dict:
        """Explicit compile-ahead warmup: compile every element's bucket
        ladder now (``run_warmup`` does this implicitly at start when
        ``[compile] warmup`` is on).  Needs negotiated specs, so the
        pipeline must be PLAYING; the report is also kept on
        :attr:`warmup_report`."""
        from .warmup import collect_plan, execute

        if self.state != "PLAYING":
            raise PipelineError(
                "warmup() needs a started pipeline (negotiated specs)")
        self.warmup_report = execute(collect_plan(self), pipeline=self)
        try:
            # HBM residency check over the warmed executables (typed
            # HbmCapacityWarning + degraded reason when over capacity —
            # advisory, never a failure; see obs/profiler.py)
            from ..obs.profiler import check_hbm_capacity

            self.warmup_report["hbm"] = check_hbm_capacity(self)
        except Exception:  # noqa: BLE001 — the residency check is advisory
            pass
        return self.warmup_report

    def attach_tracer(self, tracer):
        """Attach a tracer (name or instance) to this pipeline — the
        programmatic ``GST_TRACERS`` surface.  Hooks connect immediately
        when PLAYING, else at the next start; returns the tracer so the
        caller can read ``tracer.summary()`` (also merged into
        :meth:`stats` under ``"tracers"``)."""
        from ..obs.tracers import make_tracer

        if isinstance(tracer, str):
            tracer = make_tracer(tracer)
        self._tracers.append(tracer)
        if self.state == "PLAYING":
            tracer.start(self)
        return tracer

    def detach_tracer(self, tracer) -> None:
        tracer.stop()
        if tracer in self._tracers:
            self._tracers.remove(tracer)

    @property
    def tracers(self) -> List:
        return list(self._tracers)

    def stats(self) -> dict:
        """Per-node invoke-latency summary (ms) for this pipeline's nodes
        (populated when profiling is enabled), plus one ``"tracers"`` entry
        per attached tracer — e2e latency, throughput, drop accounting."""
        from ..utils import profiling

        all_stats = profiling.stats()
        out = {k: v for k, v in all_stats.items() if k in self.nodes}
        if self._tracers:
            out["tracers"] = {t.name: t.summary() for t in self._tracers}
        rec = self.recovery_stats()
        if rec:
            out["recovery"] = rec
        if self._lanes is not None:
            out["lanes"] = self._lanes.stats()
        return out

    def flight_snapshot(self) -> list:
        """Span records accumulated by a ``spans`` tracer (the flight
        recorder), time-ordered and ready for
        :func:`nnstreamer_tpu.obs.spans.chrome_trace` /
        :func:`~nnstreamer_tpu.obs.spans.waterfall`.  Readable during
        PLAYING and after stop (the recorder outlives the hooks)."""
        from ..obs import spans

        return spans.snapshot()

    def _tracers_active(self) -> bool:
        return any(t.active for t in self._tracers)

    def _dump_dot(self, transition: str) -> None:
        """Write ``{name}.{transition}.dot`` into the conf'd dump dir on a
        state transition / error — the full GST_DEBUG_DUMP_DOT_DIR analog
        (the reference dumps on every transition, not just PLAYING)."""
        import os
        import warnings

        from ..conf import conf

        try:
            dot_dir = conf.get_path("common", "dump_dot_dir", "")
            if not dot_dir:
                return
            os.makedirs(dot_dir, exist_ok=True)
            path = os.path.join(dot_dir, f"{self.name}.{transition}.dot")
            with open(path, "w") as f:
                f.write(self.to_dot(annotate=self._tracers_active()))
        except Exception as exc:  # noqa: BLE001 — observability stays non-fatal
            warnings.warn(f"dot dump ({transition}) failed: {exc!r}",
                          stacklevel=2)

    def _dump_flight(self, transition: str) -> None:
        """Write the flight recorder as Chrome-trace JSON on error (conf
        ``[obs] flight_dump_dir``) — the post-mortem the span layer exists
        for: open ``{name}.error.trace.json`` in Perfetto."""
        import json
        import os
        import warnings

        from ..conf import conf
        from ..obs import spans

        try:
            if not spans.enabled:
                return
            dump_dir = conf.get_path("obs", "flight_dump_dir", "")
            if not dump_dir:
                return
            os.makedirs(dump_dir, exist_ok=True)
            path = os.path.join(dump_dir, f"{self.name}.{transition}.trace.json")
            doc = spans.chrome_trace(spans.snapshot(), process_name=self.name)
            try:
                from ..obs.device import device_memory_snapshot

                mem = device_memory_snapshot()
                if mem:
                    # "otherData" is the trace-event format's sidecar slot:
                    # what the device allocators held when the graph died
                    doc["otherData"] = {"device_memory": mem}
            except Exception:  # noqa: BLE001 — the dump matters more
                pass
            try:
                from ..obs.profiler import hbm_ledger

                ledger = hbm_ledger()
                if ledger:
                    # the per-executable memory_analysis() ledger next to
                    # the live allocator stats: an OOM verdict can name
                    # the largest resident executable, not just the
                    # device that died
                    doc.setdefault("otherData", {})["hbm_ledger"] = ledger
            except Exception:  # noqa: BLE001 — the dump matters more
                pass
            with open(path, "w") as f:
                json.dump(doc, f)
        except Exception as exc:  # noqa: BLE001
            warnings.warn(f"flight dump ({transition}) failed: {exc!r}",
                          stacklevel=2)

    def _dot_annotations(self) -> Dict[str, str]:
        """Live per-node stats for annotated dot dumps: frames pushed from
        the stats tracer, queue depth from queue-like nodes' stats()."""
        notes: Dict[str, str] = {}
        for tracer in self._tracers:
            if tracer.name != "stats" or not tracer.active:
                continue
            for name, s in tracer.summary().items():
                parts = []
                if s.get("frames") is not None:
                    parts.append(f"{s['frames']} frames")
                if s.get("queue_depth") is not None:
                    parts.append(f"depth {s['queue_depth']}")
                if parts:
                    notes[name] = ", ".join(parts)
        for node in self.nodes.values():
            if node.name in notes:
                continue
            node_stats = getattr(node, "stats", None)
            if node_stats is None:
                continue
            try:
                s = node_stats()
            except Exception:  # noqa: BLE001 — annotation is best-effort
                continue
            if isinstance(s, dict) and s.get("depth") is not None:
                notes[node.name] = f"depth {s['depth']}"
        return notes

    def to_dot(self, annotate: bool = False) -> str:
        """Graphviz dump of the graph with negotiated specs — the analog of
        GST_DEBUG_DUMP_DOT_DIR pipeline dumps (``tools/debugging/``).
        ``annotate=True`` adds live stats (frames pushed, queue depth) to
        node labels when tracers are collecting."""
        notes = self._dot_annotations() if annotate else {}
        lines = [f'digraph "{self.name}" {{', "  rankdir=LR;", "  node [shape=box];"]
        for node in self.nodes.values():
            label = f"{node.name}\\n{type(node).__name__}"
            extra = notes.get(node.name)
            if extra:
                label += f"\\n{extra}"
            lines.append(f'  "{node.name}" [label="{label}"];')
        for node in self.nodes.values():
            for pad in node.src_pads.values():
                if pad.peer is not None:
                    label = str(pad.spec) if pad.spec is not None else ""
                    lines.append(
                        f'  "{node.name}" -> "{pad.peer.node.name}" '
                        f'[label="{pad.name}→{pad.peer.name}\\n{label}"];'
                    )
        lines.append("}")
        return "\n".join(lines)
