"""``tensor_filter``: the central element — invokes an NN model on the stream.

Analog of ``gst/nnstreamer/tensor_filter/tensor_filter.c`` (the
GstBaseTransform at ``:132``):

- ``framework=`` selects a backend from the registry (lazy import — the
  ``dlopen`` analog, ``nnstreamer_subplugin.c:74-103``);
- the model opens on start (``:873-888``);
- negotiation reconciles model metadata, user ``input``/``inputtype``/
  ``output``/``outputtype`` property overrides, and the upstream stream spec
  (``load_tensor_info``/``configure_tensor``, ``:442-505,513-623``),
  failing loudly on mismatch;
- steady state maps input tensors → backend ``invoke`` → output frame
  (``:316-436``); device-resident backends keep outputs on TPU (the
  ``allocate_in_invoke`` generalization).

Timing never blocks the dispatching thread: while the hook bus has a
listener each dispatch records a ``<filter>.invoke`` stage span (host side
of upload + enqueue) and emits ``device_dispatch``, from which the device
lane (:mod:`nnstreamer_tpu.obs.device`) observes the completion; that
enqueue→done time is also the per-node latency of ``Pipeline.stats()``
when profiling is enabled (:mod:`nnstreamer_tpu.utils.profiling`).

Where the program runs on an accelerator and host consumers read its
results downstream, the way back can leave the dispatching thread: an
ordered completion worker (:mod:`nnstreamer_tpu.graph.completion`) pushes
a round's results downstream once they are ready, while the thread that
dispatched it carries the next round to the device.  The filter does so
where its own clock says the way back is worth it; on the host's CPU and
under dispatcher lanes the way back stays in the dispatching thread.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

from .. import faults as _faults
from ..backends.base import FilterBackend, get_backend
from ..buffer import Frame
from ..graph import completion as _completion
from ..graph.node import NegotiationError, Node, Pad
from ..graph.registry import register_element
from ..obs import hooks as _hooks
from ..obs import spans as _spans
from ..spec import TensorSpec, TensorsSpec

_UNDECIDED = object()  # a run's completion worker, before its first round


@register_element("tensor_filter")
class TensorFilter(Node):
    def __init__(
        self,
        name: Optional[str] = None,
        framework: str = "",
        model: object = None,
        custom: str = "",
        input: str = "",
        inputtype: str = "",
        output: str = "",
        outputtype: str = "",
        backend: Optional[FilterBackend] = None,
    ):
        super().__init__(name)
        self.add_sink_pad("sink")
        self.add_src_pad("src")
        if backend is not None:
            self.backend = backend
        else:
            if not framework:
                raise ValueError("tensor_filter requires framework=")
            self.backend = get_backend(framework)
        self.framework = framework or self.backend.name
        self.model = model
        self.custom = str(custom)
        self._prop_in = self._parse_spec_props(input, inputtype)
        self._prop_out = self._parse_spec_props(output, outputtype)
        self._opened = False
        self._downstream_host = False  # set at configure from topology
        self._fused_pre: list = []  # TensorTransforms folded in (optimize.py)
        self._fused_post: list = []
        self._fusion_dirty = False
        self.dispatches = 0  # traced dispatches: the spans' round id
        # this run's graph.completion.Completion, or None where the way
        # back stays in the dispatching thread; decided at the first round
        self._done = _UNDECIDED
        self._force_ahead = False  # tests: every round ahead, on any device

    def set_fused_transforms(self, pre: list, post: list) -> None:
        """Install transforms fused into this filter's XLA program (called
        by the graph optimizer, ``graph/optimize.py``)."""
        self._fused_pre = list(pre)
        self._fused_post = list(post)
        self._fusion_dirty = True  # next wrapper install must drop the cache

    @staticmethod
    def _parse_spec_props(dims: str, types: str) -> Optional[TensorsSpec]:
        """Parse reference-style ``input=3:224:224:1.1:10`` + ``inputtype=...``
        property pairs (``tensor_filter_common.c:261-292``; '.' separates
        multiple tensors)."""
        if not dims and not types:
            return None
        dim_list = [d for d in str(dims).split(".") if d] if dims else []
        type_list = [t for t in str(types).split(",") if t] if types else []
        n = max(len(dim_list), len(type_list))
        tensors = []
        for i in range(n):
            d = dim_list[i] if i < len(dim_list) else None
            t = type_list[i] if i < len(type_list) else None
            if d is not None:
                tensors.append(TensorSpec.from_dims_string(d, t))
            else:
                from ..spec import dtype_from_name

                tensors.append(TensorSpec(dtype=dtype_from_name(t)))
        return TensorsSpec(tensors=tuple(tensors))

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        super().start()
        if not self._opened:
            if self.model is None and getattr(self.backend, "model", None) is not None:
                # injected pre-opened backend (model already loaded, possibly
                # with pre-compiled executables in its cache): re-opening
                # would discard that warm state
                self._opened = True
            else:
                self.backend.open(self.model, self.custom)
                self._opened = True

    def interrupt(self) -> None:
        """``Pipeline.stop``: no more hand-offs to the completion worker
        (the pipeline joins it with its other threads)."""
        if isinstance(self._done, _completion.Completion):
            self._done.close(wait=False)

    def stop(self) -> None:
        done, self._done = self._done, _UNDECIDED
        if isinstance(done, _completion.Completion):
            done.close()
        if self._opened:
            self.backend.close()
            self._opened = False
        super().stop()

    # -- negotiation --------------------------------------------------------

    def sink_spec(self, pad_name: str) -> TensorsSpec:
        del pad_name
        if self._fused_pre:
            # the stream spec is pre-transform; the model spec (and any
            # input= property, which describes the MODEL input) only applies
            # after the fused pre-ops run — checked in _install_fusion
            return TensorsSpec()
        spec = self.backend.model_spec() if self._opened else None
        if spec is not None and self._prop_in is not None:
            merged = spec.intersect(self._prop_in)
            if merged is None:
                raise NegotiationError(
                    f"{self.name}: input property {self._prop_in} conflicts "
                    f"with model spec {spec}"
                )
            return merged
        return self._prop_in or spec or TensorsSpec()

    def _upstream_device_resident(self) -> bool:
        from ..graph.residency import chain_device_resident

        return chain_device_resident(self, "up")

    def _downstream_device_resident(self) -> bool:
        from ..graph.residency import chain_device_resident

        return chain_device_resident(self, "down")

    def configure(self, in_specs: Dict[str, TensorsSpec]) -> Dict[str, TensorsSpec]:
        in_spec = in_specs["sink"]
        if hasattr(self.backend, "expect_device_input"):
            self.backend.expect_device_input = self._upstream_device_resident()
        # downstream host consumers (decoders, numpy sinks) will call
        # np.asarray on our outputs: start the device→host copy at emit
        # time so their blocking read finds local data instead of paying a
        # full round trip per frame
        self._downstream_host = not self._downstream_device_resident()
        if self._fused_pre or self._fused_post:
            self._install_fusion(in_spec)  # validates model spec vs chain
            # compile against the RAW stream spec: the fused program's
            # entry point consumes pre-transform frames
            out_spec = self.backend.reconfigure_fused(in_spec)
            if hasattr(self.backend, "set_drift_hook"):
                # un-renegotiated shape/dtype drift (polymorphic upstream
                # pad) must rebuild the fused chain, not just recompile
                self.backend.set_drift_hook(self._drift_reinstall)
        else:
            out_spec = self.backend.reconfigure(in_spec)
        # output= property describes the MODEL output; with fused post-
        # transforms the pad spec is post-transform, so the check happened
        # against the model output inside _install_fusion instead.
        if self._prop_out is not None and not self._fused_post:
            merged = out_spec.intersect(self._prop_out)
            if merged is None:
                raise NegotiationError(
                    f"{self.name}: model output {out_spec} conflicts with "
                    f"output property {self._prop_out}"
                )
            out_spec = merged
        if in_spec.rate is not None and out_spec.rate is None:
            out_spec = TensorsSpec(tensors=out_spec.tensors, rate=in_spec.rate)
        return {"src": out_spec}

    def _drift_reinstall(self, drifted_spec: TensorsSpec) -> None:
        """Rebind the fused chain to a drifted input spec: stage functions
        bake per-spec geometry (transpose/dimchg), so drift re-runs the
        install before recompiling (the executable cache keys by spec, so
        alternating shapes stay cheap)."""
        self._install_fusion(drifted_spec)
        self.backend.reconfigure_fused(drifted_spec)

    def _install_fusion(self, in_spec: TensorsSpec) -> TensorsSpec:
        """Compose fused pre/post transforms around the backend fn so the
        whole chain compiles as ONE XLA program.  Returns the spec the model
        actually sees (post-pre-transforms)."""
        import jax.numpy as jnp

        pre_stages = []
        spec_cur = in_spec
        for tr in self._fused_pre:
            pre_stages.append([tr.build_fn(t) for t in spec_cur.tensors])
            spec_cur = TensorsSpec(
                tensors=tuple(tr.out_spec_for(t) for t in spec_cur.tensors),
                rate=spec_cur.rate,
            )
        model_spec = self.backend.model_spec()
        if model_spec is not None and model_spec.intersect(spec_cur) is None:
            raise NegotiationError(
                f"{self.name}: fused pre-transform output {spec_cur} is "
                f"incompatible with model spec {model_spec}"
            )
        # input= property describes the MODEL input, which with fusion is the
        # pre-transform chain's output — enforce it here (the unfused path
        # enforces it in sink_spec).
        if self._prop_in is not None and self._prop_in.intersect(spec_cur) is None:
            raise NegotiationError(
                f"{self.name}: fused pre-transform output {spec_cur} "
                f"conflicts with input property {self._prop_in}"
            )
        # post stages come in two shapes: per-tensor transforms (zipped
        # 1:1, the classic tensor_transform protocol) and N:M "multi"
        # stages (segment-folded decoder heads, graph/segments.py) that
        # consume the whole tensor tuple at once
        post_stages = []  # (zip_fns | None, multi_fn | None)
        if self._fused_post:
            spec_o = self.backend.trace_output_spec(spec_cur)
            if self._prop_out is not None and self._prop_out.intersect(spec_o) is None:
                raise NegotiationError(
                    f"{self.name}: model output {spec_o} conflicts with "
                    f"output property {self._prop_out}"
                )
            post = list(self._fused_post)
            for i, tr in enumerate(post):
                build_multi = getattr(tr, "build_multi", None)
                if build_multi is not None:
                    built = build_multi(spec_o)
                    if built is None:
                        # per-element fallback: the stage refused this
                        # geometry, so drop it AND the rest of the chain
                        # (later stages consume its output), telling each
                        # to restore its host path
                        for rest in post[i:]:
                            refuse = getattr(rest, "on_refuse", None)
                            if refuse is not None:
                                refuse()
                        break
                    mfn, spec_o = built
                    post_stages.append((None, mfn))
                else:
                    post_stages.append(
                        ([tr.build_fn(t) for t in spec_o.tensors], None))
                    spec_o = TensorsSpec(
                        tensors=tuple(tr.out_spec_for(t) for t in spec_o.tensors),
                        rate=spec_o.rate,
                    )

        def wrapper(orig):
            def fn(*xs):
                for stage in pre_stages:
                    xs = tuple(f(x, jnp) for f, x in zip(stage, xs))
                out = orig(*xs)
                single = not isinstance(out, (tuple, list))
                outs = (out,) if single else tuple(out)
                multi_used = False
                for zip_fns, multi_fn in post_stages:
                    if multi_fn is not None:
                        outs = tuple(multi_fn(outs, jnp))
                        multi_used = True
                    else:
                        outs = tuple(f(x, jnp) for f, x in zip(zip_fns, outs))
                if multi_used:
                    # an N:M stage dissolved the model's output structure;
                    # emit the stage tuple as-is
                    return outs[0] if len(outs) == 1 else outs
                if single:
                    return outs[0]
                if hasattr(out, "_fields"):  # namedtuple output
                    return type(out)(*outs)
                return type(out)(outs)
            return fn

        # a spec-derived rebuild of the SAME fused chain keeps the backend's
        # executable cache (mid-stream renegotiation alternating A/B shapes
        # hits the cache); only a changed transform list invalidates
        self.backend.set_wrapper(wrapper, invalidate=self._fusion_dirty)
        self._fusion_dirty = False
        return spec_cur

    # -- compile-ahead warmup ------------------------------------------------

    def warm_spec(self, spec: TensorsSpec) -> None:
        """AOT-compile one runtime geometry into the backend's executable
        cache without disturbing the active (negotiated) entry — the
        warmup planner's per-bucket thunk (``graph/warmup.py``; upstream
        ``tensor_dynbatch`` enumerates the buckets).  Fused filters take
        the drift-reinstall path: the fused wrapper bakes per-spec
        geometry, so each bucket compiles with ITS wrapper, and the
        negotiated wrapper is re-installed afterwards — exactly the
        discipline the runtime drift hook follows."""
        be = self.backend
        # serialize with the dispatch path: Node._dispatch invokes under
        # this lock, so a frame never observes the transient bucket-spec
        # backend state between a warm compile and the active restore
        # (explicit pipeline.warmup() runs while PLAYING)
        with self._lock:
            if self._fused_pre or self._fused_post:
                active = self.sink_pads["sink"].spec
                self._install_fusion(spec)
                be.reconfigure_fused(spec)
                if active is not None:
                    self._install_fusion(active)
                    be.reconfigure_fused(active)
                return
            warm = getattr(be, "warm_compile", None)
            if warm is not None:
                warm(spec)

    # -- hot loop -----------------------------------------------------------

    def process(self, pad: Pad, frame: Frame):
        del pad
        if _faults.enabled:
            # chaos point "backend_invoke": invoke_delay/device_stall
            # sleep here, invoke_raise raises — an InjectedFault is then
            # handled exactly like a real one (restart policy or
            # post_error)
            _faults.maybe_invoke(self.name)
        if _hooks.enabled:
            # async dispatch: invoke() returns at ENQUEUE.  The stage span
            # bounds the host side (implicit upload + enqueue); the device
            # tracer's completion probe recovers the true device time —
            # t0 here is the enqueue timestamp of its device_exec span,
            # and the dispatch count joins the two.
            self.dispatches += 1
            t0 = time.perf_counter_ns()
            tok = _spans.stage_begin(self.name + ".invoke",
                                     round=self.dispatches)
            try:
                outs = self.backend.invoke(frame.tensors)
            finally:
                _spans.stage_end(tok)
            _hooks.emit("device_dispatch", self, frame, outs, t0)
        else:
            outs = self.backend.invoke(frame.tensors)
        if not outs:
            return None  # backend dropped the frame (FLOW_DROPPED analog)
        if self._downstream_host:
            for o in outs:
                start = getattr(o, "copy_to_host_async", None)
                if start is not None:
                    start()  # non-blocking; overlaps the d2h with dispatches
        return frame.with_tensors(outs)

    def _emit(self, result) -> None:
        if result is None:
            return
        done = self._done
        if done is _UNDECIDED:
            done = self._done = self._completion()
        is_round = not self._quarantined
        if done is None:
            if is_round:
                _completion.count_round(self.name, "inline")
            super()._emit(result)
            return
        done.emit(result, time.perf_counter_ns() if is_round else None)

    def _handle_event(self, pad: Pad, event) -> None:
        if isinstance(self._done, _completion.Completion):
            self._done.flush()  # every frame before the event goes first
        super()._handle_event(pad, event)

    def _completion(self) -> Optional[_completion.Completion]:
        """This run's completion worker, or None where the way back stays
        in the dispatching thread: a program on the host's CPU (host and
        "device" are the same cores), results that stay on the device
        downstream, dispatcher lanes (they schedule the chains
        themselves)."""
        if self._force_ahead:
            return _completion.Completion(
                self, _completion.AheadRule(ahead=True, fixed=True))
        pl = self.pipeline
        on_accelerator = getattr(self.backend, "on_accelerator", None)
        if (pl is None or pl._lanes is not None or not self._downstream_host
                or on_accelerator is None or not on_accelerator()):
            return None
        return _completion.Completion(self, _completion.AheadRule())
