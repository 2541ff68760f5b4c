#!/usr/bin/env python
"""chip_smoke.py: does the streaming path run on the chip?

Drives the system's main path once, through the entry points a user calls,
at the full width of MobileNet-v2 (1.0, 224x224, 1001 classes, bf16), in ONE
process that owns the chip from start to end.  Weights are random (seeded);
every input (frames, labels file) is generated from a seed inside the
checkout.  Not a benchmark: the seconds it prints say whether compiles hit
the cache and whether anything compiled after warm-up, nothing more.

    python chip_smoke.py                 # on the machine with the TPU
    python chip_smoke.py --cpu-rehearsal # control-flow check, tiny sizes

Exit codes: 0 every phase passed on a TPU; 1 a phase failed; 2 jax found no
TPU (nothing is built, no result is printed); 3 the rehearsal finished (it
cannot print a pass).  On success the last stdout line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Phases (each fails the run on any mismatch):
- labeling: videotestsrc → tensor_converter → tensor_transform (fused into
  the model) → tensor_upload → queue → tensor_filter framework=jax →
  tensor_decoder image_labeling → tensor_sink, with a tee on the filter's
  output so the pipeline's own logits are checked too;
- serving door: a QueryServer in this process answers a tensor_query_client
  pipeline over loopback; replies equal the labeling logits;
- multi-stream: 4 sources → tensor_mux → tensor_batch → the model at batch
  4 → tensor_unbatch → tensor_demux; per-stream outputs equal batch-1;
- decode session: ContinuousBatcher at its shipped defaults, two identical
  sessions, prefill then feed/get;
- kernels: every Pallas kernel the tree ships, compiled by Mosaic
  (interpret=False) and compared with its jnp reference;
- attention paths: one ViT whose heads tile, compiled for the chip (every
  layer the fused kernel) and under jax.default_device(cpu), as the backend's
  cpu_fallback retry compiles it (every layer full_attention); same logits;
- four chips (only when jax.device_count() >= 4): multi-stream again under
  NNSTPU_MESH=dp:4, output shards on four distinct devices;
- attention on four chips (the same condition): the ViT with its batch
  sharded over the four (GSPMD partitions it: full_attention) and a pipelined
  encoder (the kernel inside shard_map), each against one device.
"""

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
NORMALIZE = "typecast:float32,add:-127.5,div:127.5"
# bf16 logits against a float32 (precision=highest) forward of the same
# frames: relative L2 error per frame.  bf16 keeps 8 mantissa bits and the
# network is ~50 layers deep; 0.031 was the worst frame measured on a v5e
# with these seeded weights (PERF.md Findings, PR 21), so 0.06 is 2x that.
# Two bf16 programs of the same model (fused vs unfused normalize, batch 1
# vs batch 4) differ by the same rounding noise and get the same bound.
BF16_REL_L2 = 0.06

FULL = dict(image=224, width=1.0, classes=1001, frames=32, query_frames=8,
            streams=4, per_stream=8)
REHEARSAL = dict(image=32, width=0.35, classes=1001, frames=8, query_frames=4,
                 streams=4, per_stream=2)


def say(*args):
    print(*args, flush=True)


def check(cond, why):
    """``assert`` that survives ``python -O``."""
    if not cond:
        raise AssertionError(why)


# -- compile accounting: jax's own monitoring events, so a silent jit retrace
# -- counts the same as a backend compile the repo's counters know about

class CompileWatch:
    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snap(self):
        return (self.count, self.seconds, self.cache_hits)


def counts(counter):
    """{first label: count} of one of the repo's counters."""
    from nnstreamer_tpu.obs.metrics import REGISTRY

    c = REGISTRY.get(counter)
    if c is None:
        return {}
    return {k[0]: int(v.value) for k, v in dict(c.children()).items()}


def repo_compiles():
    """{result: count} of the repo's own record_compile counters."""
    return counts("nnstpu_compile_total")


def attention_lowerings():
    """{path: count} of the attention calls lowered so far."""
    return counts("nnstpu_attention_lowerings_total")


def risen(before):
    """What attention_lowerings() has risen by since ``before``."""
    return {k: v - before.get(k, 0) for k, v in attention_lowerings().items()
            if v - before.get(k, 0)}


class Smoke:
    def __init__(self, sizes, rehearsal, queue_backend):
        self.sizes = sizes
        self.rehearsal = rehearsal
        self.queue_backend = queue_backend
        self.watch = CompileWatch()
        self.phases = []      # per-phase summary rows, in order
        self.failed = []
        self.logits = None    # labeling phase: (frames, classes) float32

    # -- phase runner -------------------------------------------------------

    def phase(self, name, fn):
        """Run one phase; a raise fails the run (exit 1, no result line)
        after the remaining phases have had their turn."""
        from nnstreamer_tpu.obs.export import degraded_snapshot

        say(f"== {name}")
        c0, r0, t0 = self.watch.snap(), repo_compiles(), time.perf_counter()
        row = {"phase": name, "ok": False}
        try:
            row.update(fn() or {})
            degraded = degraded_snapshot()
            check(not degraded, f"degraded backend(s): {degraded}")
            row["ok"] = True
        except Exception:  # noqa: BLE001 — recorded as a FAILED phase
            traceback.print_exc(file=sys.stdout)
            self.failed.append(name)
        c1, r1 = self.watch.snap(), repo_compiles()
        row["xla_compiles"] = c1[0] - c0[0]
        row["compile_s"] = round(c1[1] - c0[1], 3)
        row["cache_hits"] = c1[2] - c0[2]
        row["repo_compiles"] = {k: v - r0.get(k, 0) for k, v in r1.items()
                                if v - r0.get(k, 0)}
        row["wall_s"] = round(time.perf_counter() - t0, 3)
        self.phases.append(row)
        say(f"-- {name}: {'PASS' if row['ok'] else 'FAIL'} {json.dumps(row)}")

    # -- shared pieces ------------------------------------------------------

    def model(self, batch=None):
        from nnstreamer_tpu.models import mobilenet_v2

        s = self.sizes
        return mobilenet_v2.build(num_classes=s["classes"],
                                  width_mult=s["width"],
                                  image_size=s["image"], batch=batch)

    def source(self, p, n, seed, name=None):
        """videotestsrc (seeded random frames) → tensor_converter."""
        import nnstreamer_tpu as nns

        s = self.sizes
        src = p.add(nns.make("videotestsrc", name=name, num_buffers=n,
                             pattern="random", seed=seed,
                             width=s["image"], height=s["image"]))
        conv = p.add(nns.make("tensor_converter"))
        p.link(src, conv)
        return conv

    def source_frames(self, n, seed):
        """The uint8 frames ``source(n, seed)`` emits, collected on host."""
        import numpy as np

        import nnstreamer_tpu as nns
        from nnstreamer_tpu.elements.sink import TensorSink

        p = nns.Pipeline(name="smoke_frames")
        sink = p.add(TensorSink(collect=True))
        p.link(self.source(p, n, seed), sink)
        p.run(timeout=120)
        return np.stack([np.asarray(f.tensor(0)) for f in sink.frames])

    def check_close(self, got, want, what):
        import numpy as np

        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        check(got.shape == want.shape, (what, got.shape, want.shape))
        check(np.isfinite(got).all(), f"{what}: non-finite values")
        rel = (np.linalg.norm(got - want, axis=-1)
               / np.linalg.norm(want, axis=-1))
        worst = float(rel.max())
        check(worst <= BF16_REL_L2,
              f"{what}: relative L2 error {worst:.4f} > {BF16_REL_L2}")
        return round(worst, 5)

    def first_output_marker(self):
        """``(mark, callback)``: the callback stamps the clock and both
        compile counts when a sink sees its first frame."""
        mark = {}

        def on_frame(_frame):
            if not mark:
                mark.update(t=time.perf_counter(), xla=self.watch.count,
                            xla_s=self.watch.seconds, repo=repo_compiles())

        return mark, on_frame

    def on_device(self, arr, what):
        import jax

        check(isinstance(arr, jax.Array), f"{what}: {type(arr)} on host")
        plats = {d.platform for d in arr.devices()}
        check(self.rehearsal or plats == {"tpu"},
              f"{what}: lives on {plats}, expected a TPU")

    # -- phases -------------------------------------------------------------

    def labeling(self):
        import numpy as np

        import jax
        import jax.numpy as jnp

        import nnstreamer_tpu as nns
        from nnstreamer_tpu.elements.filter import TensorFilter
        from nnstreamer_tpu.elements.sink import TensorSink
        from nnstreamer_tpu.models import mobilenet_v2

        s = self.sizes
        n = s["frames"]
        model = self.model()
        os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
        labels_path = os.path.join(HERE, "chiprun_out", "smoke_labels.txt")
        with open(labels_path, "w") as f:
            f.write("\n".join(f"class_{i}" for i in range(s["classes"])))

        p = nns.Pipeline(name="smoke_labeling")
        conv = self.source(p, n, seed=0)
        norm = p.add(nns.make("tensor_transform", mode="arithmetic",
                              option=NORMALIZE))
        up = p.add(nns.make("tensor_upload"))
        q = p.add(nns.make("queue", max_size_buffers=16))
        filt = p.add(TensorFilter(framework="jax", model=model))
        tee = p.add(nns.make("tee"))
        dec = p.add(nns.make("tensor_decoder", mode="image_labeling",
                             option1=labels_path))
        labels = p.add(TensorSink(collect=True, name="labels"))
        logits = p.add(TensorSink(collect=True, name="logits"))
        p.link_chain(conv, norm, up, q, filt, tee)
        p.link_chain(tee, dec, labels)
        p.link(tee, logits)
        first, on_logits = self.first_output_marker()
        logits.connect("new-data", on_logits)
        xla_s0 = self.watch.seconds
        t0 = time.perf_counter()
        p.run(timeout=600)
        jax.block_until_ready([f.tensor(0) for f in logits.frames])
        t_end = time.perf_counter()

        check("tensor_transform" not in " ".join(p.nodes),
              "the normalize chain was not fused into the model")
        check(q.backend_kind == self.queue_backend, q.backend_kind)
        check(filt.backend._degraded is None, filt.backend._degraded)
        check(len(logits.frames) == n and len(labels.frames) == n,
              (len(logits.frames), len(labels.frames)))
        pts = [f.pts for f in logits.frames]
        check(pts == sorted(set(pts)), f"frames out of order: {pts}")
        for f in logits.frames:
            self.on_device(f.tensor(0), "filter output")
        got = np.stack([np.asarray(f.tensor(0)) for f in logits.frames])
        check(got.shape == (n, s["classes"]) and got.dtype == np.float32,
              (got.shape, got.dtype))
        for i, f in enumerate(labels.frames):
            label = bytes(np.asarray(f.tensor(0))).decode()
            check(label == f"class_{int(got[i].argmax())}", (i, label))
        # no compile after the first frame came out, by jax's count and by
        # the repo's own counters; exactly one executable was built
        check(self.watch.count == first["xla"],
              f"{self.watch.count - first['xla']} XLA compile(s) mid-stream")
        check(repo_compiles() == first["repo"], (repo_compiles(), first))

        # float32 reference of the same frames, on the same device
        frames = self.source_frames(n, seed=0)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda x: mobilenet_v2.apply(
                model.params, (x.astype(jnp.float32) - 127.5) / 127.5,
                dtype=jnp.float32))
            want = np.concatenate([np.asarray(ref(frames[i:i + 8]))
                                   for i in range(0, n, 8)])
        worst = self.check_close(got, want, "bf16 pipeline vs float32")
        self.logits = got
        return {"frames": n, "first_frame_s": round(first["t"] - t0, 3),
                "model_compile_s": round(first["xla_s"] - xla_s0, 3),
                "stream_s": round(t_end - first["t"], 3),
                "rel_l2_vs_f32": worst,
                "distinct_labels": len({int(r.argmax()) for r in got})}

    def serving_door(self):
        import numpy as np

        import nnstreamer_tpu as nns
        from nnstreamer_tpu.elements.query import QueryServer, TensorQueryClient
        from nnstreamer_tpu.elements.sink import TensorSink

        n = self.sizes["query_frames"]
        got, stamps = [], []
        with QueryServer(framework="jax", model=self.model()) as srv:
            p = nns.Pipeline(name="smoke_query")
            conv = self.source(p, n, seed=0)
            norm = p.add(nns.make("tensor_transform", mode="arithmetic",
                                  option=NORMALIZE))
            client = p.add(TensorQueryClient(port=srv.port,
                                             request_timeout=600.0))
            sink = p.add(TensorSink())

            def on_reply(frame):
                stamps.append((time.perf_counter(), self.watch.count))
                got.append(np.asarray(frame.tensor(0)))

            sink.connect("new-data", on_reply)
            p.link_chain(conv, norm, client, sink)
            t0 = time.perf_counter()
            p.run(timeout=600)
        check(len(got) == n, f"{len(got)} of {n} replies")
        check(stamps[-1][1] == stamps[0][1], "XLA compile after first reply")
        worst = self.check_close(np.stack(got), self.logits[:n],
                                 "query replies vs labeling logits")
        return {"requests": n, "first_frame_s": round(stamps[0][0] - t0, 3),
                "stream_s": round(stamps[-1][0] - stamps[0][0], 3),
                "rel_l2_vs_labeling": worst}

    def multi_stream(self, shards=1):
        import numpy as np

        import jax

        import nnstreamer_tpu as nns
        from nnstreamer_tpu.elements.filter import TensorFilter
        from nnstreamer_tpu.elements.sink import TensorSink

        s = self.sizes
        k, per = s["streams"], s["per_stream"]
        p = nns.Pipeline(name=f"smoke_mux_x{shards}")
        mux = p.add(nns.make("tensor_mux", sync_mode="nosync"))
        for i in range(k):
            # stream i replays labeling frames [i*per, (i+1)*per)
            p.link(self.source(p, per, seed=i * per, name=f"cam{i}"),
                   f"{mux.name}.sink_{i}")
        batch = p.add(nns.make("tensor_batch"))
        norm = p.add(nns.make("tensor_transform", mode="arithmetic",
                              option=NORMALIZE))
        filt = p.add(TensorFilter(framework="jax", model=self.model(batch=k)))
        probe = p.add(nns.make("tee"))
        batched = p.add(TensorSink(collect=True, name="batched"))
        unbatch = p.add(nns.make("tensor_unbatch"))
        demux = p.add(nns.make("tensor_demux"))
        p.link_chain(mux, batch, norm, filt, probe, unbatch, demux)
        p.link(probe, batched)
        sinks = []
        for i in range(k):
            sinks.append(p.add(TensorSink(collect=True, name=f"out{i}")))
            p.link(f"{demux.name}.src_{i}", sinks[i])
        first, on_first = self.first_output_marker()
        batched.connect("new-data", on_first)
        t0 = time.perf_counter()
        p.run(timeout=600)
        jax.block_until_ready([f.tensor(0) for f in batched.frames])
        t_end = time.perf_counter()

        check(filt.backend._degraded is None, filt.backend._degraded)
        check(len(batched.frames) == per, len(batched.frames))
        check(self.watch.count == first["xla"], "XLA compile mid-stream")
        for f in batched.frames:
            out = f.tensor(0)
            self.on_device(out, "batched filter output")
            devs = {sh.device for sh in out.addressable_shards}
            check(len(devs) == shards,
                  f"output shards on {len(devs)} device(s), expected {shards}")
        worst = 0.0
        for i, sink in enumerate(sinks):
            check(len(sink.frames) == per, (i, len(sink.frames)))
            got = np.stack([np.asarray(f.tensor(0)).reshape(-1)
                            for f in sink.frames])
            worst = max(worst, self.check_close(
                got, self.logits[i * per:(i + 1) * per],
                f"stream {i} (batch {k}) vs batch-1 logits"))
        return {"frames": k * per, "shards": shards,
                "first_frame_s": round(first["t"] - t0, 3),
                "stream_s": round(t_end - first["t"], 3),
                "rel_l2_vs_batch1": worst}

    def decode_session(self):
        import numpy as np

        from nnstreamer_tpu.serving import ContinuousBatcher

        rng = np.random.default_rng(7)
        steps = 4
        t0 = time.perf_counter()
        with ContinuousBatcher() as eng:  # shipped defaults
            t_built = time.perf_counter()
            prompt = rng.standard_normal((5, eng.d_in)).astype(np.float32)
            feeds = rng.standard_normal((steps, eng.d_in)).astype(np.float32)
            a, b = eng.open_session(timeout=60), eng.open_session(timeout=60)
            outs = {id(a): [], id(b): []}
            for sess in (a, b):
                sess.prefill(prompt)
            for sess in (a, b):
                outs[id(sess)].append(sess.get(timeout=600))
            warm = self.watch.count  # prefill bucket + step are built now
            for x in feeds:
                for sess in (a, b):
                    sess.feed(x)
                for sess in (a, b):
                    outs[id(sess)].append(sess.get(timeout=600))
            ticks = eng.ticks
            n_out = eng.n_out
        t_end = time.perf_counter()
        check(self.watch.count == warm, "XLA compile after the first step")
        ya, yb = np.stack(outs[id(a)]), np.stack(outs[id(b)])
        check(ya.shape == (steps + 1, n_out), ya.shape)
        check(np.isfinite(ya).all() and np.isfinite(yb).all(),
              "non-finite decode outputs")
        check(np.array_equal(ya, yb),
              f"identical streams diverged: max|d|={np.abs(ya - yb).max()}")
        check(not np.array_equal(ya[0], ya[-1]), "outputs do not evolve")
        return {"requests": 2 * (steps + 1), "ticks": ticks,
                "build_s": round(t_built - t0, 3),
                "stream_s": round(t_end - t_built, 3)}

    def kernels(self):
        import numpy as np

        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.decoders.bounding_boxes import PRE_NMS_TOP_K
        from nnstreamer_tpu.ops.nms import nms_keep, pallas_nms_keep
        from nnstreamer_tpu.ops.pallas_kernels import (
            _apply_chain,
            fused_arith,
            int8_matmul,
        )
        from nnstreamer_tpu.ops.quant import (
            quantize_activations,
            quantize_weight,
        )

        interpret = self.rehearsal  # on the chip Mosaic compiles every one
        rng = np.random.default_rng(11)
        s = self.sizes
        out = {}

        # fused_arith: the tensor_transform acceleration=pallas kernel on
        # one uint8 frame, the normalize chain
        chain = (("typecast", jnp.float32), ("add", -127.5), ("div", 127.5))
        x = rng.integers(0, 256, (s["image"], s["image"], 3)).astype(np.uint8)
        got = np.asarray(jax.jit(
            lambda a: fused_arith(a, chain, interpret=interpret))(x))
        want = np.asarray(jax.jit(lambda a: _apply_chain(a, chain))(x))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        out["fused_arith_max_abs_err"] = float(np.abs(got - want).max())

        # int8_matmul at the MobileNet classifier head
        w = rng.standard_normal((1280, s["classes"])).astype(np.float32) * .05
        bias = rng.standard_normal(s["classes"]).astype(np.float32)
        qw = quantize_weight(jnp.asarray(w), axis=-1)
        wq, ws = np.asarray(qw.q), np.asarray(qw.scale).reshape(1, -1)
        for m in (1, 8):
            a = rng.standard_normal((m, 1280)).astype(np.float32)
            aq, a_scale = quantize_activations(jnp.asarray(a))
            got = np.asarray(int8_matmul(aq, qw.q, a_scale,
                                         qw.scale.reshape(1, -1), bias,
                                         interpret=interpret))
            acc = np.asarray(aq).astype(np.int64) @ wq.astype(np.int64)
            want = acc.astype(np.float32) * (float(a_scale) * ws) + bias
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
            out[f"int8_matmul_m{m}_max_abs_err"] = float(
                np.abs(got - want).max())

        # NMS at the SSD decoder's K, bit for bit against the XLA form
        k = PRE_NMS_TOP_K
        bx = rng.integers(0, 60, (2, k)).astype(np.float32)
        wh = rng.integers(1, 30, (2, k)).astype(np.float32)
        valid = rng.random(k) >= 0.2
        args = tuple(jnp.asarray(v) for v in (bx[0], bx[1], wh[0], wh[1],
                                              valid))
        got = np.asarray(jax.jit(
            lambda *v: pallas_nms_keep(*v, interpret=interpret))(*args))
        want = np.asarray(jax.jit(nms_keep)(*args))
        check(np.array_equal(got, want), "pallas_nms_keep != nms_keep")
        check(0 < int(got.sum()) < int(valid.sum()), "degenerate NMS case")
        out["nms_kept"] = f"{int(got.sum())}/{k}"

        # fused attention at the ViT benchmark's head geometry (16 heads of
        # 96 in groups of four, T = 576; tiny on the CPU) against
        # full_attention in float32
        from nnstreamer_tpu.ops.fused_attention import fused_attention
        from nnstreamer_tpu.parallel.ring_attention import full_attention

        t, h, dh = (16, 4, 32) if self.rehearsal else (576, 16, 96)
        qkv = jnp.asarray(rng.standard_normal((2, t, 3 * h * dh)),
                          jnp.bfloat16)
        for causal in (False, True):
            got = np.asarray(jax.jit(lambda a: fused_attention(
                a, h, causal=causal, interpret=interpret))(qkv)
                .astype(jnp.float32))
            with jax.default_matmul_precision("highest"):
                q, k_, v = (a.reshape(2, t, h, dh) for a in jnp.split(
                    qkv.astype(jnp.float32), 3, axis=-1))
                want = np.asarray(jax.jit(lambda *a: full_attention(
                    *a, causal=causal))(q, k_, v)).reshape(2, t, h * dh)
            np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
            out[f"fused_attention_causal{int(causal)}_max_abs_err"] = float(
                np.abs(got - want).max())
        # the blocked kernel at the Laguna sliding layer's shape (16 windows
        # of 4096, 64 heads over 8, a window of 512 = one block, rot 128 by
        # the tables) as attention()'s rule lowers it, the band folded,
        # against rotate() and XLA's walk at float32, a key/value head of a
        # batch row at a time (all of them at once hold 69 GB of scores)
        from nnstreamer_tpu.models.laguna import rotary_tables
        from nnstreamer_tpu.obs.metrics import REGISTRY
        from nnstreamer_tpu.ops import fused_attention as fa

        b, t, hq, hkv, window = ((1, 512, 2, 1, 128) if self.rehearsal
                                 else (16, 4096, 64, 8, 512))
        group = hq // hkv
        q = jnp.asarray(rng.standard_normal((b, t, hq * 128)), jnp.bfloat16)
        k_, v = (jnp.asarray(rng.standard_normal((b, t, hkv * 128)),
                             jnp.bfloat16) for _ in range(2))
        tables = rotary_tables({"rope_theta": 10000}, 128, t)
        if interpret:
            got = jax.jit(lambda *a: fa.blocked_attention(
                *a, hq, hkv, window, 128, 128, interpret=True,
                rotary=tables))(q, k_, v)
        else:
            got = jax.jit(lambda *a: fa.attention(
                a[0], hq, True, k=a[1], v=a[2], n_kv_heads=hkv,
                window=window, rotary=tables))(q, k_, v)

        def by_head(a, heads):
            return a.reshape(b, t, hkv, heads * 128).transpose(
                0, 2, 1, 3).reshape(b * hkv, 1, t, heads * 128)

        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda q, k_, v: jax.lax.map(
                lambda a: fa.plain_grouped_attention(
                    *a, group, 1, causal=True, window=window),
                (by_head(fa.rotate(q, *tables, hq), group),
                 by_head(fa.rotate(k_, *tables, hkv), 1), by_head(v, 1))
            ))(*(a.astype(jnp.float32) for a in (q, k_, v)))
        want = want.reshape(b, hkv, t, group * 128).transpose(
            0, 2, 1, 3).reshape(q.shape)
        err = jnp.abs(got.astype(jnp.float32) - want)
        check(bool((err <= 2e-2 + 2e-2 * jnp.abs(want)).all()),
              "nns_blocked_attention (sliding, folded) != XLA's walk")
        out["blocked_attention_sliding_max_abs_err"] = float(err.max())
        walks = REGISTRY.get("nnstpu_attention_band_walk_total")
        out["blocked_attention_band_walk"] = {
            "/".join(key): c.value for key, c in walks.children()} \
            if walks else {}
        check(interpret or out["blocked_attention_band_walk"]
              == {"folded": 1}, "the sliding layer's band was not folded")
        # the grouped experts (ops/grouped_experts): uneven groups, one of
        # them empty, the last tile cut off, against the two ragged dots
        from nnstreamer_tpu.ops.grouped_experts import grouped_experts

        d, f, tile, sizes = ((32, 16, 16, [20, 0, 7, 23]) if self.rehearsal
                             else (256, 128, 512, [700, 0, 1500, 300]))
        m, sizes = sum(sizes), jnp.asarray(sizes, jnp.int32)
        rows = jnp.asarray(rng.standard_normal((m, d)), jnp.bfloat16)
        w_in = jnp.asarray(rng.standard_normal((4, d, 2 * f)) * d ** -0.5,
                           jnp.bfloat16)
        w_out = jnp.asarray(rng.standard_normal((4, f, d)) * f ** -0.5,
                            jnp.bfloat16)
        pair_w = jnp.asarray(rng.random(m), jnp.float32)
        got = np.asarray(jax.jit(lambda *a: grouped_experts(
            *a, tile_rows=tile, interpret=interpret))(
            rows, w_in, w_out, sizes, pair_w).astype(jnp.float32))
        gate_up = jax.lax.ragged_dot(rows, w_in, sizes,
                                     preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]).astype(
            jnp.bfloat16)
        want = np.asarray(jax.lax.ragged_dot(
            hidden, w_out, sizes, preferred_element_type=jnp.float32)
            * pair_w[:, None])
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
        out["grouped_experts_max_abs_err"] = float(np.abs(got - want).max())
        # a share's way back (parallel/moe._routed_share) as this device
        # lowers it, at the two cells' widths: an even routing (one pass) and
        # every pick held (eight passes) against the dense masked sum
        from nnstreamer_tpu.obs.metrics import REGISTRY
        from nnstreamer_tpu.parallel import moe

        n, top, f = (64, 4, 16) if self.rehearsal else (512, 8, 128)
        for d, held, total in (((128, 4, 16), (256, 6, 12)) if self.rehearsal
                               else ((6144, 16, 256), (7168, 12, 192))):
            x = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
            w_in = jnp.asarray(rng.standard_normal((held, d, 2 * f))
                               * d ** -0.5, jnp.bfloat16)
            w_out = jnp.asarray(rng.standard_normal((held, f, d))
                                * 0.5 * f ** -0.5, jnp.bfloat16)
            share = jax.jit(lambda *a, total=total: moe.routed_experts(
                *a, first=0, total=total))
            for load, among in (("even", total), ("all_held", held)):
                experts = jnp.asarray(np.argsort(
                    rng.random((n, among)), axis=-1)[:, :top], jnp.int32)
                w = jnp.asarray(rng.random((n, top)) + 0.5, jnp.float32)
                w = w / w.sum(axis=-1, keepdims=True)
                got = np.asarray(share(x, w, experts, w_in, w_out).astype(
                    jnp.float32))
                with jax.default_matmul_precision("highest"):
                    want = np.asarray(sum(
                        (w * (experts == e)).sum(axis=-1)[:, None]
                        * moe.swiglu(x.astype(jnp.float32),
                                     w_in[e].astype(jnp.float32),
                                     w_out[e].astype(jnp.float32))
                        for e in range(held)))
                np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
                out[f"share_way_back_d{d}_{load}_max_abs_err"] = float(
                    np.abs(got - want).max())
        ways = REGISTRY.get("nnstpu_moe_share_combine_total")
        out["share_way_back_lowered"] = {
            "/".join(k): c.value for k, c in ways.children()}
        check(interpret or out["share_way_back_lowered"] == {"kernel": 2},
              "a share's way back did not lower to nns_combine_rows")
        # the selection and the attention under it (ops/sparse_attention):
        # both kernels against the plain walks, the selection bit for bit
        from nnstreamer_tpu.ops import sparse_attention as sa

        t, h, dn, dr, dv, hi, top = ((256, 2, 64, 64, 128, 2, 8)
                                     if self.rehearsal
                                     else (2048, 4, 192, 64, 256, 4, 256))

        def bf16(*shape):
            return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

        q_i, k_i = bf16(2, t, hi * 128), bf16(2, t, 128)
        w_i = jnp.asarray(rng.standard_normal((2, t, hi)), jnp.float32)
        want = np.asarray(jax.jit(lambda *a: sa._select(*a, top_k=top))(
            q_i, k_i, w_i))
        got = np.asarray(jax.jit(lambda *a: sa.index_select(
            *a, top, interpret=interpret))(q_i, k_i, w_i))
        # the kernel's products and XLA's need not round alike: a key at
        # the edge may fall the other way, and no more
        out["selection_keys_apart"] = int((got != want).sum())
        check(out["selection_keys_apart"] <= want.sum() // 1000,
              "nns_index_select selects other keys than the plain walk")
        mask = jnp.asarray(want)
        q, k_n = bf16(2, t, h * (dn + dr)), bf16(2, t, h * dn)
        k_r, v = bf16(2, t, dr), bf16(2, t, h * dv)
        # the cell's head shape, pairs of heads of 192 | 64 and v 256: the
        # projections unrotated and the tables, q rotated on the kernel's
        # blocks, against rotate() and the walk through XLA at float32
        from nnstreamer_tpu.models.laguna import rotary_tables
        from nnstreamer_tpu.ops.fused_attention import rotate

        tables = rotary_tables({"rope_theta": 8000000.0}, dr, t)
        got = np.asarray(jax.jit(lambda *a: sa.sparse_attention_kernel(
            *a, h, tables, interpret=interpret))(
            q, k_n, k_r, v, mask).astype(jnp.float32))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(lambda *a: sa._plain(
                *a, n_heads=h))(*(a.astype(jnp.float32) for a in (
                    rotate(q, *tables, h, dn), k_n, rotate(k_r, *tables, 1),
                    v)), mask))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
        out["latent_sparse_attention_max_abs_err"] = float(
            np.abs(got - want).max())
        # the same attention without a selection, heads of 128 | 64 and v
        # 128, YaRN tables and a score scale of the caller's: the kernel
        # over every causal key against rotate() and the walk at float32
        dn, dv, scale = 128, 128, 0.13
        q, k_n, v = bf16(2, t, h * (dn + dr)), bf16(2, t, h * dn), \
            bf16(2, t, h * dv)
        tables = rotary_tables(
            {"rope_theta": 10000.0, "rope_type": "yarn", "factor": 32,
             "original_max_position_embeddings": t // 4, "beta_fast": 32,
             "beta_slow": 1, "attention_factor": 1.0}, dr, t)
        got = np.asarray(jax.jit(lambda *a: sa.latent_attention_kernel(
            *a, h, tables, scale, interpret=interpret))(
            q, k_n, k_r, v).astype(jnp.float32))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(lambda *a: sa._plain(
                *a, None, n_heads=h, scale=scale))(*(
                    a.astype(jnp.float32) for a in (
                        rotate(q, *tables, h, dn), k_n,
                        rotate(k_r, *tables, 1), v))))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
        out["latent_attention_max_abs_err"] = float(np.abs(got - want).max())
        out["compiled_by"] = "interpreter" if interpret else "mosaic"
        return out

    def tiling_vit(self):
        """A fresh ViT (nothing traced or lowered for it yet) whose shape
        tiles for the fused kernel (4 heads of 64, 400 tokens), and a batch
        of 8."""
        import jax.numpy as jnp
        import numpy as np

        from nnstreamer_tpu.models import vit

        model = vit.build(num_classes=16, image_size=160, patch=8,
                          d_model=256, n_heads=4, n_layers=2, batch=8,
                          dtype=jnp.float32, seed=11)
        frames = np.random.default_rng(7).standard_normal(
            (8, 160, 160, 3)).astype(np.float32)
        return model.fn(), frames

    def attention_paths(self):
        import jax
        import numpy as np

        chip = "plain" if self.rehearsal else "fused"
        fn, frames = self.tiling_vit()
        before = attention_lowerings()
        on_chip = np.asarray(jax.jit(fn)(frames))
        check(risen(before) == {chip: 2}, f"chip program: {risen(before)}")
        # what JaxBackend's [recovery] cpu_fallback retry does on this host
        fn, _ = self.tiling_vit()
        before = attention_lowerings()
        with jax.default_device(jax.devices("cpu")[0]):
            on_cpu = jax.jit(fn)(frames)
        check(risen(before) == {"plain": 2}, f"cpu program: {risen(before)}")
        check({d.platform for d in on_cpu.devices()} == {"cpu"},
              "cpu_fallback program did not run on the CPU")
        return {"chip_path": chip, "rel_l2_chip_vs_cpu": self.check_close(
            on_chip, np.asarray(on_cpu), "ViT on the chip vs on the CPU")}

    def four_chips(self):
        from nnstreamer_tpu.parallel.mesh import reset_dispatch_mesh

        os.environ["NNSTPU_MESH"] = "dp:4"
        try:
            return self.multi_stream(shards=4)
        finally:
            del os.environ["NNSTPU_MESH"]
            reset_dispatch_mesh()

    def attention_on_four_chips(self):
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        from nnstreamer_tpu.models import transformer
        from nnstreamer_tpu.parallel.mesh import make_mesh

        # the batch sharded over four chips: GSPMD partitions the program,
        # which Mosaic refuses, so every layer is full_attention
        chip = "plain" if self.rehearsal else "fused"
        fn, frames = self.tiling_vit()
        one = np.asarray(jax.jit(fn)(frames))
        fn, _ = self.tiling_vit()
        dp = make_mesh((4,), ("dp",), devices=jax.devices()[:4])
        before = attention_lowerings()
        sharded = jax.jit(fn, in_shardings=NamedSharding(dp, P("dp")))(frames)
        check(risen(before) == {"plain": 2}, f"dp:4 program: {risen(before)}")
        check(len(sharded.sharding.device_set) == 4, sharded.sharding)
        out = {"rel_l2_dp4_vs_one_chip": self.check_close(
            np.asarray(sharded), one, "ViT over dp:4 vs one chip")}

        # a pipelined encoder: the blocks run inside shard_map over the whole
        # mesh, where each chip's program is its own and holds the kernel
        kw = dict(seq_len=384, d_in=32, n_out=8, d_model=256, n_heads=4,
                  n_layers=4, causal=True, seed=5)
        pp = make_mesh((4,), ("pp",), devices=jax.devices()[:4])
        x = np.random.default_rng(9).standard_normal(
            (8, 384, 32)).astype(np.float32)
        before = attention_lowerings()
        piped = transformer.build_pipelined(pp, "pp", batch=8, **kw)
        got = np.asarray(jax.jit(piped.fn())(x))
        check(risen(before) == {chip: 1},  # one scanned block a stage
              f"pipelined program: {risen(before)}")
        want = np.asarray(jax.jit(transformer.build(batch=8, **kw).fn())(x))
        out["rel_l2_pp4_vs_one_chip"] = self.check_close(
            got, want, "pipelined encoder over pp:4 vs one chip")
        out["pipelined_path"] = chip
        return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on whatever jax finds, kernels in the "
                         "interpreter; checks control flow only and exits 3")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke.py: jax found no TPU (platform={dev.platform!r}, "
              f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
              file=sys.stderr)
        return 2

    sys.path.insert(0, HERE)
    import jaxlib

    from nnstreamer_tpu import native
    from nnstreamer_tpu.backends.exec_cache import ensure_compile_cache

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    cache_dir = ensure_compile_cache()
    smoke = Smoke(REHEARSAL if args.cpu_rehearsal else FULL,
                  rehearsal=args.cpu_rehearsal,
                  queue_backend=native.queue_backend())  # raises if broken
    header = {
        "device": device,
        "versions": {"python": sys.version.split()[0], "jax": jax.__version__,
                     "jaxlib": jaxlib.__version__, "libtpu": libtpu_version},
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0,
        "queue_backend": smoke.queue_backend,
        "sizes": smoke.sizes,
        "rehearsal": args.cpu_rehearsal,
    }
    say(f"chip_smoke {json.dumps(header)}")

    t0 = time.perf_counter()
    smoke.phase("labeling", smoke.labeling)
    smoke.phase("serving_door", smoke.serving_door)
    smoke.phase("multi_stream", smoke.multi_stream)
    smoke.phase("decode_session", smoke.decode_session)
    smoke.phase("kernels", smoke.kernels)
    smoke.phase("attention_paths", smoke.attention_paths)
    if device["count"] >= 4:
        smoke.phase("four_chips", smoke.four_chips)
        smoke.phase("attention_on_four_chips", smoke.attention_on_four_chips)
    else:
        say(f"== four_chips: not run ({device['count']} device(s))")

    say("SUMMARY " + json.dumps({
        **header, "phases": smoke.phases, "failed": smoke.failed,
        "total_s": round(time.perf_counter() - t0, 1)}))
    if smoke.failed:
        return 1
    if args.cpu_rehearsal:
        say("rehearsal finished: control flow only, not a pass")
        return 3
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
