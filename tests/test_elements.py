"""Element tests: converter, mux/merge time-sync, demux, split, aggregator —
the SSAT per-element test dirs re-done as harness tests (survey §4)."""

from fractions import Fraction

import numpy as np
import pytest

from nnstreamer_tpu import Pipeline, parse_launch
from nnstreamer_tpu.buffer import Frame, SECOND
from nnstreamer_tpu.elements.aggregator import TensorAggregator
from nnstreamer_tpu.elements.converter import TensorConverter
from nnstreamer_tpu.elements.demux import TensorDemux
from nnstreamer_tpu.elements.merge import TensorMerge
from nnstreamer_tpu.elements.mux import TensorMux
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.split import TensorSplit
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec


def frames_with_ts(arrays, dur=SECOND // 30):
    return [
        Frame.of(a, pts=i * dur, duration=dur) for i, a in enumerate(arrays)
    ]


class TestConverter:
    def test_video_passthrough_spec(self):
        p = parse_launch(
            "videotestsrc num-buffers=2 width=20 height=10 ! "
            "tensor_converter ! tensor_sink name=out collect=true"
        )
        p.run(timeout=10)
        f = p["out"].frames[0]
        assert f.tensor(0).shape == (10, 20, 3)
        assert f.tensor(0).dtype == np.uint8

    def test_frames_per_tensor_batches(self):
        data = frames_with_ts([np.full((4, 4, 3), i, np.uint8) for i in range(6)])
        p = Pipeline()
        src = p.add(DataSrc(data=data, rate=Fraction(30)))
        conv = p.add(TensorConverter(frames_per_tensor=3))
        sink = p.add(TensorSink(collect=True))
        p.link_chain(src, conv, sink)
        p.run(timeout=10)
        assert sink.num_frames == 2
        out = sink.frames[0].tensor(0)
        assert out.shape == (3, 4, 4, 3)
        assert out[1, 0, 0, 0] == 1
        # batched output rate is input rate / 3
        assert sink.sink_pads["sink"].spec.rate == Fraction(10)

    def test_octet_reinterpret(self):
        raw = np.arange(24, dtype=np.uint8)
        p = Pipeline()
        src = p.add(DataSrc(data=[raw]))
        conv = p.add(TensorConverter(input_dim="2:3", input_type="float32"))
        sink = p.add(TensorSink(collect=True))
        p.link_chain(src, conv, sink)
        p.run(timeout=10)
        out = sink.frames[0].tensor(0)
        assert out.dtype == np.float32
        assert out.shape == (3, 2)
        np.testing.assert_array_equal(
            out, np.arange(24, dtype=np.uint8).view(np.float32).reshape(3, 2)
        )

    def test_stride_strip(self):
        # upstream produced (h, padded_w, c); converter strips to width
        arr = np.zeros((4, 8, 3), np.uint8)
        arr[:, :6] = 7
        f = Frame.of(arr, width=6, stride=8)
        from nnstreamer_tpu.media import VideoSpec

        f.meta["media"] = VideoSpec(width=6, height=4)
        p = Pipeline()
        src = p.add(
            DataSrc(
                data=[f],
                spec=TensorsSpec.of(TensorSpec(dtype=np.uint8, shape=(4, 6, 3))),
            )
        )
        conv = p.add(TensorConverter())
        sink = p.add(TensorSink(collect=True))
        p.link_chain(src, conv, sink)
        p.run(timeout=10)
        out = sink.frames[0].tensor(0)
        assert out.shape == (4, 6, 3)
        assert (out == 7).all()


class TestMux:
    def _run_mux(self, streams, sync_mode="slowest", sync_option=""):
        p = Pipeline()
        mux = p.add(TensorMux(sync_mode=sync_mode, sync_option=sync_option))
        for i, frames in enumerate(streams):
            src = p.add(DataSrc(name=f"s{i}", data=frames))
            p.link(src, f"{mux.name}.sink_{i}")
        sink = p.add(TensorSink(collect=True))
        p.link(mux, sink)
        p.run(timeout=10)
        return sink

    def test_nosync_pairs(self):
        a = frames_with_ts([np.full((2,), i, np.int32) for i in range(3)])
        b = frames_with_ts([np.full((3,), 10 + i, np.int32) for i in range(3)])
        sink = self._run_mux([a, b], "nosync")
        assert sink.num_frames == 3
        f = sink.frames[0]
        assert f.num_tensors == 2
        assert f.tensor(0).shape == (2,) and f.tensor(1).shape == (3,)

    def test_slowest_waits_for_laggard(self):
        dur = SECOND // 30
        # stream a at 30fps, stream b at 15fps (every other frame)
        a = [Frame.of(np.full((1,), i, np.int32), pts=i * dur, duration=dur) for i in range(6)]
        b = [
            Frame.of(np.full((1,), 100 + i, np.int32), pts=i * 2 * dur, duration=2 * dur)
            for i in range(3)
        ]
        sink = self._run_mux([a, b], "slowest")
        # sync point follows the slower stream: roughly one output per b frame
        assert 3 <= sink.num_frames <= 4
        for f in sink.frames:
            # paired a frame should be the closest to the b frame's pts
            av, bv = int(f.tensor(0)[0]), int(f.tensor(1)[0])
            assert abs(av - (bv - 100) * 2) <= 1

    def test_spec_concatenation(self):
        a = [Frame.of(np.zeros((2,), np.float32))]
        b = [Frame.of(np.zeros((4, 4), np.uint8))]
        sink = self._run_mux([a, b], "nosync")
        spec = sink.sink_pads["sink"].spec
        assert spec.num_tensors == 2
        assert spec.tensors[0].dtype == np.float32
        assert spec.tensors[1].shape == (4, 4)

    def test_basepad_follows_base_timestamps(self):
        dur = SECOND // 30
        a = [Frame.of(np.full((1,), i, np.int32), pts=i * dur, duration=dur) for i in range(3)]
        b = [Frame.of(np.full((1,), 100 + i, np.int32), pts=i * dur, duration=dur) for i in range(3)]
        sink = self._run_mux([a, b], "basepad", sync_option="0")
        assert sink.num_frames == 3
        for i, f in enumerate(sink.frames):
            assert int(f.tensor(0)[0]) == i  # base pad frames in order

    def test_basepad_tolerance_keeps_pad_count_stable(self):
        """A pad whose head is outside tolerance contributes its LAST frame
        (reference tensor_common.c:1270+ pad->buffer) — never a combine
        round with fewer pads than linked (VERDICT weak #6)."""
        dur = SECOND // 30
        # base pad: regular 30fps; other pad: first frame aligned, second
        # frame far in the future (outside the 1-frame tolerance)
        a = [Frame.of(np.full((1,), i, np.int32), pts=i * dur, duration=dur) for i in range(3)]
        b = [
            Frame.of(np.full((1,), 100, np.int32), pts=0, duration=dur),
            Frame.of(np.full((1,), 101, np.int32), pts=50 * dur, duration=dur),
        ]
        sink = self._run_mux([a, b], "basepad", sync_option=f"0:{dur}")
        assert sink.num_frames >= 2
        for f in sink.frames:
            assert f.num_tensors == 2, "combine round lost a pad"
        # rounds 2..n reuse pad b's last in-tolerance frame (value 100)
        assert int(sink.frames[1].tensor(1)[0]) == 100


class TestMerge:
    def test_linear_concat_innermost(self, rng):
        a = rng.standard_normal((4, 2)).astype(np.float32)
        b = rng.standard_normal((4, 3)).astype(np.float32)
        p = Pipeline()
        merge = p.add(TensorMerge(mode="linear", option="0", sync_mode="nosync"))
        s0 = p.add(DataSrc(name="m0", data=[a]))
        s1 = p.add(DataSrc(name="m1", data=[b]))
        p.link(s0, f"{merge.name}.sink_0")
        p.link(s1, f"{merge.name}.sink_1")
        sink = p.add(TensorSink(collect=True))
        p.link(merge, sink)
        p.run(timeout=10)
        out = np.asarray(sink.frames[0].tensor(0))
        np.testing.assert_array_equal(out, np.concatenate([a, b], axis=1))

    def test_rank_mismatch_fails(self):
        from nnstreamer_tpu import NegotiationError

        p = Pipeline()
        merge = p.add(TensorMerge(option="0", sync_mode="nosync"))
        s0 = p.add(DataSrc(name="m0", data=[np.zeros((2, 2), np.float32)]))
        s1 = p.add(DataSrc(name="m1", data=[np.zeros((2, 2, 2), np.float32)]))
        p.link(s0, f"{merge.name}.sink_0")
        p.link(s1, f"{merge.name}.sink_1")
        sink = p.add(TensorSink())
        p.link(merge, sink)
        with pytest.raises(NegotiationError):
            p.start()
        p.stop()


class TestDemux:
    def test_split_tensors_to_pads(self, rng):
        a, b, c = (rng.standard_normal((i + 1,)).astype(np.float32) for i in range(3))
        p = Pipeline()
        src = p.add(DataSrc(data=[Frame.of(a, b, c)]))
        demux = p.add(TensorDemux())
        p.link(src, demux)
        sinks = []
        for i in range(3):
            s = p.add(TensorSink(name=f"out{i}", collect=True))
            p.link(f"{demux.name}.src_{i}", s)
            sinks.append(s)
        p.run(timeout=10)
        for s, expected in zip(sinks, (a, b, c)):
            np.testing.assert_array_equal(s.frames[0].tensor(0), expected)

    def test_tensorpick(self, rng):
        a, b, c = (rng.standard_normal((3,)).astype(np.float32) for _ in range(3))
        p = Pipeline()
        src = p.add(DataSrc(data=[Frame.of(a, b, c)]))
        demux = p.add(TensorDemux(tensorpick="2,0"))
        p.link(src, demux)
        s0 = p.add(TensorSink(name="p0", collect=True))
        s1 = p.add(TensorSink(name="p1", collect=True))
        p.link(f"{demux.name}.src_0", s0)
        p.link(f"{demux.name}.src_1", s1)
        p.run(timeout=10)
        np.testing.assert_array_equal(s0.frames[0].tensor(0), c)
        np.testing.assert_array_equal(s1.frames[0].tensor(0), a)


class TestSplit:
    def test_tensorseg(self, rng):
        x = rng.integers(0, 255, (4, 4, 3), dtype=np.uint8)  # NNS 3:4:4
        p = Pipeline()
        src = p.add(DataSrc(data=[x]))
        # split along NNS dim2 (height): 1:4:4 is wrong way; use segs 3:4:1 etc.
        split = p.add(TensorSplit(tensorseg="3:4:1,3:4:3"))
        p.link(src, split)
        s0 = p.add(TensorSink(name="g0", collect=True))
        s1 = p.add(TensorSink(name="g1", collect=True))
        p.link(f"{split.name}.src_0", s0)
        p.link(f"{split.name}.src_1", s1)
        p.run(timeout=10)
        np.testing.assert_array_equal(s0.frames[0].tensor(0), x[:1])
        np.testing.assert_array_equal(s1.frames[0].tensor(0), x[1:])

    def test_device_array_cut_on_the_host_for_host_consumers(self, rng):
        """A filter's output split for host consumers is cut on the host
        (one device→host copy, numpy views: no device program queued
        behind the next round); a branch into a device filter keeps the
        cut on the device."""
        from nnstreamer_tpu.backends.jax_backend import JaxModel
        from nnstreamer_tpu.elements.filter import TensorFilter

        x = rng.standard_normal((6, 4)).astype(np.float32)
        model = JaxModel(apply=lambda params, v: v + 1.0)
        p = Pipeline()
        src = p.add(DataSrc(data=[x]))
        filt = p.add(TensorFilter(framework="jax", model=model))
        split = p.add(TensorSplit(tensorseg="4:2,4:4"))
        host = p.add(TensorSink(name="host", collect=True))
        filt2 = p.add(TensorFilter(framework="jax", model=model))
        dev = p.add(TensorSink(name="dev", collect=True))
        p.link_chain(src, filt, split)
        p.link(f"{split.name}.src_0", host)
        p.link(f"{split.name}.src_1", filt2)
        p.link(filt2, dev)
        p.run(timeout=60)
        assert split._to_host is False  # one branch reaches a device filter
        np.testing.assert_allclose(np.asarray(dev.frames[0].tensor(0)),
                                   x[2:] + 2.0)

        p2 = Pipeline()
        src = p2.add(DataSrc(data=[x]))
        filt = p2.add(TensorFilter(framework="jax", model=model))
        split = p2.add(TensorSplit(tensorseg="4:2,4:4"))
        s0 = p2.add(TensorSink(name="g0", collect=True))
        s1 = p2.add(TensorSink(name="g1", collect=True))
        p2.link_chain(src, filt, split)
        p2.link(f"{split.name}.src_0", s0)
        p2.link(f"{split.name}.src_1", s1)
        p2.run(timeout=60)
        assert split._to_host is True
        for sink, rows in ((s0, x[:2]), (s1, x[2:])):
            got = sink.frames[0].tensor(0)
            assert isinstance(got, np.ndarray)
            np.testing.assert_allclose(got, rows + 1.0)


class TestAggregator:
    def test_tumbling_window(self):
        data = frames_with_ts([np.full((2,), i, np.float32) for i in range(6)])
        p = Pipeline()
        src = p.add(DataSrc(data=data))
        agg = p.add(TensorAggregator(frames_out=3, frames_dim=3))
        sink = p.add(TensorSink(collect=True))
        p.link_chain(src, agg, sink)
        p.run(timeout=10)
        assert sink.num_frames == 2
        out = sink.frames[0].tensor(0)
        assert out.shape == (3, 2)
        np.testing.assert_array_equal(out[:, 0], [0, 1, 2])

    def test_sliding_window_with_flush(self):
        data = frames_with_ts([np.full((1,), i, np.float32) for i in range(5)])
        p = Pipeline()
        src = p.add(DataSrc(data=data))
        agg = p.add(TensorAggregator(frames_out=3, frames_flush=1, frames_dim=3))
        sink = p.add(TensorSink(collect=True))
        p.link_chain(src, agg, sink)
        p.run(timeout=10)
        # windows: [0,1,2], [1,2,3], [2,3,4]
        assert sink.num_frames == 3
        got = [list(np.asarray(f.tensor(0))[:, 0]) for f in sink.frames]
        assert got == [[0, 1, 2], [1, 2, 3], [2, 3, 4]]

    def test_frames_in_splits(self):
        # each buffer holds 2 frames along axis 0 (NNS dim 1 for rank-2)
        data = frames_with_ts(
            [np.array([[i * 2], [i * 2 + 1]], np.float32) for i in range(3)]
        )
        p = Pipeline()
        src = p.add(DataSrc(data=data))
        agg = p.add(TensorAggregator(frames_in=2, frames_out=3, frames_dim=1))
        sink = p.add(TensorSink(collect=True))
        p.link_chain(src, agg, sink)
        p.run(timeout=10)
        assert sink.num_frames == 2
        np.testing.assert_array_equal(
            np.asarray(sink.frames[0].tensor(0))[:, 0], [0, 1, 2]
        )
        np.testing.assert_array_equal(
            np.asarray(sink.frames[1].tensor(0))[:, 0], [3, 4, 5]
        )


class TestTestSources:
    """videotestsrc/audiotestsrc pattern + timing contracts (the gtest
    pipelines' workhorse sources, unittest_sink.cpp:972+)."""

    def test_video_patterns_deterministic(self):
        from nnstreamer_tpu.elements.testsrc import VideoTestSrc

        for pattern, check in [
            ("black", lambda a: (a == 0).all()),
            ("white", lambda a: (a == 255).all()),
            ("random", lambda a: a.std() > 10),
            ("smpte", lambda a: a.std() > 10),
        ]:
            src = VideoTestSrc(pattern=pattern, width=16, height=12)
            f0 = src._make_frame(0)
            assert f0.shape == (12, 16, 3) and f0.dtype == np.uint8
            assert check(f0), pattern
            # deterministic per index
            np.testing.assert_array_equal(f0, VideoTestSrc(
                pattern=pattern, width=16, height=12)._make_frame(0))

    def test_video_timestamps_follow_framerate(self):
        p = parse_launch(
            "videotestsrc num-buffers=3 width=8 height=8 framerate=50/1 ! "
            "tensor_converter ! tensor_sink name=out collect=true"
        )
        p.run(timeout=30)
        sink = p.get_by_name("out")
        pts = [f.pts for f in sink.frames]
        assert pts == [0, 20_000_000, 40_000_000]  # 50 fps → 20 ms

    def test_audio_sine_properties(self):
        from nnstreamer_tpu.buffer import SECOND
        from nnstreamer_tpu.elements.testsrc import AudioTestSrc

        src = AudioTestSrc(num_buffers=2, samplesperbuffer=160, channels=2,
                           rate=16000, freq=1000.0)
        frames = list(src.frames())
        assert len(frames) == 2
        a = frames[0].tensor(0)
        assert a.shape == (160, 2) and a.dtype == np.int16
        assert a.std() > 1000  # actually a sine, not silence
        assert frames[1].pts == 160 * SECOND // 16000
        silent = AudioTestSrc(num_buffers=1, wave="silence")
        assert np.asarray(list(silent.frames())[0].tensor(0)).std() == 0


class TestProfilingStats:
    def test_stats_summarize_invokes(self):
        from nnstreamer_tpu.backends.jax_backend import JaxModel
        from nnstreamer_tpu.elements.filter import TensorFilter
        from nnstreamer_tpu.elements.sink import TensorSink
        from nnstreamer_tpu.elements.testsrc import DataSrc
        from nnstreamer_tpu.utils import profiling

        profiling.reset()
        model = JaxModel(apply=lambda p, x: x + 1.0)
        pipe = Pipeline()
        src = pipe.add(DataSrc(data=[np.ones((4,), np.float32)] * 6))
        filt = pipe.add(TensorFilter(framework="jax", model=model, name="f"))
        sink = pipe.add(TensorSink())
        pipe.link_chain(src, filt, sink)
        with profiling.profiled():
            pipe.run(timeout=60)
        stats = pipe.stats()
        assert "f" in stats
        s = stats["f"]
        assert s["count"] == 6
        assert 0 < s["min_ms"] <= s["p50_ms"] <= s["max_ms"]
        profiling.reset()
        assert profiling.stats() == {}


class TestMediaSpecs:
    """Media-type → tensor-caps derivation (the tensor_converter.c:930-1135
    per-media config analog)."""

    def test_video_formats_and_batching(self):
        from nnstreamer_tpu.media import VideoSpec

        v = VideoSpec(format="RGB", width=8, height=4, rate=Fraction(30))
        assert v.channels == 3
        s = v.tensor_spec()
        assert s.tensors[0].shape == (4, 8, 3) and s.rate == Fraction(30)
        s4 = v.tensor_spec(frames_per_tensor=4)
        assert s4.tensors[0].shape == (4, 4, 8, 3)
        assert s4.rate == Fraction(30, 4)  # batched stream rate drops
        assert VideoSpec(format="GRAY8", width=2, height=2).channels == 1
        assert VideoSpec(format="BGRx", width=2, height=2).channels == 4
        with pytest.raises(ValueError, match="format"):
            VideoSpec(format="YUY2")

    def test_audio_formats(self):
        from nnstreamer_tpu.media import AudioSpec

        a = AudioSpec(format="F32LE", channels=2, sample_rate=16000)
        assert a.dtype == np.float32
        s = a.tensor_spec(frames_per_tensor=160)
        assert s.tensors[0].shape == (160, 2)
        assert s.rate == Fraction(16000, 160)
        with pytest.raises(ValueError, match="format"):
            AudioSpec(format="MP3")

    def test_text_and_octet(self):
        from nnstreamer_tpu.media import OctetSpec, TextSpec
        from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

        t = TextSpec(size=16).tensor_spec()
        assert t.tensors[0].shape == (16,) and t.tensors[0].dtype == np.uint8
        custom = TensorsSpec.of(TensorSpec(dtype=np.int16, shape=(3, 2)))
        assert OctetSpec(spec=custom).tensor_spec() is custom
        with pytest.raises(ValueError, match="octet"):
            OctetSpec().tensor_spec()
