"""filesrc / filesink: the SSAT backbone endpoints (raw-byte streams in,
byte-exact golden capture out — ``runTest.sh`` pipelines are built on
these)."""

import numpy as np
import pytest

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.elements.file_io import FileSink, FileSrc
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.elements.transform import TensorTransform


class TestFileSrc:
    def test_whole_file_one_frame(self, tmp_path):
        raw = bytes(range(256)) * 4
        p_in = tmp_path / "frames.raw"
        p_in.write_bytes(raw)
        p = Pipeline()
        src = p.add(FileSrc(location=str(p_in)))
        sink = p.add(TensorSink(collect=True))
        p.link_chain(src, sink)
        p.run(timeout=30)
        assert len(sink.frames) == 1
        t = sink.frames[0].tensor(0)
        assert t.dtype == np.uint8 and t.shape == (1024,)
        assert bytes(t.tobytes()) == raw

    def test_blocksize_chunks_and_partial_tail_dropped(self, tmp_path):
        p_in = tmp_path / "frames.raw"
        p_in.write_bytes(bytes(100))  # 3 full 30-byte chunks + 10 tail
        p = Pipeline()
        src = p.add(FileSrc(location=str(p_in), blocksize=30))
        sink = p.add(TensorSink(collect=True))
        p.link_chain(src, sink)
        p.run(timeout=30)
        assert [f.tensor(0).shape for f in sink.frames] == [(30,)] * 3

    def test_num_buffers_limits(self, tmp_path):
        p_in = tmp_path / "frames.raw"
        p_in.write_bytes(bytes(100))
        p = Pipeline()
        src = p.add(FileSrc(location=str(p_in), blocksize=10, num_buffers=4))
        sink = p.add(TensorSink(collect=True))
        p.link_chain(src, sink)
        p.run(timeout=30)
        assert len(sink.frames) == 4

    def test_npy_typed_load(self, tmp_path):
        arr = np.arange(24, dtype=np.float32).reshape(4, 6)
        p_in = tmp_path / "x.npy"
        np.save(p_in, arr)
        p = Pipeline()
        src = p.add(FileSrc(location=str(p_in)))
        sink = p.add(TensorSink(collect=True))
        p.link_chain(src, sink)
        p.run(timeout=30)
        np.testing.assert_array_equal(sink.frames[0].tensor(0), arr)
        assert src.output_spec().tensors[0].shape == (4, 6)

    def test_missing_location_rejected(self):
        with pytest.raises(ValueError, match="location"):
            FileSrc()


class TestFileSink:
    def test_golden_capture_byte_exact(self, tmp_path):
        """datasrc → transform → filesink, then compare bytes against an
        independent numpy computation (the runTest.sh golden pattern)."""
        frames = [np.full((8,), i, np.uint8) for i in range(5)]
        out = tmp_path / "out.bin"
        p = Pipeline()
        src = p.add(DataSrc(data=[f.copy() for f in frames]))
        tr = p.add(TensorTransform(mode="arithmetic", option="mul:2",
                                   acceleration=False))
        sink = p.add(FileSink(location=str(out)))
        p.link_chain(src, tr, sink)
        p.run(timeout=30)
        assert sink.num_frames == 5
        expected = b"".join((f * 2).tobytes() for f in frames)
        assert out.read_bytes() == expected

    def test_roundtrip_src_to_sink(self, tmp_path):
        raw = np.random.default_rng(0).integers(0, 256, 300).astype(np.uint8)
        p_in, p_out = tmp_path / "in.raw", tmp_path / "out.raw"
        p_in.write_bytes(raw.tobytes())
        p = Pipeline()
        src = p.add(FileSrc(location=str(p_in), blocksize=50))
        sink = p.add(FileSink(location=str(p_out)))
        p.link_chain(src, sink)
        p.run(timeout=30)
        assert p_out.read_bytes() == raw.tobytes()

    def test_missing_location_rejected(self):
        with pytest.raises(ValueError, match="location"):
            FileSink()
