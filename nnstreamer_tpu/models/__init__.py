"""Built-in model zoo: MobileNet-v2 labeling, SSD-MobileNet boxes, PoseNet
heatmaps, LSTM recurrence, batched multi-stream classification, and the
benchmark's four configurations (``vit``, ``laguna``, ``glm_dsa``,
``axk1``)."""

from . import audio_cnn, lstm, mobilenet_v2, posenet, ssd_mobilenet, transformer  # noqa: F401
