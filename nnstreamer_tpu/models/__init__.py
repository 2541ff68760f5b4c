"""Built-in model zoo: MobileNet-v2 labeling, SSD-MobileNet boxes, PoseNet
heatmaps, LSTM recurrence, batched multi-stream classification, and the
benchmark's five configurations (``vit``, ``laguna``, ``glm_dsa``,
``axk1``, ``falcon_h1``: a launch string's ``builder=<name>`` imports the
module of that name here)."""

from . import audio_cnn, lstm, mobilenet_v2, posenet, ssd_mobilenet, transformer  # noqa: F401
