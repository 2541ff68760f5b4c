"""``BENCHMARK.json`` and the data files it names.

Nothing here knows a cell, a configuration, a mix or a metric by name: each
is found by the name the manifest gives it,

- ``benchmark/configs/<config>.json`` (sizes; ``kind`` names
  ``benchmark/model_kinds/<kind>.py``, ``reference`` names
  ``benchmark/references/<reference>.py``),
- ``benchmark/traffic/<traffic>.json`` (parameters; ``kind`` names
  ``benchmark/traffic_kinds/<kind>.py``),
- ``benchmark/layer_metrics/<metric>.json`` (``reader`` names
  ``benchmark/layer_metrics/<reader>.py``, ``function`` the callable in it).

What each has to offer, whatever the model and whatever the traffic
(``MODEL_KIND`` ... ``TRAFFIC_KEYS`` below; ``tests/benchmark`` holds every
entry to them, and ``tests/benchmark/fixtures/second_kind`` is a kind of each
that is no ViT and no camera):

A configuration file: ``source``, ``kind``, ``reference``, ``dtype`` (of the
served weights), ``weights_seed``, ``reduced``, ``assumed``, ``limits`` and
``rehearsal_limits`` (name -> limit of each number ``check`` compares),
and whatever its kind reads (the ``vit`` kind: ``build``, ``rehearsal``).

A model kind, over ``sizes = kind.sizes(cfg, rehearsal)``:

- ``init_weights(sizes, seed)``: a pytree of host ``numpy`` arrays in the
  configuration's ``dtype`` (plain ints beside them are fine), the same for
  the same seed; ``param_count(sizes)``: their sizes' sum;
- ``build_program(sizes, weights, batch, control=False)``: the system under
  test, a ``JaxModel`` at batch ``batch``; ``control=True`` the step below
  the configuration's precision, there to be refused;
- ``frame_shape(sizes)``: one client's frame, without the batch;
- ``frame_flops(sizes)["total"]``: FLOPs a frame needs (``step_mfu``);
- ``marks(sizes)``: label -> ``{"names": [...], "dims": [[...]]}``, what
  marks an op of the trace as that part's (``trace_reduce.carries``); for a
  label ``x`` with a roofline, ``x_work(sizes)`` -> ``{"flops", "bytes"}`` a
  frame (``layer_metrics/device_trace.roofline``).  May be empty.  The
  metric ``x_roofline`` lists under ``workloads`` the cells whose kind marks
  ``x``, so that no cell of another kind is asked for it.

A reference: ``forward(sizes, cfg, weights, frames)`` -> float32 rows, one a
frame; imports nothing of the program.

A traffic file: ``kind``, ``streams`` (the model's batch and the frames a
step finishes), ``rehearsal`` (what a CPU run overrides), and whatever its
kind reads.  A traffic kind:

- ``run(mix, model, cfg, kind, sizes, seed, seconds, trace_dir,
  break_output)`` -> a result with ``t0_ns``, ``t1_ns``, ``push_ns``,
  ``window`` (the end-to-end values and ``attempted``), ``failed``,
  ``fail_notes``, ``drained``, ``degraded``, ``trace_dir``
  (``traffic_kinds/closed_loop.Result`` is one);
- ``per_frame_faults(result)`` and ``sample(result, mix, seed)`` ->
  ``(frames, program_rows, picks)``: what is compared once the window has
  closed (``closed_loop`` has ``sample`` for any closed loop, and a
  ``per_frame_faults`` for graphs that end in the ``image_labeling`` decoder:
  label and score against the argmax of the frame's own logits row; a kind
  with another decoder counts its routing faults itself);
- ``example_input(mix, cfg, kind, sizes, batch)`` -> ``(shape, dtype,
  front)``: what the executable takes (``compile_rehearsal.py``).

A reader: ``function(ctx)`` -> a number, or ``None`` where it finds nothing
to read (never 0 for a share).  ``ctx``: ``result``, ``kind``, ``sizes``,
``chips``, ``frames_per_step``, ``notes``, and after the trace is reduced
``slices`` (``trace_reduce.Slice``, ``marked_ns`` by label) and ``peak``.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# what run.py, compile_rehearsal.py and the readers call, by module
MODEL_KIND = ("sizes", "init_weights", "param_count", "build_program",
              "frame_shape", "frame_flops", "marks")
TRAFFIC_KIND = ("run", "per_frame_faults", "sample", "example_input")
REFERENCE = ("forward",)
CONFIG_KEYS = ("source", "kind", "reference", "dtype", "weights_seed",
               "reduced", "assumed", "limits", "rehearsal_limits")
TRAFFIC_KEYS = ("kind", "streams", "rehearsal")


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts), "r", encoding="utf-8") as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    return load_json(root, "BENCHMARK.json")


def find(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}: "
                   f"{[e['name'] for e in entries]}")


def load_config(manifest: Dict[str, Any], name: str,
                root: str = ROOT) -> Dict[str, Any]:
    entry = find(manifest["configs"], name, "configuration")
    cfg = load_json(root, entry["file"])
    cfg["name"] = name
    return cfg


def load_traffic(name: str) -> Dict[str, Any]:
    mix = load_json(HERE, "traffic", f"{name}.json")
    mix["name"] = name
    return mix


def load_layer_metric(name: str) -> Dict[str, Any]:
    spec = load_json(HERE, "layer_metrics", f"{name}.json")
    spec["name"] = name
    return spec


def module(package: str, name: str):
    """``benchmark/<package>/<name>.py``, imported by name."""
    return importlib.import_module(f"benchmark.{package}.{name}")


def cell_metrics(manifest: Dict[str, Any], group: str,
                 workload: str) -> List[Dict[str, Any]]:
    """The metrics of ``group`` (``end_to_end``/``per_layer``) that the cell
    ``workload`` reports: those with no ``workloads`` key, or that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]
