"""From the profiler's trace to numbers: the one reduction every PR shares.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain lists of ``Op`` per device; everything after that is arithmetic on
those lists, tested on hand-built events (``tests/benchmark``).

On a TPU the device planes are ``/device:TPU:<n>``; the line ``XLA Modules``
holds one event per run of a whole executable (``jit_flat_fn(<id>)``) and
``XLA Ops`` one per HLO op, whose *name* is the HLO instruction's text with
its shapes (``%fusion.66 = bf16[32,16,1369]{...} fusion(bf16[32,16,1369,1369]
{...} %x, ...)``); ``Async XLA Ops`` (copy-start/done pairs) overlap those
and are not counted.  The measured slice runs from the start of the second
run of the model's executable in the trace (the first is as a rule cut by the
trace's start) to the start of the last: a whole number of steps with the
gaps between them, so that neither the trace's ragged ends nor a cut step
count as idle or busy.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple


class Op(NamedTuple):
    name: str           # short: opcode and result shape, summed over layers
    start_ns: float
    dur_ns: float
    text: str = ""      # the HLO instruction as the trace gives it, shapes and all

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class DeviceTrace(NamedTuple):
    device: str
    modules: List[Op]   # whole executables
    ops: List[Op]       # HLO ops


MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def short_name(hlo: str) -> str:
    """``%fusion.66 = bf16[32,16,1369]{2,1,0:T(8,128)} fusion(...)`` ->
    ``fusion bf16[32,16,1369]``: the instruction's name without its number and
    its result shape without layouts, so that the same op of every layer sums
    under one name."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return re.sub(r"\(\d+\)$", "", hlo)[:120]
    root = re.sub(r"[.\d]+$", "", head.lstrip("%"))
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):  # the result type ends at the first space
        if ch in "({[":            # that no bracket encloses
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == " " and depth == 0:
            end = i
            break
    shape = re.sub(r"\{[^{}]*\}", "", rest[:end])
    return f"{root} {shape}"[:120]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> List[DeviceTrace]:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_profile(profile) -> List[DeviceTrace]:
    """The device lines of a ``jax.profiler.ProfileData``."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        modules: List[Op] = []
        ops: List[Op] = []
        for line in plane.lines:
            if line.name not in (MODULE_LINE, OP_LINE):
                continue
            into = modules if line.name == MODULE_LINE else ops
            for ev in line.events:
                into.append(Op(short_name(ev.name), float(ev.start_ns),
                               float(ev.duration_ns), ev.name))
        modules.sort(key=lambda o: o.start_ns)
        ops.sort(key=lambda o: o.start_ns)
        out.append(DeviceTrace(plane.name, modules, ops))
    return out


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clip(ops: Sequence[Op], w0: float, w1: float) -> List[Tuple[float, float]]:
    return [(max(o.start_ns, w0), min(o.end_ns, w1)) for o in ops
            if o.end_ns > w0 and o.start_ns < w1]


def idle_gaps(ops: Sequence[Op], w0: float, w1: float) -> List[Tuple[str, float]]:
    """Every stretch of ``[w0, w1)`` in which no op ran, longest first, named
    by the op that ended before it (``after:<op>``)."""
    gaps: List[Tuple[str, float]] = []
    edge, last = w0, "window_start"
    for o in sorted((o for o in ops if o.end_ns > w0 and o.start_ns < w1),
                    key=lambda o: o.start_ns):
        if o.start_ns > edge:
            gaps.append((f"after:{last}", o.start_ns - edge))
        if o.end_ns > edge:
            edge, last = o.end_ns, o.name
    if w1 > edge:
        gaps.append((f"after:{last}", w1 - edge))
    return sorted(gaps, key=lambda g: -g[1])


def model_runs(modules: Sequence[Op]) -> List[Op]:
    """The runs of the model's executable: of the module names in the trace,
    the one with the most summed time (warm-up is over, and the only other
    programs of a streaming window are transfers and slices)."""
    by_name: Dict[str, float] = {}
    for m in modules:
        by_name[m.name] = by_name.get(m.name, 0.0) + m.dur_ns
    if not by_name:
        return []
    top = max(by_name, key=by_name.get)
    return [m for m in modules if m.name == top]


def has_trailing_dims(text: str, dims: Tuple[int, ...]) -> bool:
    """Does ``text`` name an array whose last dims are ``dims``?  HLO writes
    shapes as ``bf16[32,16,1369,1369]``; layouts in braces do not count."""
    want = ",".join(str(d) for d in dims)
    return re.search(r"\[(?:\d+,)*" + re.escape(want) + r"\]", text) is not None


def carries(op: Op, mark: Dict[str, Sequence]) -> bool:
    """Does ``op`` carry ``mark``?  A mark names a part of the program by
    what the trace shows of it: ``names``, prefixes of an op's short name (a
    kernel's own name, as ``nns_fused_attention``), and/or ``dims``, the
    trailing dims of an array the op reads or writes (attention's ``T x T``
    scores where XLA keeps them in HBM).  Either is enough."""
    return (any(op.name.startswith(n) for n in mark.get("names", ()))
            or any(has_trailing_dims(op.text, tuple(d))
                   for d in mark.get("dims", ())))


class Slice(NamedTuple):
    steps: int
    window_ns: float
    busy_ns: float
    model_ns: float          # summed device time of the model's executable
    marked_ns: Dict[str, float]  # label -> summed device time of its ops
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def reduce_device(dev: DeviceTrace,
                  marks: Optional[Dict[str, Dict[str, Sequence]]] = None,
                  top: int = 10) -> Optional[Slice]:
    """The numbers of one device over the whole steps its trace holds, or
    ``None`` where it holds fewer than two whole starts of the model's
    executable after the first, which the trace's start may have cut.
    ``marks`` (a model kind's ``marks(sizes)``) maps a label to what marks an
    op as that part's; a label no op carries is left out of ``marked_ns``."""
    runs = model_runs(dev.modules)[1:]
    if len(runs) < 2:
        return None
    w0, w1 = runs[0].start_ns, runs[-1].start_ns
    steps = runs[:-1]
    ops = [o for o in dev.ops if w0 <= o.start_ns < w1]
    busy_from = ops if ops else [m for m in dev.modules
                                 if w0 <= m.start_ns < w1]
    marks = marks or {}
    marked: Dict[str, float] = {}
    by_name: Dict[str, float] = {}
    for o in ops:
        by_name[o.name] = by_name.get(o.name, 0.0) + o.dur_ns
        for label, mark in marks.items():
            if carries(o, mark):
                marked[label] = marked.get(label, 0.0) + o.dur_ns
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return Slice(
        steps=len(steps), window_ns=w1 - w0,
        busy_ns=union_ns(clip(busy_from, w0, w1)),
        model_ns=sum(min(m.end_ns, w1) - m.start_ns for m in steps),
        marked_ns=marked,
        device_ops=[(n, t / 1e9) for n, t in ranked],
        idle_gaps=[(n, t / 1e9) for n, t in idle_gaps(busy_from, w0, w1)[:top]],
    )


def reduce_trace(devices: Sequence[DeviceTrace],
                 marks: Optional[Dict[str, Dict[str, Sequence]]] = None
                 ) -> List[Slice]:
    return [s for s in (reduce_device(d, marks) for d in devices)
            if s is not None]
