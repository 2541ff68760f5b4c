#!/usr/bin/env bash
# Execute the EXACT steps of .github/workflows/ci.yml on this host and tee
# the transcript to CI_RUN_<date>.log — the executed-once proof the r3
# verdict asked for (row 42: config existed but had never run anywhere).
#
# Documented divergences from the YAML (everything else runs verbatim):
# - the dependency-install step is skipped (deps baked into this image;
#   `pip install` unavailable);
# - every step runs on the CPU (JAX_PLATFORMS=cpu); the chip is reached
#   only through the builder's tool, where `python chip_smoke.py` goes
#   first (CI has no benchmark step: `benchmark/run.py` needs the chip).
# Exit code 0 = the workflow would have passed.
set -uo pipefail
cd "$(dirname "$0")/.."
LOG="${1:-CI_RUN_$(date +%Y%m%d).log}"
: >"$LOG"

run_step() {
  local name="$1"; shift
  echo "=== STEP: $name ===" | tee -a "$LOG"
  local t0=$SECONDS
  if "$@" >>"$LOG" 2>&1; then
    echo "--- PASS (${name}, $((SECONDS - t0))s)" | tee -a "$LOG"
  else
    echo "--- FAIL (${name}, $((SECONDS - t0))s)" | tee -a "$LOG"
    echo "=== CI RESULT: FAIL ===" | tee -a "$LOG"
    exit 1
  fi
}

echo "ci run: $(date '+%Y-%m-%d %H:%M:%S') host=$(uname -sr) python=$(python -V 2>&1)" | tee -a "$LOG"

run_step "Build native runtime + C ABI (g++ smoke)" \
  python -c "from nnstreamer_tpu.native.capi import build_capi; print(build_capi())"

run_step "Run test suite with coverage gate" \
  env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python tools/coverage_tool.py tests/ -q

run_step "Coverage floor check" python - <<'PY'
floor = 75.0
last = open("build/coverage.txt").read().strip().splitlines()[-1]
pct = float(last.split()[-1].rstrip("%"))
print(f"coverage {pct:.1f}% (floor {floor}%)")
raise SystemExit(0 if pct >= floor else 1)
PY

run_step "Static analysis (nnslint contract gate: zero new findings)" \
  python tools/nnslint.py

run_step "Static analysis (lockdep smoke: seeded ABBA + cycle-clean pipeline)" \
  env NNSTPU_LOCKDEP=1 python - <<'PY'
import threading
import time

import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.analysis import lockdep
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.queue import Queue
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc

assert lockdep.installed(), "NNSTPU_LOCKDEP=1 did not install the verifier"

# 1) the detector detects: a seeded ABBA cycle must be reported
# (separate lines: lockdep keys locks by allocation site)
a = threading.Lock()
b = threading.Lock()
def ab():
    with a:
        with b:
            time.sleep(0.001)
def ba():
    with b:
        with a:
            time.sleep(0.001)
for fn in (ab, ba):
    t = threading.Thread(target=fn)
    t.start()
    t.join(timeout=30)
rep = lockdep.report()
assert len(rep["cycles"]) == 1, lockdep.format_report()

# 2) the runtime is clean: a real queue+filter pipeline (source thread,
# queue worker, dispatch chain, watchdoggable state machinery) must
# produce zero cycles and zero blocking-calls-under-lock
lockdep.reset()
got = []
p = Pipeline(name="ci_lockdep")
src = p.add(DataSrc(data=[np.full(4, i, np.float32) for i in range(16)],
                    name="s"))
q = p.add(Queue(max_size_buffers=8, name="q"))
filt = p.add(TensorFilter(framework="custom", model=lambda x: x * 2,
                          name="f"))
p.link_chain(src, q, filt, p.add(TensorSink(callback=got.append,
                                            name="out")))
p.run(timeout=120)
assert len(got) == 16, got
rep = lockdep.report()
assert rep["cycles"] == [], lockdep.format_report()
assert rep["blocking_calls"] == [], lockdep.format_report()

# 3) the dispatcher-lane runtime is clean too: the same pipeline on
# event-loop lanes (ready-rings, arm/run locks, helper promotion, the
# backpressure help path) must add its lock sites without a single new
# order cycle or blocking call under lock
import os
lockdep.reset()
os.environ["NNSTPU_DISPATCH_LANES"] = "2"
got2 = []
p2 = Pipeline(name="ci_lockdep_lanes")
src2 = p2.add(DataSrc(data=[np.full(4, i, np.float32) for i in range(16)],
                      name="s"))
q2 = p2.add(Queue(max_size_buffers=4, name="q"))
filt2 = p2.add(TensorFilter(framework="custom", model=lambda x: x * 2,
                            name="f"))
p2.link_chain(src2, q2, filt2, p2.add(TensorSink(callback=got2.append,
                                                 name="out")))
p2.run(timeout=120)
del os.environ["NNSTPU_DISPATCH_LANES"]
assert len(got2) == 16, got2
rep2 = lockdep.report()
assert rep2["cycles"] == [], lockdep.format_report()
assert rep2["blocking_calls"] == [], lockdep.format_report()

# 4) whole-segment compilation is clean: a jax filter with a decoder
# folded into its program (graph/segments.py — fusion install under the
# filter lock, undo closures on stop) must add no order cycle and no
# blocking call under lock
from nnstreamer_tpu.backends.jax_backend import JaxModel
from nnstreamer_tpu.elements.decoder import TensorDecoder
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

lockdep.reset()
W = np.random.default_rng(0).standard_normal((8, 10)).astype(np.float32)
seg_model = JaxModel(apply=lambda p, x: x @ W,
                     input_spec=TensorsSpec.of(
                         TensorSpec(dtype=np.float32, shape=(8,))))
got3 = []
p3 = Pipeline(name="ci_lockdep_seg")
p3.segment_compile = True
src3 = p3.add(DataSrc(data=[np.full(8, i, np.float32) for i in range(8)],
                      name="s"))
filt3 = p3.add(TensorFilter(framework="jax", model=seg_model, name="f"))
dec3 = p3.add(TensorDecoder(mode="image_labeling", name="d"))
p3.link_chain(src3, filt3, dec3, p3.add(TensorSink(callback=got3.append,
                                                   name="out")))
p3.run(timeout=120)
assert len(got3) == 8, got3
assert dec3.plugin._lowered is None, "segment fold not undone on stop"
rep3 = lockdep.report()
assert rep3["cycles"] == [], lockdep.format_report()
assert rep3["blocking_calls"] == [], lockdep.format_report()
print(f"lockdep smoke OK: seeded cycle detected, pipeline clean over "
      f"{rep['sites']} lock sites / {rep['edges']} order edges; lane "
      f"runtime clean over {rep2['sites']} sites / {rep2['edges']} edges; "
      f"segment-folded pipeline clean over {rep3['sites']} sites")
PY

run_step "Driver entry points (compile check + multichip dryrun)" \
  env XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -c "
import jax
jax.config.update('jax_platforms', 'cpu')
import __graft_entry__ as g
fn, args = g.entry()
print(jax.eval_shape(fn, *args))
g.dryrun_multichip(8)
print('dryrun OK')
"

run_step "Observability smoke (tracers + Prometheus scrape)" \
  env NNSTPU_TRACERS="latency;stats" NNSTPU_METRICS_PORT=0 \
  python - <<'PY'
import urllib.request

import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.obs import export

got = []
p = Pipeline(name="ci_obs")
src = p.add(DataSrc(data=[np.full(4, i, np.float32) for i in range(8)]))
p.link(src, p.add(TensorSink(callback=got.append, name="out")))
p.run(timeout=120)
assert len(got) == 8, got

tr = p.stats()["tracers"]
(lat,), = (list(tr["latency"].values()),)
assert lat["count"] == 8, tr

server = export._server
assert server is not None, "NNSTPU_METRICS_PORT did not start the endpoint"
with urllib.request.urlopen(server.url, timeout=30) as resp:
    body = resp.read().decode("utf-8")
assert resp.status == 200 and body.strip(), "empty exposition"
assert "nnstpu_e2e_latency_ms_bucket" in body, body[:400]
assert "nnstpu_element_frames_total" in body, body[:400]
export.shutdown_server()
print(f"observability smoke OK: {len(body)} bytes of exposition, "
      f"e2e p99={lat['p99_ms']:.3f} ms")
PY

run_step "Tracing smoke (spans tracer + Chrome-trace export)" \
  env NNSTPU_TRACERS=spans \
  python - <<'PY'
import json

import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.queue import Queue
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.obs import spans

got = []
p = Pipeline(name="ci_spans")
src = p.add(DataSrc(data=[np.full(4, i, np.float32) for i in range(8)],
                    name="s"))
q = p.add(Queue(max_size_buffers=8, name="q"))
filt = p.add(TensorFilter(framework="custom", model=lambda x: x * 2,
                          name="f"))
sink = p.add(TensorSink(callback=got.append, name="out"))
p.link_chain(src, q, filt, sink)
p.run(timeout=120)
assert len(got) == 8, got
assert all(spans.META_KEY in fr.meta for fr in got), \
    "trace context lost before the sink"

snap = p.flight_snapshot()
doc = json.loads(json.dumps(spans.chrome_trace(snap)))  # valid JSON
events = doc["traceEvents"]
xs = [e for e in events if e.get("ph") == "X"]
assert xs, "no complete spans recorded"

# nested dispatch spans: the filter's slice strictly contains the sink's
# on the queue worker thread
nested = any(
    a["tid"] == b["tid"] and a["name"] == "f" and b["name"] == "out"
    and a["ts"] <= b["ts"] and b["ts"] + b["dur"] <= a["ts"] + a["dur"] + 1e-6
    for a in xs for b in xs)
assert nested, "dispatch spans are not nested"

# at least one flow event pair crossing threads (src thread -> queue worker)
starts = {e["id"]: e for e in events if e.get("ph") == "s"}
cross = [e for e in events if e.get("ph") == "f"
         and e["id"] in starts and starts[e["id"]]["tid"] != e["tid"]]
assert cross, "no cross-thread flow event"

print(f"tracing smoke OK: {len(snap)} records, {len(xs)} spans, "
      f"{len(cross)} cross-thread flows; waterfall:")
print("\n".join(spans.waterfall(snap, limit=2).splitlines()[:8]))
PY

run_step "Device-obs smoke (device lane + compile counters + watchdog)" \
  env NNSTPU_TRACERS="latency,spans,device" NNSTPU_METRICS_PORT=0 \
      NNSTPU_OBS_FLIGHT_DUMP_DIR=/tmp/ci_device_obs_dumps \
  python - <<'PY'
import json
import os
import time
import urllib.error
import urllib.request

import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np

from nnstreamer_tpu import Frame, Pipeline
from nnstreamer_tpu.backends.jax_backend import JaxModel
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.graph.node import SourceNode
from nnstreamer_tpu.obs import export, spans
from nnstreamer_tpu.obs.watchdog import PipelineWatchdog
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

model = JaxModel(apply=lambda p_, x: x * 2,
                 input_spec=TensorsSpec.of(
                     TensorSpec(dtype=np.float32, shape=(4,))))
got = []
p = Pipeline(name="ci_device")
src = p.add(DataSrc(data=[np.full(4, i, np.float32) for i in range(8)],
                    name="s"))
filt = p.add(TensorFilter(framework="jax", model=model, name="f"))
p.link_chain(src, filt, p.add(TensorSink(callback=got.append, name="out")))
p.run(timeout=120)
assert len(got) == 8, got
(dev,) = [t for t in p.tracers if t.name == "device"]
deadline = time.time() + 30
while time.time() < deadline and dev.summary()["completed"] < 8:
    time.sleep(0.05)
summ = dev.summary()
assert summ["completed"] == 8 and summ["dropped"] == 0, summ
assert summ["compiles"]["miss"] >= 1, summ

doc = json.loads(json.dumps(spans.chrome_trace(p.flight_snapshot())))
execs = [e for e in doc["traceEvents"]
         if e.get("ph") == "X" and e["name"] == "device_exec"]
assert len(execs) == 8, "no per-dispatch device_exec spans"
rows = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
        if e.get("ph") == "M" and e["name"] == "thread_name"}
assert any(v.startswith("device:") for v in rows.values()), rows

server = export._server
assert server is not None, "NNSTPU_METRICS_PORT did not start the endpoint"
with urllib.request.urlopen(server.url, timeout=30) as resp:
    body = resp.read().decode("utf-8")
assert "nnstpu_device_exec_seconds_bucket" in body, body[:400]
assert 'nnstpu_compile_total{result="miss"}' in body, \
    [l for l in body.splitlines() if "compile" in l]
assert "nnstpu_device_dispatches_total" in body

# -- watchdog: a deliberately stalled source flips /healthz + dumps -----
class StallSrc(SourceNode):
    def output_spec(self):
        return TensorsSpec.of(TensorSpec(dtype=np.float32, shape=(4,)))
    def frames(self):
        yield Frame.of(np.zeros(4, np.float32))
        self._stop_evt.wait()

p2 = Pipeline(name="ci_stall")
p2.link(p2.add(StallSrc(name="cam")), p2.add(TensorSink(name="out")))
wd = p2.attach_tracer(PipelineWatchdog(interval_s=0.05, stall_s=0.2))
p2.start()
deadline = time.time() + 30
while time.time() < deadline and wd.summary()["healthy"]:
    time.sleep(0.05)
assert not wd.summary()["healthy"], wd.summary()
assert any("stalled_source:cam" in r for r in wd.summary()["reasons"])
try:
    urllib.request.urlopen(
        f"http://{server.host}:{server.port}/healthz", timeout=30)
    raise AssertionError("/healthz stayed 200 on a stalled pipeline")
except urllib.error.HTTPError as e:
    assert e.code == 503 and b"stalled_source:cam" in e.read()
dump = "/tmp/ci_device_obs_dumps/ci_stall.stall.trace.json"
assert os.path.exists(dump), "watchdog wrote no stall flight dump"
p2.stop()
export.shutdown_server()
print(f"device-obs smoke OK: {len(execs)} device_exec spans on "
      f"{[v for v in rows.values() if v.startswith('device:')]}, "
      f"compile misses={summ['compiles']['miss']}, watchdog flagged the "
      "stall and dumped flight data")
PY

run_step "Zero-copy smoke (pooled batch assembly + copies-per-frame gate)" \
  python - <<'PY'
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.backends.jax_backend import JaxModel
from nnstreamer_tpu.elements.batch import TensorBatch, TensorUnbatch
from nnstreamer_tpu.elements.demux import TensorDemux
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.mux import TensorMux
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.obs.metrics import MetricsRegistry
from nnstreamer_tpu.obs.tracers import CopiesTracer
from nnstreamer_tpu.pool import default_pool
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

STREAMS, FRAMES, DIM = 2, 100, 4096  # 16 KB rows, slot-wise pooled path
row = np.zeros((DIM,), np.float32)
model = JaxModel(apply=lambda p_, x: x,
                 input_spec=TensorsSpec.of(
                     TensorSpec(dtype=np.float32, shape=(STREAMS, DIM))))
count = [0]
p = Pipeline(name="ci_zerocopy")
mux = p.add(TensorMux(sync_mode="nosync"))
for i in range(STREAMS):
    src = p.add(DataSrc(name=f"s{i}",
                        data=[row.copy() for _ in range(FRAMES)]))
    p.link(src, f"{mux.name}.sink_{i}")
batch = p.add(TensorBatch())
filt = p.add(TensorFilter(name="f", framework="jax", model=model))
unb = p.add(TensorUnbatch())
demux = p.add(TensorDemux())
p.link_chain(mux, batch, filt, unb, demux)
for i in range(STREAMS):
    p.link(f"{demux.name}.src_{i}",
           p.add(TensorSink(name=f"o{i}",
                            callback=lambda fr: count.__setitem__(
                                0, count[0] + 1))))
tracer = p.attach_tracer(CopiesTracer(registry=MetricsRegistry()))
p.run(timeout=300)
assert count[0] == STREAMS * FRAMES, count

summ = tracer.summary()
row_bytes = row.nbytes
# copy-count regression gate: slot-wise assembly copies each source frame
# into the batch exactly ONCE (<= 1.05x payload bytes per frame), and the
# pool keeps fresh allocations to a handful of warmup leases — a new copy
# or allocation on this path fails CI before it costs throughput
budget = row_bytes * 1.05
assert summ["frames"] > 0
per_frame = summ["bytes_per_frame"]
assert per_frame <= budget, (per_frame, budget, summ)
assert summ["total_allocs"] <= 4, summ
st = default_pool().stats()
assert st["hits"] > 0, st  # the free list is actually being reused
print(f"zero-copy smoke OK: {per_frame / 1024:.1f} KB copied/frame "
      f"(budget {budget / 1024:.1f}), {summ['total_allocs']} fresh allocs "
      f"over {summ['frames']} frames, pool hits={st['hits']} "
      f"misses={st['misses']}")
PY

run_step "Scheduling smoke (DRR fairness + typed shed + live scrape)" \
  python - <<'PY'
import socket
import threading
import time
import urllib.request

import numpy as np

from nnstreamer_tpu.elements.query import (
    QueryOverloadError, QueryServer, recv_tensors, send_tensors)
from nnstreamer_tpu.obs.export import MetricsServer
from nnstreamer_tpu.obs.metrics import MetricsRegistry
from nnstreamer_tpu.sched import AdmissionController, Scheduler


def model(x):  # invoke cost proportional to rows
    time.sleep(0.002 * x.shape[0])
    return x * 2.0


def query(port, tensors):
    s = socket.create_connection(("127.0.0.1", port))
    try:
        send_tensors(s, tensors, 0)
        return recv_tensors(s)
    finally:
        s.close()


reg = MetricsRegistry()
sch = Scheduler("drr", quantum=8.0,
                admission=AdmissionController(max_queue=32),
                name="ci", registry=reg)
done, failures, shed = [], [], []
stop = threading.Event()
with QueryServer(framework="custom", model=model, batch=8,
                 batch_window_ms=5.0, scheduler=sch) as srv, \
        MetricsServer(port=0, registry=reg) as ms:

    def slow_flood():
        conns = [socket.create_connection(("127.0.0.1", srv.port))
                 for _ in range(3)]
        try:
            while not stop.is_set():
                for s in conns:
                    send_tensors(s, (np.ones((24, 4), np.float32),), 0)
                for s in conns:
                    recv_tensors(s)
        except (ConnectionError, OSError):
            pass
        finally:
            for s in conns:
                s.close()

    def fast(k):
        try:
            for i in range(8):
                out, _ = query(srv.port,
                               (np.full((1, 4), float(i), np.float32),))
                np.testing.assert_allclose(out[0], 2.0 * i)
            done.append(k)
        except Exception as exc:  # noqa: BLE001
            failures.append((k, exc))

    flood = threading.Thread(target=slow_flood, daemon=True)
    flood.start()
    time.sleep(0.1)
    fasts = [threading.Thread(target=fast, args=(k,)) for k in range(7)]
    for t in fasts:
        t.start()
    for t in fasts:
        t.join(timeout=120)
    stop.set()
    flood.join(timeout=30)
    assert not failures, failures
    assert len(done) == 7, done  # every fast client completed under flood
    # overload beyond admission limits sheds typed (zero hung conns)
    tight = Scheduler("fifo", admission=AdmissionController(max_queue=1),
                      name="ci_tight", registry=reg)
    with QueryServer(framework="custom", model=model,
                     scheduler=tight) as srv2:
        outcomes = []

        def burst():
            try:
                query(srv2.port, (np.ones((40, 4), np.float32),))
                outcomes.append("ok")
            except QueryOverloadError:
                outcomes.append("shed")

        bs = [threading.Thread(target=burst) for _ in range(3)]
        for t in bs:
            t.start()
        for t in bs:
            t.join(timeout=60)
        assert sorted(outcomes) == ["ok", "shed", "shed"], outcomes
    tight.close()
    with urllib.request.urlopen(ms.url, timeout=30) as resp:
        body = resp.read().decode("utf-8")
    assert "nnstpu_sched_queue_wait_ms_bucket" in body, body[:400]
    assert 'nnstpu_sched_dispatched_total{server="ci"}' in body
    assert ('nnstpu_sched_shed_total{server="ci_tight",reason="queue_full"'
            ',tenant="127.0.0.1"} 2') \
        in body, [l for l in body.splitlines() if "shed" in l]
st = srv.stats()["sched"]
sch.close()
print(f"scheduling smoke OK: {st['dispatched']} scheduled dispatches, "
      f"7/7 fast clients under flood, 2 typed sheds, live scrape carried "
      "nnstpu_sched_*")
PY

run_step "Chaos smoke (injected faults + self-healing + retrying client)" \
  env NNSTPU_FAULTS="seed=7;socket_drop@server:every=4,count=3;queue_wedge@cq:after=1,ms=1500" \
  python - <<'PY'
import time
import urllib.error
import urllib.request

import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np

from nnstreamer_tpu import Pipeline, faults
from nnstreamer_tpu.buffer import Frame
from nnstreamer_tpu.elements.query import QueryServer, TensorQueryClient
from nnstreamer_tpu.elements.queue import Queue
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.obs import export
from nnstreamer_tpu.obs.watchdog import PipelineWatchdog
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

VEC4 = TensorsSpec.of(TensorSpec(dtype=np.float32, shape=(4,)))

# -- 1: the server's reply socket is dropped mid-stream (a killed worker,
# as the client sees it); the retrying client must ride through to success
with QueryServer(framework="custom", model=lambda x: x * 2.0) as srv:
    cli = TensorQueryClient(host="127.0.0.1", port=srv.port, out_spec=VEC4,
                            request_timeout=30.0, retries=3,
                            retry_backoff_ms=10, name="chaos_cli")
    cli.start()
    for i in range(12):
        out = cli.process(
            None, Frame.of(np.full(4, float(i), np.float32), pts=i))
        np.testing.assert_allclose(np.asarray(out.tensor(0)), 2.0 * i)
eng = faults.engine()
drops = eng.injections.get("socket_drop", 0)
assert drops == 3, eng.stats()
assert cli.retries_total == drops, (cli.retries_total, drops)

# -- 2: a queue wedges under NNSTPU_FAULTS; the recovering watchdog must
# flag it (503), drain it, and /healthz must return to 200 in the window
server = export.ensure_server(0)
n = 60
got = []
p = Pipeline(name="chaos_ci")
src = p.add(DataSrc(data=[Frame.of(np.full(4, float(i), np.float32), pts=i)
                          for i in range(n)]))
q = p.add(Queue(max_size_buffers=200, name="cq"))
sink = p.add(TensorSink(name="out"))
sink.connect("new-data", lambda fr: got.append(fr.pts))
p.link_chain(src, q, sink)
p.attach_tracer(PipelineWatchdog(interval_s=0.05, stall_s=0.2,
                                 recover=True))
p.start()


def healthz():
    try:
        with urllib.request.urlopen(
                f"http://{server.host}:{server.port}/healthz",
                timeout=10) as r:
            return r.status
    except urllib.error.HTTPError as e:
        return e.code


deadline = time.time() + 30
while time.time() < deadline and healthz() != 503:
    time.sleep(0.02)
assert healthz() == 503, "watchdog never flagged the wedged queue"
assert p.wait(timeout=60), "pipeline did not reach EOS after recovery"
deadline = time.time() + 10
while time.time() < deadline and healthz() != 200:
    time.sleep(0.05)
code = healthz()
rec = p.recovery_stats()
p.stop()
export.shutdown_server()
assert code == 200, f"/healthz stuck at {code} after recovery"
assert rec["actions"].get("drain_queue", 0) >= 1, rec
assert len(got) + rec.get("shed_total", 0) == n, (len(got), rec)
print(f"chaos smoke OK: {drops} injected socket drops all retried to "
      f"success; watchdog drained the wedged queue (shed "
      f"{rec['shed_total']} typed), ledger balances "
      f"{len(got)}+{rec['shed_total']}=={n}, /healthz back to 200")
PY

run_step "Dispatcher-lane smoke (chaos soak on lanes: healthy end, exact ledger, byte-identical replay)" \
  env NNSTPU_DISPATCH_LANES=auto \
  python - <<'PY'
# The chaos-soak template (tests/test_soak.py) in lane mode: the
# run-to-completion runtime must ride a seeded raise+delay fault mix to
# a healthy EOS with the recovery ledger balancing EXACTLY and the
# fault engine's decision log replaying byte-identical — proof that
# supervised recovery and deterministic chaos are substrate-invariant.
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np

from nnstreamer_tpu import Pipeline, faults
from nnstreamer_tpu.buffer import Frame
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.queue import Queue
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc

n = 400
spec = "seed=1234;invoke_raise@f:rate=0.03;invoke_delay@f:rate=0.02,ms=1"
eng = faults.install(spec)
try:
    got = []
    p = Pipeline(name="ci_lane_soak")
    src = p.add(DataSrc(data=[
        Frame.of(np.full(4, float(i), np.float32), pts=i)
        for i in range(n)]))
    q = p.add(Queue(max_size_buffers=64, name="qsoak"))
    filt = p.add(TensorFilter(framework="custom",
                              model=lambda x: x * 2.0, name="f"))
    sink = p.add(TensorSink(name="out"))
    sink.connect("new-data",
                 lambda fr: got.append((fr.pts,
                                        float(np.asarray(fr.tensor(0))[0]))))
    p.link_chain(src, q, filt, sink)
    p.set_restart_policy("f", mode="restart", backoff_ms=1,
                         backoff_cap_ms=4, max_restarts=1000,
                         window_s=300.0)
    p.start()
    assert p._lanes is not None, "lane runtime did not activate"
    nlanes = p._lanes.nlanes
    assert p.wait(timeout=600)
    p.stop()

    raises = eng.injections.get("invoke_raise", 0)
    delays = eng.injections.get("invoke_delay", 0)
    assert raises > 0 and delays > 0, eng.stats()
    assert p.state == "STOPPED" and p._error is None
    rec = p.recovery_stats()
    assert rec["actions"]["restart_node"] == raises, rec
    assert rec["shed_total"] == raises, rec
    assert len(got) + rec["shed_total"] == n, (len(got), rec)
    assert [pts for pts, _ in got] == sorted(pts for pts, _ in got)
    for pts, val in got:
        assert val == 2.0 * pts, (pts, val)
    replay = faults.ChaosEngine(spec)
    for _ in range(n):
        replay.decide("backend_invoke", "f")
    assert replay.log == eng.log, "replay diverged from the live run"
    assert replay.injections == eng.injections
    print(f"lane smoke OK: {nlanes} lane(s), {len(got)} delivered + "
          f"{rec['shed_total']} typed shed == {n} offered, "
          f"{raises} restarts == {raises} injected raises, replay "
          f"byte-identical over {len(eng.log)} decisions")
finally:
    faults.deactivate()
PY

run_step "Mesh smoke (8-device host mesh: equivalence + per-chip spans)" \
  env NNSTPU_MESH=dp:8 XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      JAX_PLATFORMS=cpu \
  python - <<'PY'
import time

import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.backends.jax_backend import JaxBackend, JaxModel
from nnstreamer_tpu.elements.dynbatch import DynBatch, DynUnbatch
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.obs import spans
from nnstreamer_tpu.obs.device import DeviceTracer
from nnstreamer_tpu.obs.metrics import MetricsRegistry
from nnstreamer_tpu.parallel.mesh import dispatch_mesh_devices
from nnstreamer_tpu.spec import TensorsSpec

assert len(jax.devices()) == 8, jax.devices()
assert dispatch_mesh_devices() == 8

# -- sharded vs single-device equivalence on the raw backend ------------
w = (np.arange(16, dtype=np.float32).reshape(4, 4) / 7.0)
model = JaxModel(apply=lambda p, x: x @ p["w"] + 0.5, params={"w": w})
x = np.random.default_rng(7).standard_normal((16, 4)).astype(np.float32)
sharded = JaxBackend(); sharded.open(model)
sharded.reconfigure(TensorsSpec.from_arrays((x,)))
assert sharded._mesh is not None, "mesh did not activate"
(out,) = sharded.invoke((x,))
assert len(out.sharding.device_set) == 8, out.sharding
np.testing.assert_allclose(np.asarray(out), x @ w + 0.5, rtol=1e-5)

# -- dynbatch e2e over the mesh with the device lane attached -----------
got = []
mdl = JaxModel(apply=lambda p, x: x * 3.0, input_spec=None)
p = Pipeline(name="ci_mesh")
src = p.add(DataSrc(data=[np.full((4,), i, np.float32)
                          for i in range(24)], name="s"))
db = p.add(DynBatch(max_batch=8, name="db"))
filt = p.add(TensorFilter(framework="jax", model=mdl, name="f"))
un = p.add(DynUnbatch(name="un"))
p.link_chain(src, db, filt, un,
             p.add(TensorSink(callback=got.append, name="out")))
reg = MetricsRegistry()
dev = p.attach_tracer(DeviceTracer(registry=reg))
p.run(timeout=120)
assert len(got) == 24, len(got)
vals = sorted(float(f.tensors[0][0]) for f in got)
np.testing.assert_allclose(vals, [i * 3.0 for i in range(24)], rtol=1e-6)
deadline = time.time() + 30
while time.time() < deadline:
    s = dev.summary()
    if s["dispatches"] and s["completed"] == s["dispatches"]:
        break
    time.sleep(0.05)
summ = dev.summary()
assert summ["compiles"]["miss"] >= 1, summ

# nnstpu_device_exec spans on >= 2 device tracks (per-chip rows)
doc = spans.chrome_trace(p.flight_snapshot())
rows = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
        if e.get("ph") == "M" and e["name"] == "thread_name"}
tracks = {rows[e["tid"]] for e in doc["traceEvents"]
          if e.get("ph") == "X" and e["name"] == "device_exec"}
dev_tracks = sorted(t for t in tracks if t.startswith("device:cpu:"))
assert len(dev_tracks) >= 2, tracks
assert len(summ["by_device"]) == 8, summ["by_device"]
print(f"mesh smoke OK: sharded backend matched single-device to 1e-5, "
      f"24 dynbatch frames exact over 8 chips, device_exec spans on "
      f"{len(dev_tracks)} device tracks ({dev_tracks[0]}..{dev_tracks[-1]}), "
      f"compile misses={summ['compiles']['miss']} (no per-frame churn)")
PY

run_step "Utilization smoke (busy-fraction series, no MFU on a device without a peak, device_idle spans)" \
  env NNSTPU_MESH=dp:8 XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      JAX_PLATFORMS=cpu NNSTPU_OBS_DEVICE_IDLE_GAP_MS=10 \
  python - <<'PY'
# The device utilization observatory (ISSUE 11): on a CPU-mesh host a
# dynbatch pipeline must expose per-device nnstpu_device_busy_fraction
# series and cost-stamped device_exec spans — but NO MFU and no roofline
# class, because the CPU's device_kind has no row in obs.util.DEVICE_PEAKS;
# device starvation must render as device_idle spans in the Perfetto
# export.
import time

import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.backends.jax_backend import JaxModel
from nnstreamer_tpu.buffer import Frame
from nnstreamer_tpu.elements.dynbatch import DynBatch, DynUnbatch
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.graph.node import Node
from nnstreamer_tpu.obs import hooks, spans
from nnstreamer_tpu.obs import util as obs_util
from nnstreamer_tpu.obs.collector import attribute_trace
from nnstreamer_tpu.obs.device import DeviceTracer
from nnstreamer_tpu.obs.export import render_text
from nnstreamer_tpu.obs.metrics import MetricsRegistry

assert len(jax.devices()) == 8

# -- mesh dynbatch pipeline: per-device MFU + busy series ---------------
import jax.numpy as jnp
W = np.random.default_rng(0).standard_normal((64, 64)).astype(np.float32)
mdl = JaxModel(apply=lambda p, x: jnp.tanh(x @ W), input_spec=None)
reg = MetricsRegistry()
p = Pipeline(name="ci_util")
src = p.add(DataSrc(data=[np.ones(64, np.float32) for _ in range(24)],
                    name="s"))
p.link_chain(src, p.add(DynBatch(max_batch=8, name="db")),
             p.add(TensorFilter(framework="jax", model=mdl, name="f")),
             p.add(DynUnbatch(name="un")),
             p.add(TensorSink(name="out")))
dev = p.attach_tracer(DeviceTracer(registry=reg))
p.run(timeout=120)
deadline = time.time() + 30
while time.time() < deadline:
    s = dev.summary()
    if s["dispatches"] and s["completed"] == s["dispatches"]:
        break
    time.sleep(0.05)
summ = dev.summary()
assert len(summ["by_device"]) == 8, summ["by_device"]
for label, d in summ["by_device"].items():
    assert d["mfu"] is None, (label, d)  # no peak for this device_kind
    assert 0.0 <= d["busy_fraction"] <= 1.0, (label, d)
text = render_text(reg)
mfu_series = [l for l in text.splitlines() if l.startswith("nnstpu_mfu{")]
busy_series = [l for l in text.splitlines()
               if l.startswith("nnstpu_device_busy_fraction{")]
assert mfu_series == [], mfu_series
assert len(busy_series) == 8, busy_series
execs = [r for r in spans.snapshot()
         if r[0] == spans.PH_COMPLETE and r[4] == "device_exec"]
assert execs and all(
    r[9].get("flops") and r[9].get("mfu") is None
    and r[9].get("roofline") == "unknown" for r in execs), execs[-1][9]
# the v5e row is the published peak, keyed by jax's device_kind
assert obs_util.peak_tflops("TPU v5 lite") == 197.0
assert obs_util.peak_gbs("TPU v5 lite") == 819.0

# -- device_idle dead-time spans + attribution leg ----------------------
reg2 = MetricsRegistry()
p2 = Pipeline(name="ci_idle")
node = p2.add(Node(name="f"))
tr = DeviceTracer(registry=reg2, capacity=8)
p2._tracers.append(tr)
tr.start(p2)
trace_id = spans.new_trace_id()
frame = Frame.of(np.zeros(4, np.float32))
frame.meta[spans.META_KEY] = [trace_id, 1, 0, None]
for pause in (0.0, 0.05):  # 50 ms gap >> the 10 ms threshold
    time.sleep(pause)
    hooks.emit("device_dispatch", node, frame,
               (np.zeros(4, np.float32),), time.perf_counter_ns())
    deadline = time.time() + 10
    while tr.summary()["completed"] < 1 and time.time() < deadline:
        time.sleep(0.01)
deadline = time.time() + 10
while tr.summary()["completed"] < 2 and time.time() < deadline:
    time.sleep(0.01)
tr.stop()
doc = spans.chrome_trace(spans.snapshot())
idle_events = [e for e in doc["traceEvents"]
               if e.get("ph") == "X" and e["name"] == "device_idle"]
assert idle_events, "no device_idle span in the Perfetto export"
assert idle_events[0]["args"]["reason"] in (
    "host_dispatch", "queue_wait", "wire")
legs = attribute_trace(
    [r for r in spans.snapshot()
     if r[0] == spans.PH_COMPLETE and r[6] == trace_id])
assert legs.get("device_idle", 0) > 0, legs

print(f"utilization smoke OK: {len(busy_series)} busy-fraction series "
      f"over 8 devices, no MFU series on a CPU host, "
      f"{len(execs)} cost-stamped device_exec spans, "
      f"{len(idle_events)} device_idle span(s) "
      f"(reason={idle_events[0]['args']['reason']}, device_idle leg "
      f"attributed)")
PY

run_step "Cost-observatory smoke (stage-cost gauges, COST_MODEL.json idempotence, device-lane reconciliation, perfdiff self-compare)" \
  env JAX_PLATFORMS=cpu \
  python - <<'PY'
# The pipeline cost observatory (ISSUE 16): a CPU pipeline under the
# costmodel tracer must expose nnstpu_stage_cost_us{pipeline,node,leg}
# series and the cost_model stats provider; its device_exec leg must
# reconcile with the device lane's own accounting within 5%; the
# persisted COST_MODEL.json must be idempotent across two flushes AND
# across two whole runs; and a perfdiff self-compare must type every
# verdict flat with exit code 0.
import json
import os
import tempfile
import time

tmp = tempfile.mkdtemp(prefix="ci_costmodel_")
os.environ["NNSTPU_OBS_COSTMODEL_PATH"] = os.path.join(tmp, "COST_MODEL.json")

import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.backends.jax_backend import JaxModel
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.queue import Queue
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.obs.costmodel import CostModelTracer, load_cost_model
from nnstreamer_tpu.obs.device import DeviceTracer
from nnstreamer_tpu.obs.export import stats_snapshot
from nnstreamer_tpu.obs.metrics import MetricsRegistry
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec
from tools import perfdiff


def run_once():
    reg = MetricsRegistry()
    model = JaxModel(apply=lambda params, x: x * 2,
                     input_spec=TensorsSpec.of(
                         TensorSpec(dtype=np.float32, shape=(4,))))
    got = []
    p = Pipeline(name="cicost")
    src = p.add(DataSrc(data=[np.full(4, i, np.float32)
                              for i in range(8)], name="s"))
    filt = p.add(TensorFilter(framework="jax", model=model, name="f"))
    q = p.add(Queue(max_size_buffers=4, name="q"))
    p.link_chain(src, filt, q, p.add(TensorSink(callback=got.append,
                                                name="out")))
    dev = p.attach_tracer(DeviceTracer(registry=reg))
    cm = p.attach_tracer(CostModelTracer(registry=reg))
    p.run(timeout=120)
    deadline = time.time() + 30
    while time.time() < deadline and (dev.summary()["completed"] < 8
                                      or len(got) < 8):
        time.sleep(0.05)
    p.stop()
    return reg, dev, cm


reg, dev, cm = run_once()

# live series + stats provider
reg.collect()
labels = {k for k, _ in reg.get("nnstpu_stage_cost_us").children()}
assert ("cicost", "f", "dispatch") in labels, sorted(labels)
assert ("cicost", "f", "device_exec") in labels, sorted(labels)
assert ("cicost", "q", "queue_wait") in labels, sorted(labels)
assert "cicost" in stats_snapshot()["cost_model"]

# device_exec must reconcile with the device lane (same reaper feed)
stages = cm.stage_snapshots()
key = [k for k in stages if "|f|" in k][0]
leg = stages[key]["legs"]["device_exec"]
cm_us = leg["mean_us"] * leg["count"]
dev_us = dev.summary()["device_ns"] / 1e3
drift = abs(cm_us - dev_us) / max(dev_us, 1e-9)
assert drift < 0.05, (cm_us, dev_us, drift)

# flush idempotence within a run
d1, d2 = cm.flush(), cm.flush()
assert d1["stages"][key]["legs"] == d2["stages"][key]["legs"]

# idempotence across two WHOLE runs: the doc stays valid, history is
# per-run, the pooled aggregate only grows by the second run's samples
n1 = d2["stages"][key]["legs"]["device_exec"]["count"]
run_once()
doc = load_cost_model()
pooled = doc["stages"][key]["legs"]["device_exec"]
assert pooled["count"] > n1 and len(doc["stages"][key]["runs"]) == 2

# perfdiff self-compare: every verdict flat, exit 0, nothing regressed
rc = perfdiff.main(["--json"])
assert rc == 0
rep = perfdiff.report(perfdiff.diff_cost_models(doc, doc))
assert rep["verdict"] == "flat" and rep["regressed"] == 0, rep
assert rep["compared"] >= 3

print(f"cost-observatory smoke OK: {len(labels)} stage-cost series, "
      f"device_exec reconciled to {100 * drift:.2f}% of the device "
      f"lane, COST_MODEL.json idempotent ({pooled['count']} pooled "
      f"samples over 2 runs), perfdiff self-compare flat over "
      f"{rep['compared']} legs")
PY

run_step "Fleet smoke (router + 3 workers: kill -9, SIGTERM drain, /healthz convergence)" \
  python - <<'PY'
import jax
jax.config.update('jax_platforms', 'cpu')
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from nnstreamer_tpu.elements.query import (
    QueryError, QuerySessionBrokenError, QueryUnavailableError,
    recv_tensors, send_tensors)

DECODE = "capacity=2,t_max=8,d_in=4,n_out=4,d_model=16,n_heads=2,n_layers=1"


def spawn(args):
    p = subprocess.Popen(
        [sys.executable, "-m", "nnstreamer_tpu.fleet"] + args
        + ["--platform", "cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    line = p.stdout.readline()  # the JSON ports line
    return p, json.loads(line)


procs = []
try:
    workers = []
    for i in range(3):
        p, info = spawn(["worker", "--name", f"w{i}", "--port", "0",
                         "--health-port", "0", "--model", "x2",
                         "--decode", DECODE, "--decode-port", "0",
                         "--drain-timeout", "5"])
        procs.append(p)
        workers.append(info)
    qspec = ",".join(f"127.0.0.1:{w['port']}/{w['health_port']}"
                     for w in workers)
    dspec = ",".join(f"127.0.0.1:{w['decode_port']}/{w['health_port']}"
                     for w in workers)
    qr_p, qr = spawn(["router", "--name", "qrouter", "--port", "0",
                      "--health-port", "0", "--workers", qspec])
    procs.append(qr_p)
    dr_p, dr = spawn(["router", "--name", "drouter", "--port", "0",
                      "--health-port", "0", "--stateful",
                      "--workers", dspec])
    procs.append(dr_p)

    def q_request(val):
        s = socket.create_connection(("127.0.0.1", qr["port"]), timeout=20)
        s.settimeout(20)
        try:
            send_tensors(s, (np.full(4, val, np.float32),), 0)
            outs, _ = recv_tensors(s)
            return float(np.asarray(outs[0])[0])
        finally:
            s.close()

    stateless = {"n": 0, "errors": []}
    stop = threading.Event()

    def q_client():
        i = 0
        while not stop.is_set():
            i += 1
            try:
                assert q_request(float(i)) == 2.0 * i
                stateless["n"] += 1
            except Exception as exc:  # noqa: BLE001
                stateless["errors"].append(repr(exc))
            time.sleep(0.01)

    decode = {"delivered": 0, "typed": 0, "untyped": [], "rebuilt": 0}

    def d_client():
        s = None
        while not stop.is_set():
            try:
                if s is None:
                    s = socket.create_connection(
                        ("127.0.0.1", dr["port"]), timeout=20)
                    s.settimeout(20)
                send_tensors(s, (np.zeros(4, np.float32),), 0)
                outs, _ = recv_tensors(s)
                assert np.asarray(outs[0]).shape == (4,)
                decode["delivered"] += 1
            except (QuerySessionBrokenError, QueryUnavailableError,
                    QueryError):
                decode["typed"] += 1
                if s is not None:
                    s.close(); s = None
                decode["rebuilt"] += 1
            except (ConnectionError, OSError):
                decode["typed"] += 1  # torn socket right after the typed frame
                if s is not None:
                    s.close(); s = None
            except Exception as exc:  # noqa: BLE001
                decode["untyped"].append(repr(exc))
            time.sleep(0.02)
        if s is not None:
            s.close()

    ths = [threading.Thread(target=q_client) for _ in range(3)] \
        + [threading.Thread(target=d_client) for _ in range(2)]
    for t in ths:
        t.start()
    time.sleep(1.0)                       # traffic established
    # kill -9 a worker that is HOSTING a live decode session (so the
    # stateful fail-fast contract is actually exercised), SIGTERM-drain
    # one of the others
    with urllib.request.urlopen(
            f"http://127.0.0.1:{dr['health_port']}/stats.json",
            timeout=10) as r:
        by_worker = json.load(r)["fleet:drouter"]["sessions_by_worker"]
    victim = sorted(by_worker)[0]            # worker id == "host:port"
    vi = next(i for i, w in enumerate(workers)
              if victim.endswith(f":{w['decode_port']}"))
    di = next(i for i in range(3) if i != vi)
    os.kill(workers[vi]["pid"], signal.SIGKILL)   # crash mid-stream
    time.sleep(0.6)
    os.kill(workers[di]["pid"], signal.SIGTERM)   # drain mid-stream
    time.sleep(2.5)                       # ride through the churn
    stop.set()
    for t in ths:
        t.join(timeout=30)

    assert stateless["errors"] == [], \
        f"stateless errors surfaced: {stateless['errors'][:3]}"
    assert stateless["n"] >= 50, stateless
    assert decode["untyped"] == [], decode
    assert decode["typed"] >= 1, decode   # the kill was felt, typed only
    assert decode["delivered"] >= 10, decode

    # /healthz convergence: the survivor answers 200-json, the killed and
    # drained workers are down in the router's membership view
    si = next(i for i in range(3) if i not in (vi, di))
    with urllib.request.urlopen(
            f"http://127.0.0.1:{workers[si]['health_port']}/healthz",
            timeout=10) as r:
        doc = json.loads(r.read())
        assert r.status == 200 and doc["status"] == "ok", doc

    def converged():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{qr['health_port']}/stats.json",
                timeout=10) as r:
            st = json.load(r)["fleet:qrouter"]
        states = {k: v["state"] for k, v in st["membership"]["workers"].items()}
        up = [k for k, v in states.items() if v == "up"]
        gone = [k for k, v in states.items()
                if v in ("down", "suspect", "unhealthy")]
        ok = len(up) == 1 and len(gone) == 2 \
            and st["offered"] == st["delivered"] + st["shed_total"]
        return ok, states, st

    deadline = time.time() + 20
    ok, states, st = converged()
    while time.time() < deadline and not ok:
        time.sleep(0.2)
        ok, states, st = converged()
    assert ok, (states, st["offered"], st["delivered"], st["shed_total"])
    print(f"fleet smoke OK: {stateless['n']} stateless requests with zero "
          f"errors through a kill -9 + SIGTERM drain; decode sessions "
          f"broke typed only ({decode['typed']} typed, "
          f"{decode['delivered']} steps delivered); router ledger "
          f"{st['offered']}=={st['delivered']}+{st['shed_total']}; "
          f"membership converged {states}")
finally:
    for p in procs:
        try:
            p.kill()
        except OSError:
            pass
PY

run_step "Migration smoke (SIGTERM-drain a session-hosting worker: zero [SESSION], token-identical)" \
  python - <<'PY'
# ISSUE 12 acceptance, subprocess edition: a stateful fleet (2 decode
# workers + repo + migrating router), a live decode session mid-
# generation, SIGTERM the session-hosting worker — the router's
# migration monitor moves the session to the survivor, the client sees
# ZERO errors, the transcript is token-identical to an unmigrated
# control run, the session ledger stays exact, and
# nnstpu_session_migrations_total{result="ok"} >= 1 on the router's
# /metrics.  Stateless traffic rides its own router through the same
# churn with an exact offered == delivered + shed ledger.
import jax
jax.config.update('jax_platforms', 'cpu')
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from nnstreamer_tpu.elements.query import recv_tensors, send_tensors
from nnstreamer_tpu.serving import ContinuousBatcher

DECODE = "capacity=2,t_max=8,d_in=4,n_out=4,d_model=16,n_heads=2,n_layers=1"
ENGINE = dict(capacity=2, t_max=8, d_in=4, n_out=4, d_model=16, n_heads=2,
              n_layers=1)


def spawn(args):
    p = subprocess.Popen(
        [sys.executable, "-m", "nnstreamer_tpu.fleet"] + args
        + ["--platform", "cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    line = p.stdout.readline()
    return p, json.loads(line)


procs = []
try:
    repo_p, repo = spawn(["repo", "--port", "0"])
    procs.append(repo_p)
    workers = []
    for i in range(2):
        p, info = spawn(["worker", "--name", f"mw{i}", "--port", "0",
                         "--health-port", "0", "--model", "x2",
                         "--decode", DECODE, "--decode-port", "0",
                         "--drain-timeout", "8"])
        procs.append(p)
        workers.append(info)
    qspec = ",".join(f"127.0.0.1:{w['port']}/{w['health_port']}"
                     for w in workers)
    dspec = ",".join(f"127.0.0.1:{w['decode_port']}/{w['health_port']}"
                     for w in workers)
    qr_p, qr = spawn(["router", "--name", "mig-q", "--port", "0",
                      "--health-port", "0", "--workers", qspec])
    procs.append(qr_p)
    dr_p, dr = spawn(["router", "--name", "mig-d", "--port", "0",
                      "--health-port", "0", "--stateful",
                      "--repo", f"127.0.0.1:{repo['port']}",
                      "--workers", dspec])
    procs.append(dr_p)

    prompt = np.random.RandomState(0).rand(3, 4).astype(np.float32)
    steps = [np.random.RandomState(i + 10).rand(4).astype(np.float32)
             for i in range(24)]

    # control transcript: one unmigrated in-process engine, same params
    with ContinuousBatcher(**ENGINE) as ctl_eng:
        cs = ctl_eng.open_session()
        cs.prefill(prompt)
        control = [cs.get(timeout=15)]
        for s in steps:
            cs.feed(s)
            control.append(cs.get(timeout=15))
        cs.close()

    # stateless traffic through the same churn window (exact ledger)
    stateless = {"n": 0, "errors": []}
    stop = threading.Event()

    def q_client():
        i = 0
        while not stop.is_set():
            i += 1
            try:
                s = socket.create_connection(("127.0.0.1", qr["port"]),
                                             timeout=20)
                s.settimeout(20)
                send_tensors(s, (np.full(4, float(i), np.float32),), 0)
                outs, _ = recv_tensors(s)
                assert float(np.asarray(outs[0])[0]) == 2.0 * i
                stateless["n"] += 1
                s.close()
            except Exception as exc:  # noqa: BLE001
                stateless["errors"].append(repr(exc))
            time.sleep(0.01)

    qt = threading.Thread(target=q_client)
    qt.start()

    # the migrating session: prefill + paced steps spanning the drain
    sock = socket.create_connection(("127.0.0.1", dr["port"]), timeout=20)
    sock.settimeout(20)

    def rt(arr):
        send_tensors(sock, (arr,), 0)
        outs, _ = recv_tensors(sock)
        return np.asarray(outs[0])

    out = [rt(prompt)]
    for s in steps[:6]:
        out.append(rt(s))

    with urllib.request.urlopen(
            f"http://127.0.0.1:{dr['health_port']}/stats.json",
            timeout=10) as r:
        by_worker = json.load(r)["fleet:mig-d"]["sessions_by_worker"]
    victim_addr = next(iter(by_worker))
    vi = next(i for i, w in enumerate(workers)
              if victim_addr.endswith(f":{w['decode_port']}"))
    os.kill(workers[vi]["pid"], signal.SIGTERM)  # drain mid-generation
    for s in steps[6:]:                          # stream THROUGH the drain
        out.append(rt(s))
        time.sleep(0.05)
    stop.set()
    qt.join(timeout=30)
    sock.close()

    assert len(out) == len(control)
    for i, (x, y) in enumerate(zip(control, out)):
        np.testing.assert_array_equal(x, y, err_msg=f"token {i}")
    assert stateless["errors"] == [], stateless["errors"][:3]
    assert stateless["n"] >= 20, stateless

    # the drained worker exits 0 (its decode drain completed clean —
    # the session was migrated off, not force-broken)
    assert procs[1 + vi].wait(timeout=30) == 0

    with urllib.request.urlopen(
            f"http://127.0.0.1:{dr['health_port']}/stats.json",
            timeout=10) as r:
        st = json.load(r)["fleet:mig-d"]
    assert st["sessions_migrated"] >= 1, st
    assert st["sessions_broken"] == 0, st
    assert st["session_ledger_exact"], st
    with urllib.request.urlopen(
            f"http://127.0.0.1:{qr['health_port']}/stats.json",
            timeout=10) as r:
        qst = json.load(r)["fleet:mig-q"]
    assert qst["offered"] == qst["delivered"] + qst["shed_total"], qst
    with urllib.request.urlopen(
            f"http://127.0.0.1:{dr['health_port']}/metrics",
            timeout=10) as r:
        metrics = r.read().decode()
    ok_line = next(
        (ln for ln in metrics.splitlines()
         if ln.startswith("nnstpu_session_migrations_total")
         and 'result="ok"' in ln), "")
    assert ok_line and float(ok_line.rsplit(" ", 1)[1]) >= 1, ok_line
    print(f"migration smoke OK: SIGTERM drain mid-generation migrated "
          f"the session ({ok_line.rsplit(' ', 1)[1]} ok handoffs), "
          f"{len(out)} outputs token-identical to the unmigrated "
          f"control, zero [SESSION] errors, session ledger exact, "
          f"{stateless['n']} stateless requests zero-error with "
          f"{qst['offered']}=={qst['delivered']}+{qst['shed_total']}")
finally:
    for p in procs:
        try:
            p.kill()
        except OSError:
            pass
PY

run_step "Autoscale smoke (seeded spike: scale up, kill -9 + respawn, rolling drain with migrated session)" \
  python - <<'PY'
# ISSUE 15 acceptance, subprocess edition: one `fleet autoscale` process
# (query router + stateful decode router + self-hosted repo + supervisor
# + autoscaler) spawning worker subprocesses on ephemeral ports.  A
# spike scales the fleet 1 -> 3 within the window; kill -9 of a
# scaled-up worker mid-traffic is respawned by the supervisor
# (warming-gated, fresh incarnation); the post-spike down-slope drains
# back to 1 via rolling SIGTERM with the live decode sessions migrated
# (zero [SESSION]); nnstpu_autoscale_events_total{action} and the exact
# spawn + router ledgers are asserted.
import jax
jax.config.update('jax_platforms', 'cpu')
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from nnstreamer_tpu.elements.query import recv_tensors, send_tensors

DECODE = "capacity=4,t_max=8,d_in=4,n_out=4,d_model=16,n_heads=2,n_layers=1"

proc = subprocess.Popen(
    [sys.executable, "-m", "nnstreamer_tpu.fleet", "autoscale",
     "--port", "0", "--health-port", "0", "--model", "x2",
     "--min-workers", "1", "--max-workers", "3", "--worker-rps", "40",
     "--warmup-spec", "float32:4", "--decode", DECODE,
     "--platform", "cpu"],
    stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
try:
    info = json.loads(proc.stdout.readline())
    assert info["role"] == "autoscale" and info["repo_port"], info
    health = info["health_port"]

    def stats():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{health}/stats.json", timeout=10) as r:
            return json.load(r)

    def asc():
        return stats()["autoscale:autoscale"]

    def wait_ready(n, timeout, cmp=lambda a, b: a >= b):
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                if cmp(asc()["ready"], n):
                    return True
            except (KeyError, OSError):
                pass
            time.sleep(0.3)
        return cmp(asc()["ready"], n)

    assert wait_ready(1, 120), asc()   # the floor worker joined (warmed)

    errors, delivered = [], [0]
    stop = threading.Event()
    spike = threading.Event()

    def q_client(gap_s, gate):
        i = 0
        while not stop.is_set():
            if gate is not None and not gate.is_set():
                time.sleep(0.05)
                continue
            i += 1
            try:
                s = socket.create_connection(
                    ("127.0.0.1", info["port"]), timeout=20)
                s.settimeout(20)
                send_tensors(s, (np.full(4, float(i), np.float32),), 0)
                outs, _ = recv_tensors(s)
                assert float(np.asarray(outs[0])[0]) == 2.0 * i
                delivered[0] += 1
                s.close()
            except Exception as exc:  # noqa: BLE001
                errors.append(repr(exc))
            time.sleep(gap_s)

    ths = [threading.Thread(target=q_client, args=(0.1, None))
           for _ in range(2)]
    ths += [threading.Thread(target=q_client, args=(0.004, spike))
            for _ in range(8)]
    for t in ths:
        t.start()
    time.sleep(2.0)
    assert asc()["ready"] == 1, asc()  # trickle fits the floor

    spike.set()                        # the seeded spike hits
    assert wait_ready(3, 120), asc()   # scaled up within the window
    print(f"scale-up OK: fleet at 3 within window "
          f"(decision: {asc()['last_decision']})")

    # live decode sessions across the scaled-up fleet (round-robin
    # pins them on distinct workers, so the down-slope MUST migrate)
    sessions = []
    for _ in range(2):
        s = socket.create_connection(
            ("127.0.0.1", info["decode_port"]), timeout=30)
        s.settimeout(30)
        send_tensors(s, (np.full((5, 4), 0.1, np.float32),), 0)
        recv_tensors(s)
        sessions.append(s)

    # kill -9 a scaled-up worker mid-traffic: the supervisor must
    # respawn it (fresh incarnation, warming-gated join).  Pick one
    # that is NOT hosting a session (the kill tests respawn, not the
    # stateful fail-fast contract).
    st = stats()
    hosts = set(st.get("fleet:autoscale-decode", {})
                .get("sessions_by_worker", {}))
    workers = asc()["supervisor"]["workers"]
    victim = next(w for w, snap in sorted(workers.items())
                  if snap["state"] == "up" and snap["pid"]
                  and w not in hosts)
    os.kill(workers[victim]["pid"], signal.SIGKILL)
    deadline = time.time() + 120
    while time.time() < deadline:
        snap = asc()
        if snap["supervisor"]["workers"].get(victim, {}).get(
                "restarts", 0) >= 1 and snap["ready"] >= 3:
            break
        time.sleep(0.3)
    snap = asc()
    assert snap["supervisor"]["workers"][victim]["restarts"] >= 1, snap
    assert snap["ready"] == 3, snap
    print(f"respawn OK: {victim} killed -9 and supervised back to ready")

    spike.clear()                      # the down-slope
    assert wait_ready(1, 120, cmp=lambda a, b: a <= b), asc()
    # the sessions survived the rolling migrate-first drain: they still
    # step, zero [SESSION]
    for s in sessions:
        for _ in range(3):
            send_tensors(s, (np.zeros(4, np.float32),), 0)
            outs, _ = recv_tensors(s)
            assert np.asarray(outs[0]).shape == (4,)
    for s in sessions:
        s.close()
    stop.set()
    for t in ths:
        t.join(timeout=60)

    st = stats()
    snap = st["autoscale:autoscale"]
    drt = st["fleet:autoscale-decode"]
    qrt = st["fleet:autoscale"]
    assert errors == [], f"stateless errors: {errors[:3]}"
    assert drt["sessions_broken"] == 0, drt
    assert drt["sessions_migrated"] >= 1, drt
    # ledgers: the autoscaler's own (spawns == joined+failed+quarantined)
    # and the router's (offered == delivered + shed), both exact
    assert snap["ledger_exact"], snap
    assert snap["spawns"] == snap["joined"] + snap["failed"] \
        + snap["quarantined"] + snap["pending"], snap
    assert snap["fleet_size_min"] == 1 and snap["fleet_size_max"] == 3
    assert qrt["offered"] == qrt["delivered"] + qrt["shed_total"], qrt
    assert qrt["offered"] >= delivered[0]

    # the metric family: every transition counted by action
    with urllib.request.urlopen(
            f"http://127.0.0.1:{health}/metrics", timeout=10) as r:
        expo = r.read().decode()
    counts = {}
    for line in expo.splitlines():
        if line.startswith("nnstpu_autoscale_events_total{"):
            action = line.split('action="')[1].split('"')[0]
            counts[action] = counts.get(action, 0) + int(float(
                line.rsplit(" ", 1)[1]))
    assert counts.get("spawn", 0) >= 3, counts      # floor + 2 scale-ups
    assert counts.get("join", 0) >= 4, counts       # incl. the respawn
    assert counts.get("respawn", 0) >= 1, counts
    assert counts.get("drain", 0) >= 2, counts
    print(f"autoscale smoke OK: 1->3->1 with kill -9 respawn; "
          f"{delivered[0]} stateless requests zero-error; "
          f"{drt['sessions_migrated']} session(s) migrated, 0 broken; "
          f"spawn ledger {snap['spawns']}=={snap['joined']}+"
          f"{snap['failed']}+{snap['quarantined']}; events {counts}")
finally:
    try:
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        proc.kill()
PY

run_step "Cold-start smoke (warm a pipeline, restart the process, zero compile misses)" \
  python - <<'PY'
# Compile-ahead acceptance gate: a warmed-then-restarted pipeline must
# serve its first frame with nnstpu_compile_total{result="miss"} == 0 —
# every executable reconstructed from the persistent cache (result in
# {hit, persist_hit} only) — and warmup-phase compile spans must land on
# the "warmup" Perfetto track, never inside the first frame's trace.
import json
import shutil
import subprocess
import sys
import tempfile

DRIVER = r'''
import json, os, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.backends.jax_backend import JaxModel
from nnstreamer_tpu.elements.dynbatch import DynBatch, DynUnbatch
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.obs import spans
from nnstreamer_tpu.obs.metrics import REGISTRY
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

D = 64
W = np.random.default_rng(0).standard_normal((D, D)).astype(np.float32)
model = JaxModel(apply=lambda p, x: jax.numpy.tanh(x @ W),
                 input_spec=TensorsSpec.of(
                     TensorSpec(dtype=np.float32, shape=(None, D))))
state = {"first": None}

def cb(frame):
    if state["first"] is None:
        np.asarray(frame.tensors[0])
        state["first"] = time.perf_counter()

p = Pipeline(name="ci_coldstart")
src = p.add(DataSrc(data=[np.ones(D, np.float32) for _ in range(4)]))
p.link_chain(src, p.add(DynBatch(max_batch=4)),
             p.add(TensorFilter(framework="jax", model=model)),
             p.add(DynUnbatch()), p.add(TensorSink(callback=cb)))
p.run(timeout=120)
assert state["first"] is not None, "no frame served"

c = REGISTRY.get("nnstpu_compile_total")
compiles = {k[0]: int(v.value) for k, v in dict(c.children()).items()}

# span attribution: every compile span sits on the "warmup" track
doc = spans.chrome_trace(spans.snapshot(), process_name="ci_coldstart")
comp = [e for e in doc["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "compile"]
rows = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
        if e.get("ph") == "M" and e["name"] == "thread_name"}
warm_rows = [tid for tid, name in rows.items() if name == "warmup"]
bad = [e for e in comp if e["tid"] not in warm_rows]
warmed = [e for e in doc["traceEvents"]
          if e.get("ph") == "X" and str(e["name"]).startswith("warm")]
print(json.dumps({"compiles": compiles, "compile_spans": len(comp),
                  "off_track": len(bad), "warmup_spans": len(warmed)}))
'''

cache = tempfile.mkdtemp(prefix="ci_coldstart_")
try:
    env = {"NNSTPU_COMPILE_CACHE_DIR": cache, "NNSTPU_COMPILE_WARMUP": "1",
           "NNSTPU_TRACERS": "spans", "JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin:/usr/local/bin"}
    import os

    env = dict(os.environ, **env)
    runs = {}
    for label in ("cold", "warm"):
        proc = subprocess.run([sys.executable, "-c", DRIVER], env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, (label, proc.stderr[-800:])
        runs[label] = json.loads(proc.stdout.strip().splitlines()[-1])
    cold, warm = runs["cold"], runs["warm"]
    assert cold["compiles"].get("miss", 0) > 0, cold  # cold run really compiled
    assert warm["compiles"].get("miss", 0) == 0, \
        f"warmed restart still compiling: {warm['compiles']}"
    assert warm["compiles"].get("persist_hit", 0) > 0, warm
    for label, run in runs.items():
        assert run["compile_spans"] > 0 and run["off_track"] == 0, (label, run)
        assert run["warmup_spans"] > 0, (label, run)
    print(f"cold-start smoke OK: cold={cold['compiles']} -> "
          f"warm={warm['compiles']} (zero misses after restart); "
          f"all {warm['compile_spans']} compile spans on the warmup track")
finally:
    shutil.rmtree(cache, ignore_errors=True)
PY

run_step "Segment smoke (whole-segment compilation: one device_exec per dispatch, host-dispatch dead time within budget, zero compile misses after warm restart)" \
  python - <<'PY'
# Whole-segment acceptance gate (graph/segments.py): the SSD pipeline
# with the tflite-ssd decoder folded into the filter's program must
# (a) run exactly one device_exec span per frame — the whole
#     converter→model→decode region is ONE device program;
# (b) cut device_idle{reason=host_dispatch} dead time to ≤10% of the
#     unfused run's (the fold removes the 1917-anchor host decode from
#     between device programs; only the overlay tail remains);
# (c) serve a warm process restart with zero compile misses — the fused
#     executable persists under its composite (StableHLO sha + segment
#     label) cache key like any other program.
import json
import os
import shutil
import subprocess
import sys
import tempfile

DRIVER = r'''
import json, os, tempfile
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from nnstreamer_tpu import Pipeline, make
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.models import ssd_mobilenet
from nnstreamer_tpu.obs import spans
from nnstreamer_tpu.obs.metrics import REGISTRY

N = 6
rng = np.random.default_rng(0)
frames = [rng.integers(0, 256, (300, 300, 3)).astype(np.uint8)
          for _ in range(N)]
model = ssd_mobilenet.build(num_labels=91, image_size=300)
priors_path = ssd_mobilenet.write_priors_file(
    os.path.join(tempfile.mkdtemp(prefix="ci_segment_priors_"),
                 "priors.txt"))
got = []
p = Pipeline(name="ci_segment")
src = p.add(DataSrc(data=frames))
conv = p.add(make("tensor_converter"))
norm = p.add(make("tensor_transform", mode="arithmetic",
                  option="typecast:float32,add:-127.5,div:127.5"))
filt = p.add(TensorFilter(framework="jax", model=model))
dec = p.add(make("tensor_decoder", mode="bounding_boxes",
                 option1="tflite-ssd", option3=priors_path,
                 option4="300:300", option5="300:300"))
sink = p.add(TensorSink(callback=got.append))
p.link_chain(src, conv, norm, filt, dec, sink)
p.start()
label = filt.backend.segment_label  # sampled while PLAYING
p.wait(300)
p.stop()
assert len(got) == N, f"delivered {len(got)}/{N} frames"

rows = spans.snapshot()
execs = [r for r in rows if r[0] == spans.PH_COMPLETE
         and r[4] == "device_exec"]
idle = [r for r in rows if r[0] == spans.PH_COMPLETE
        and r[4] == "device_idle"
        and r[9].get("reason") == "host_dispatch"]
c = REGISTRY.get("nnstpu_compile_total")
compiles = ({k[0]: int(v.value) for k, v in dict(c.children()).items()}
            if c else {})
print(json.dumps({
    "frames": len(got), "execs": len(execs), "label": label,
    "host_us_per_frame": sum(r[2] for r in idle) / 1e3 / N,
    "compiles": compiles,
}))
'''

base = dict(os.environ,
            JAX_PLATFORMS="cpu",
            NNSTPU_TRACERS="device",
            NNSTPU_OBS_DEVICE_IDLE_GAP_MS="0.05")

def child(label, **env):
    proc = subprocess.run([sys.executable, "-c", DRIVER],
                          env=dict(base, **env),
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (label, proc.stderr[-800:])
    return json.loads(proc.stdout.strip().splitlines()[-1])

cache = tempfile.mkdtemp(prefix="ci_segment_")
try:
    unf = child("unfused", NNSTPU_SEGMENT_ENABLED="0")
    assert unf["label"] == "", unf
    seg_env = {"NNSTPU_SEGMENT_ENABLED": "1",
               "NNSTPU_COMPILE_CACHE_DIR": cache,
               "NNSTPU_COMPILE_WARMUP": "1"}
    cold = child("segment-cold", **seg_env)
    assert cold["label"], "segment did not fold (empty segment label)"
    # (a) one device program per segment dispatch
    assert cold["execs"] == cold["frames"], cold
    # (b) the fold removes the host decode from between device programs
    budget = 0.10 * unf["host_us_per_frame"]
    assert cold["host_us_per_frame"] <= budget, \
        (f"fused host-dispatch {cold['host_us_per_frame']:.0f} us/frame "
         f"> 10% of unfused {unf['host_us_per_frame']:.0f}")
    assert cold["compiles"].get("miss", 0) > 0, cold  # really compiled
    # (c) warm restart: the fused executable reconstructs, never recompiles
    warm = child("segment-warm", **seg_env)
    assert warm["label"] == cold["label"], (warm, cold)
    assert warm["compiles"].get("miss", 0) == 0, \
        f"warm restart still compiling: {warm['compiles']}"
    assert warm["compiles"].get("persist_hit", 0) > 0, warm
    print(f"segment smoke OK: label={cold['label']!r}, "
          f"{cold['execs']}/{cold['frames']} device_exec, host-dispatch "
          f"{unf['host_us_per_frame']:.0f} -> {cold['host_us_per_frame']:.0f} "
          f"us/frame, warm restart compiles={warm['compiles']}")
finally:
    shutil.rmtree(cache, ignore_errors=True)
PY

run_step "Partition smoke (planner-pinned split of the SSD cascade across a subprocess fragment worker: merged trace hop arrows, exact ledger through seeded drops, regime flip = 1 repartition)" \
  python - <<'PY'
import jax
jax.config.update('jax_platforms', 'cpu')
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.graph.parse import split_launch
from nnstreamer_tpu.obs import costmodel as obs_costmodel
from nnstreamer_tpu.obs import spans
from nnstreamer_tpu.obs import util as obs_util
from nnstreamer_tpu.obs.collector import TraceCollector
from nnstreamer_tpu.obs.spans import SpanTracer
from nnstreamer_tpu.partition import (
    PartitionDeployment, RepartitionMonitor, plan_partition)

tmp = tempfile.mkdtemp(prefix="partition_smoke_")
model_py = os.path.join(tmp, "cascade_model.py")
with open(model_py, "w") as f:
    f.write(
        "from nnstreamer_tpu.models import cascade\n"
        "def get_model():\n"
        "    return cascade.build_detect_classify(\n"
        "        num_labels=91, det_size=300, k=4, crop_size=96,\n"
        "        num_classes=101, width_mult=0.5, seed=0)\n")

DESC = (
    "videotestsrc num-buffers=8 pattern=smpte width=300 height=300 ! "
    "tensor_converter name=conv ! "
    "tensor_transform mode=arithmetic option=typecast:float32,add:-127.5,"
    "div:127.5 name=norm ! "
    f"tensor_filter framework=jax model={model_py} name=cascade ! "
    "tensor_sink name=out collect=true")

# -- phase 0: golden reference (unsplit, in-process) ------------------------
ref = parse_launch(DESC)
ref.start(); ref.wait(300); ref.stop()
want = [[np.asarray(t) for t in fr.tensors] for fr in ref.nodes["out"].frames]
assert len(want) == 8, f"golden run produced {len(want)} frames"

# -- phase 1: the planner picks the cut from measured inputs ----------------
sk = obs_costmodel.stage_key
COST_MODEL = {"schema": 1, "stages": {
    # copy_bytes = what crosses the wire INTO that stage: raw video
    # (RGBA-padded, 360 KB) into conv, packed uint8 (270 KB) into norm,
    # normalized float32 (1.08 MB) into cascade — cut=2 is the cheapest
    # crossing, and the 10x server roofline makes it beat all-local
    sk("smoke", "conv"): {"legs": {"device_exec": {
        "count": 5, "mean_us": 100.0, "m2": 400.0}}, "runs": [],
        "copy_bytes_per_frame": 360_000.0},
    sk("smoke", "norm"): {"legs": {"device_exec": {
        "count": 5, "mean_us": 2000.0, "m2": 400.0}}, "runs": [],
        "copy_bytes_per_frame": 270_000.0},
    sk("smoke", "cascade"): {"legs": {"device_exec": {
        "count": 5, "mean_us": 50_000.0, "m2": 400.0}}, "runs": [],
        "flops_per_frame": 1e9, "copy_bytes_per_frame": 1_080_000.0},
}}
PEAKS = {"client": {"tflops": 0.1}, "server": {"tflops": 1.0}}
FAST = {"put_150k_ms": 0.5, "dispatch_ms": 0.2}

plan = plan_partition(DESC, pipeline="smoke", addr="127.0.0.1:0",
                      edge="edge0", cost_model=COST_MODEL,
                      wire_health=FAST, peaks=PEAKS)
assert plan.cut == 2, f"planner chose {plan.cut}: {[ (s.cut, s.total_us) for s in plan.scores ]}"
p2 = plan_partition(DESC, pipeline="smoke", addr="127.0.0.1:0",
                    edge="edge0", cost_model=COST_MODEL,
                    wire_health=FAST, peaks=PEAKS)
assert p2 == plan and p2.fingerprint == plan.fingerprint, "plan not reproducible"
print(f"planner: cut={plan.cut} fingerprint={plan.fingerprint} "
      f"scores={[(s.cut, s.total_us) for s in plan.scores]}")

# -- phase 2: subprocess server fragment, chaos on the split edge -----------
_, server_desc = split_launch(DESC, plan.cut)
env = dict(os.environ)
env["JAX_PLATFORMS"] = "cpu"
env["NNSTPU_FAULTS"] = "seed=7;socket_drop@server:every=3,count=2"
proc = subprocess.Popen(
    [sys.executable, "-m", "nnstreamer_tpu.fleet", "worker",
     "--name", "fragw", "--port", "0", "--health-port", "0",
     "--framework", "fragment", "--model", server_desc,
     "--spans", "--platform", "cpu"],
    stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True, env=env)
try:
    info = json.loads(proc.stdout.readline())
    assert info["role"] == "worker"

    client_desc, _ = split_launch(DESC, plan.cut, client_props={
        "name": "qc_edge0", "host": "127.0.0.1", "port": str(info["port"]),
        "caps": "true", "require_caps": "true", "edge": "edge0",
        "retries": "2", "retry_backoff_ms": "5", "request_timeout": "300",
    })
    spans.enable(8192)
    pipe = parse_launch(client_desc)
    pipe.attach_tracer(SpanTracer())
    pipe.start(); pipe.wait(300); pipe.stop()
    got = [[np.asarray(t) for t in fr.tensors]
           for fr in pipe.nodes["out"].frames]
    assert len(got) == 8, f"split run produced {len(got)} frames"
    for i, (w, g) in enumerate(zip(want, got)):
        assert len(w) == len(g)
        for wt, gt in zip(w, g):
            np.testing.assert_array_equal(wt, gt, err_msg=f"frame {i}")
    qc = pipe.nodes["qc_edge0"]
    assert qc._caps_wire is True, "split edge did not negotiate caps"
    assert qc.retries_total == 2, (
        f"chaos ledger: expected exactly 2 retried drops, saw "
        f"{qc.retries_total}")
    print(f"split run exact through chaos: 8/8 frames, "
          f"retries={qc.retries_total}, caps_wire={qc._caps_wire}")

    # -- merged Perfetto trace: client fragment -> hop -> server fragment
    tc = TraceCollector()
    tc.add_local("client")
    tc.add_http("fragw", info["trace_addr"])
    chrome = tc.chrome_trace()
    evs = chrome["traceEvents"]
    pids = {}
    for e in evs:
        if e.get("ph") == "X":
            pids.setdefault(e["name"], set()).add(e["pid"])
    rtt_pids = pids.get("nnsq_rtt", set())
    serve_pids = pids.get("nnsq_serve", set())
    assert rtt_pids and serve_pids and rtt_pids.isdisjoint(serve_pids), (
        f"client/server spans must sit on different pids: "
        f"rtt={rtt_pids} serve={serve_pids}")
    hop_s = [e for e in evs if e.get("name") == "nnsq_hop"
             and e["ph"] == "s"]
    hop_f = [e for e in evs if e.get("name") == "nnsq_hop"
             and e["ph"] == "f"]
    assert len(hop_s) >= 8 and len(hop_f) == len(hop_s), (
        f"expected >=8 hop arrows, got s={len(hop_s)} f={len(hop_f)}")
    by_id = {e["id"]: e for e in hop_s}
    for f_ev in hop_f:
        s_ev = by_id[f_ev["id"]]
        assert s_ev["pid"] != f_ev["pid"], "hop arrow must cross pids"
        assert s_ev["args"]["edge"] == "edge0"
    assert all(e["pid"] in rtt_pids for e in hop_s)
    assert all(e["pid"] in serve_pids for e in hop_f)
    trace_path = os.path.join(tmp, "partition_smoke.trace.json")
    with open(trace_path, "w") as f:
        json.dump(chrome, f)
    print(f"merged trace: {len(evs)} events, {len(hop_s)} client->server "
          f"hop arrows ({trace_path})")
finally:
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()
spans.disable()

# -- phase 3: forced wire-regime flip -> exactly one repartition ------------
cm_path = os.path.join(tmp, "COST_MODEL.json")
CM2 = {"schema": 1, "stages": {
    sk("rp", "conv"): {"legs": {"device_exec": {
        "count": 5, "mean_us": 100.0, "m2": 400.0}}, "runs": [],
        "copy_bytes_per_frame": 301_056.0},
    sk("rp", "scale"): {"legs": {"device_exec": {
        "count": 5, "mean_us": 4000.0, "m2": 400.0}}, "runs": [],
        "flops_per_frame": 1e9, "copy_bytes_per_frame": 150_528.0},
    sk("rp", "bias"): {"legs": {"device_exec": {
        "count": 5, "mean_us": 3000.0, "m2": 400.0}}, "runs": [],
        "flops_per_frame": 1e9, "copy_bytes_per_frame": 150_528.0},
}}
with open(cm_path, "w") as f:
    json.dump(CM2, f)
os.environ["NNSTPU_OBS_COSTMODEL_PATH"] = cm_path
RP_DESC = ("videotestsrc num-buffers=4 pattern=smpte width=4 height=4 ! "
           "tensor_converter name=conv ! "
           "tensor_transform mode=arithmetic option=mul:2.0 name=scale ! "
           "tensor_transform mode=arithmetic option=add:1.0 name=bias ! "
           "tensor_sink name=out")
rp_plan = plan_partition(RP_DESC, pipeline="rp", addr="127.0.0.1:0",
                         edge="edge1", cost_model=CM2, wire_health=FAST,
                         peaks=PEAKS)
assert rp_plan.cut == 2, f"repartition phase plan chose {rp_plan.cut}"
dep = PartitionDeployment(rp_plan).start()
try:
    obs_util.publish_wire_health(dict(FAST), addr=dep.addr)
    mon = RepartitionMonitor(dep, peaks=PEAKS)
    assert mon.evaluate_once() is None, "steady state must not trigger"
    obs_util.publish_wire_health(
        {"put_150k_ms": 50.0, "dispatch_ms": 5.0}, addr=dep.addr)
    reason = mon.evaluate_once()
    assert reason and "regime flip" in reason, f"no flip trigger: {reason}"
    assert dep.plan.cut is None and dep.worker is None
    assert dep.redeploys == 1, f"redeploys={dep.redeploys}"
    assert mon.evaluate_once() is None, "flip must trigger exactly once"
    assert mon.triggers == 1
    print(f"repartition: '{reason}' -> 1 redeploy (all-local), "
          f"second tick quiet")
finally:
    dep.stop()
    obs_util.reset_wire_health()
print("partition smoke OK: planner-pinned split, subprocess fragment "
      "exact through 2 seeded drops, merged trace with hop arrows, "
      "regime flip = exactly 1 repartition")
PY

run_step "SLO gate (loadgen ci-slo: flooding tenant shed typed, well-behaved p99 held, ledger exact)" \
  python - <<'PY'
# The production-load SLO gate (ISSUE 10): a fixed seeded scenario — an
# in-process 2-worker fleet behind a DRR + per-tenant-rate router, one
# flooding tenant vs three well-behaved tenants on mixed workloads
# (vision / LSTM window / SSD cascade).  The gate asserts the polite
# tenants' p99 and goodput hold while the flood is typed-shed, that
# ZERO requests go lost or unaccounted (client round trips reconcile
# exactly with the router's offered == delivered + shed ledger), and
# that per-trace attribution joined client records with server spans.
import json
import subprocess
import sys

proc = subprocess.run(
    [sys.executable, "tools/loadgen.py", "--scenario", "ci-slo",
     "--seed", "7", "--assert-slo", "--out", "/tmp/ci_slo_report.json"],
    capture_output=True, text=True, timeout=300)
sys.stdout.write(proc.stdout)
sys.stderr.write(proc.stderr)
assert proc.returncode == 0, f"SLO gate failed (rc={proc.returncode})"
report = json.load(open("/tmp/ci_slo_report.json"))
assert report["slo"]["pass"], report["slo"]["checks"]
assert report["ledger"]["exact"], report["ledger"]
assert report["attribution"]["joined"] > 0, report["attribution"]
flood = report["tenants"]["flood"]
wb = {n: t for n, t in report["tenants"].items() if t["well_behaved"]}
assert flood["typed_total"] > 0 and len(wb) == 3
legs = report["attribution"]["legs_ms"]
for leg in ("queue", "device", "serve", "route", "rtt"):
    assert leg in legs, (leg, sorted(legs))
print(f"SLO gate OK: flood shed {flood['typed_total']} typed of "
      f"{flood['offered']}; well-behaved p99s "
      f"{[round(t['latency_ms']['p99_ms'], 1) for t in wb.values()]} ms; "
      f"ledger exact; {report['attribution']['joined']} traces attributed "
      f"(queue/device/serve/route/wire)")
PY

run_step "Forensics smoke (seeded invoke_delay chaos: device verdicts in the gallery, p99.9 exemplar joins its flight dump, /alerts fires then resolves, ledger exact)" \
  python - <<'PY'
# Tail-forensics end-to-end (ISSUE 18): seeded invoke_delay@filter
# chaos under the ci-slo loadgen fleet must produce (a) >=1 gallery
# capture whose typed verdict is `device` — the cost-model root-cause
# chain working against a known-injected device stall; (b) the scraped
# p99.9 exemplar's trace id present in a captured flight dump — the
# scrape->trace join the exemplars exist for; (c) the SLO burn-rate
# alert firing on the run's histogram and resolving once the bad
# window drains; (d) an exact ledger — forensics must observe, never
# perturb.
import json
import os
import shutil
import sys
import time
import urllib.request

sys.path.insert(0, "tools")

GDIR = "/tmp/ci_forensics"
shutil.rmtree(GDIR, ignore_errors=True)
os.environ["NNSTPU_OBS_FORENSICS_DIR"] = GDIR
os.environ["NNSTPU_OBS_FORENSICS_MIN_SAMPLES"] = "24"
os.environ["NNSTPU_SLO_OBJECTIVES"] = "lgci:{pipeline=lg-ci-slo}<50ms@0.999"
os.environ["NNSTPU_SLO_FAST_WINDOW_S"] = "2"
os.environ["NNSTPU_SLO_SLOW_WINDOW_S"] = "4"
os.environ["NNSTPU_SLO_FAST_BURN"] = "2"
os.environ["NNSTPU_SLO_SLOW_BURN"] = "1"
os.environ["NNSTPU_SLO_EVAL_INTERVAL_S"] = "0"

import loadgen  # noqa: E402
from nnstreamer_tpu import faults  # noqa: E402
from nnstreamer_tpu.obs.export import MetricsServer  # noqa: E402
from nnstreamer_tpu.obs.metrics import REGISTRY  # noqa: E402

faults.install("invoke_delay@filter:after=60,every=40,count=6,ms=80",
               seed=7)
try:
    report = loadgen.run_scenario("ci-slo", seed=7, duration_s=2.5)
finally:
    faults.deactivate()

# (d) ledger exact under chaos
assert report["ledger"]["exact"], report["ledger"]

# (a) device verdicts in the bounded gallery
fx = report["forensics"]
assert fx["scored"] > 24 and not fx["warming"], fx
assert fx["outliers"].get("device", 0) >= 1, fx["outliers"]
docs = [json.load(open(os.path.join(GDIR, f)))
        for f in sorted(os.listdir(GDIR)) if f.endswith(".forensic.json")]
dev = [d for d in docs if d["verdict"] == "device"]
assert dev, [d["verdict"] for d in docs]
assert fx["gallery"]["entries"] == len(docs) > 0, fx["gallery"]

# (b) the p99.9 exemplar: highest non-empty bucket's exemplar across
# the run's histogram children must name a trace whose flight dump was
# captured
hist = REGISTRY.get("nnstpu_e2e_latency_ms")
best = None  # (bucket_index, value, trace_id)
for key, child in hist.children():
    if key and key[0] != "lg-ci-slo":
        continue
    for i, ex in enumerate(child.exemplars()):
        if ex is not None and (best is None or (i, ex[1]) >
                               (best[0], best[1])):
            best = (i, ex[1], ex[0])
assert best is not None, "no exemplar stamped"
tail_tid = f"{best[2]:x}"
captured_tids = {d["trace_id"] for d in docs}
assert tail_tid in captured_tids, (tail_tid, captured_tids)
cap = next(d for d in docs if d["trace_id"] == tail_tid)
assert any(e.get("args", {}).get("trace_id") == tail_tid
           for e in cap["flight"]["traceEvents"]), "flight dump empty"

# (c) burn-rate alert: the server's scrape-time engine sees the run's
# bad deltas at first /alerts, then resolves once the windows drain
srv = MetricsServer(port=0, registry=REGISTRY).start()
try:
    url = f"http://127.0.0.1:{srv.port}/alerts"
    doc = json.loads(urllib.request.urlopen(url).read())
    assert doc["firing"] == ["lgci"], doc
    assert doc["objectives"]["lgci"]["severity"] == "page", doc
    deadline = time.time() + 15
    while True:
        time.sleep(1.0)
        doc = json.loads(urllib.request.urlopen(url).read())
        if not doc["firing"]:
            break
        assert time.time() < deadline, f"alert never resolved: {doc}"
    assert doc["objectives"]["lgci"]["transitions"] == 2, doc
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}/metrics?exemplars=1"
    ).read().decode()
    assert f'# {{trace_id="{tail_tid}"}}' in text
    assert ('nnstpu_slo_alert_transitions_total{'
            'objective="lgci",state="resolved"} 1') in text
finally:
    srv.stop()

print(f"forensics smoke OK: {fx['outliers']} outliers, "
      f"{len(docs)} captures ({len(dev)} device-verdict), p99.9 exemplar "
      f"{tail_tid} joined its flight dump, alert fired (page) and "
      f"resolved, ledger exact")
PY

run_step "Profiling smoke (/profile capture joined to the cost registry, HBM series, watchdog auto-capture on injected regression, gallery idempotence)" \
  env NNSTPU_TRACERS="spans,device" NNSTPU_METRICS_PORT=0 \
      NNSTPU_OBS_PROFILE_DIR=/tmp/ci_profile_gallery \
      NNSTPU_OBS_PROFILE_KEEP=4 \
      NNSTPU_OBS_PROFILE_AUTO=true \
      NNSTPU_OBS_PROFILE_AUTO_SECONDS=0.5 \
      NNSTPU_OBS_PROFILE_AUTO_COOLDOWN_S=0 \
      NNSTPU_OBS_PROFILE_MIN_SAMPLES=8 \
  python - <<'PY'
# Deep-profiling lane end-to-end (ISSUE 20): (a) GET /profile?seconds=1
# against a serving CPU pipeline must produce an on-disk artifact and a
# parsed op table whose executable fingerprints JOIN the cost registry;
# (b) the scrape must carry the per-executable HBM series recorded at
# compile time; (c) a fault-injected device-time regression (the chaos
# engine's invoke_delay rule, routed through jax.pure_callback so the
# sleep lands INSIDE device execution where the DegradeDetector
# watches) must auto-trigger a watchdog capture; (d) the gallery must
# be idempotent across two runs — a rescan sees the same entries and
# keeps honoring the bound.
import json
import os
import shutil
import time
import urllib.request

import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np

from nnstreamer_tpu import Pipeline, faults
from nnstreamer_tpu.backends.jax_backend import JaxModel
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.obs import export, profiler
from nnstreamer_tpu.obs.util import cost_entries
from nnstreamer_tpu.obs.watchdog import PipelineWatchdog
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

GDIR = "/tmp/ci_profile_gallery"
shutil.rmtree(GDIR, ignore_errors=True)
profiler.reset_gallery()


def host_op(x):
    # the chaos point: with no rule armed this is a cheap pacing sleep;
    # an installed invoke_delay@devcb rule sleeps HERE, inside the
    # device computation
    faults.maybe_invoke("devcb")
    time.sleep(0.02)
    return np.asarray(x) * 2


def make_pipeline(name, frames):
    model = JaxModel(
        apply=lambda p_, x: jax.pure_callback(
            host_op, jax.ShapeDtypeStruct(x.shape, x.dtype), x),
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.float32, shape=(8,))))
    got = []
    p = Pipeline(name=name)
    src = p.add(DataSrc(
        data=[np.full(8, i, np.float32) for i in range(frames)], name="s"))
    filt = p.add(TensorFilter(framework="jax", model=model, name="devcb"))
    p.link_chain(src, filt, p.add(TensorSink(callback=got.append,
                                             name="out")))
    return p, got


# -- (a) on-demand /profile against a serving pipeline ------------------
p, got = make_pipeline("ci_prof", frames=120)
p.start()
try:
    server = export._server
    assert server is not None, \
        "NNSTPU_METRICS_PORT did not start the endpoint"
    while len(got) < 5:
        time.sleep(0.02)
    with urllib.request.urlopen(
            f"http://{server.host}:{server.port}/profile?seconds=1",
            timeout=60) as resp:
        summary = json.loads(resp.read())
    deadline = time.time() + 120
    while len(got) < 120 and time.time() < deadline:
        time.sleep(0.05)
    assert len(got) == 120, len(got)
finally:
    p.stop()
assert summary["trigger"] == "http", summary["trigger"]
assert summary["ops_total"] > 0, summary
assert os.path.isdir(summary["artifact_dir"])
assert profiler.find_xplane_files(summary["artifact_dir"]), \
    "no raw xplane artifacts on disk"
assert os.path.exists(summary["summary_path"])
fps = set(summary["executables"])
assert fps, "no executable fingerprints observed during the window"
registry_keys = set(cost_entries())
assert fps <= registry_keys, (fps, registry_keys)
attributed = {row.get("executable") for row in summary["ops"]}
assert attributed <= registry_keys | {""}, attributed
assert attributed & registry_keys, \
    "op table rows did not join the cost registry"

# -- (b) compile-time HBM series on the scrape --------------------------
with urllib.request.urlopen(server.url, timeout=30) as resp:
    body = resp.read().decode("utf-8")
assert "nnstpu_executable_hbm_bytes" in body, body[:400]
hbm_lines = [l for l in body.splitlines()
             if l.startswith("nnstpu_executable_hbm_bytes{")]
assert any(f'executable="{fp}"' in l for fp in fps for l in hbm_lines), \
    hbm_lines[:5]
assert "nnstpu_op_time_us" in body
assert 'nnstpu_profile_captures_total{trigger="http",outcome="ok"}' in body

# -- (c) watchdog auto-capture on the injected regression ---------------
# ~30 clean baseline frames arm the Welford baseline (min_samples=8),
# then 8 injected 200ms delays inside device execution blow the
# perfdiff noise band
faults.install("invoke_delay@devcb:after=30,every=1,count=8,ms=200",
               seed=7)
try:
    p2, got2 = make_pipeline("ci_prof_auto", frames=60)
    wd = p2.attach_tracer(PipelineWatchdog(interval_s=0.05))
    p2.start()
    try:
        assert wd._profile_detector is not None, \
            "NNSTPU_OBS_PROFILE_AUTO=true did not arm the detector"
        deadline = time.time() + 120
        while time.time() < deadline:
            with wd._lock:
                if wd._auto_captures >= 1:
                    break
            time.sleep(0.05)
        with wd._lock:
            auto = wd._auto_captures
        assert auto >= 1, "watchdog never auto-captured on the regression"
    finally:
        p2.stop()
finally:
    faults.deactivate()
wd_caps = [s for s in profiler.recent_captures()
           if s["trigger"] == "watchdog"]
assert wd_caps, "no watchdog-triggered capture banked"
assert wd.summary()["profile_auto"]["captures"] >= 1

# -- (d) gallery idempotence across two runs ----------------------------
before = profiler.gallery().entries()
assert before and len(before) <= 4, before
profiler.reset_gallery()  # "restart": force a rescan from disk
after = profiler.gallery().entries()
assert after == before, (before, after)
profiler.capture_profile(seconds=0.1)
assert len(profiler.gallery().entries()) <= 4

export.shutdown_server()
print(f"profiling smoke OK: /profile joined {len(fps)} fingerprint(s) to "
      f"the cost registry, {len(hbm_lines)} HBM series, "
      f"{auto} watchdog auto-capture(s), gallery stable at "
      f"{len(after)} entries")
PY

echo "=== CI RESULT: PASS ===" | tee -a "$LOG"
