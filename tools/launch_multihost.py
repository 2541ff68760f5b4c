#!/usr/bin/env python
"""Multi-host job launcher: the torchrun/mpirun analog for nnstreamer_tpu.

The reference's concurrency never leaves one process (no NCCL/MPI — survey
§2.6), so it never needed a launcher.  The TPU-native framework scales the
*compute* across processes (``parallel/mesh.py``), and this tool is the
missing runtime piece: spawn N worker processes on this host, wire them to
one coordinator, stream their output, and fail fast as a unit.

    python tools/launch_multihost.py --nprocs 2 --devices-per-proc 2 \\
        worker.py [worker args...]

Every worker inherits the ``NNS_MULTIHOST_*`` contract and calls
``parallel.mesh.init_from_env()``; after that ``jax.devices()`` spans the
job and a ``make_mesh`` lays dp/tp axes over it (XLA routes collectives
over ICI within a host, DCN across — here the CPU cross-process
transport).

Single-host multi-process on the CPU is the envelope the tests execute
(a chip belongs to one process, so the ranks run on CPU devices); on a
real multi-host TPU pod the same worker runs unmodified under the platform's per-host launcher
(no env vars needed — jax auto-discovers the coordinator), which is why
the contract lives in ``init_from_env`` and not in worker code.

Exit code: 0 iff every worker exited 0.  On the first failure the
remaining workers are killed (the mpirun discipline — a half-dead
collective job otherwise hangs in the next psum).
"""

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def stream(proc: subprocess.Popen, rank: int) -> None:
    for line in proc.stdout:  # type: ignore[union-attr]
        sys.stdout.write(f"[rank {rank}] {line}")
        sys.stdout.flush()


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--nprocs", type=int, default=2,
                    help="worker process count (default 2)")
    ap.add_argument("--devices-per-proc", type=int, default=None,
                    help="virtual CPU devices per worker (sets XLA_FLAGS "
                         "xla_force_host_platform_device_count; omit on "
                         "real accelerator hosts)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of an EXTERNAL process-0 coordinator "
                         "(for true multi-host: run the launcher once per "
                         "host with --rank-offset); default: a free local "
                         "port")
    ap.add_argument("--rank-offset", type=int, default=0,
                    help="first rank spawned by this launcher invocation")
    ap.add_argument("--total-procs", type=int, default=None,
                    help="job-wide process count when launching across "
                         "hosts (default: --nprocs)")
    ap.add_argument("worker", help="python script every worker runs")
    ap.add_argument("worker_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()

    coord = args.coordinator or f"localhost:{free_port()}"
    total = args.total_procs or args.nprocs

    procs = []
    for i in range(args.nprocs):
        rank = args.rank_offset + i
        env = dict(os.environ)
        env["NNS_MULTIHOST_COORD"] = coord
        env["NNS_MULTIHOST_NPROCS"] = str(total)
        env["NNS_MULTIHOST_PROC_ID"] = str(rank)
        if args.devices_per_proc:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.devices_per_proc}"
            ).strip()
            env["JAX_PLATFORMS"] = "cpu"
        procs.append(subprocess.Popen(
            [sys.executable, args.worker, *args.worker_args],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        ))

    threads = [threading.Thread(target=stream, args=(p, args.rank_offset + i),
                                daemon=True)
               for i, p in enumerate(procs)]
    for t in threads:
        t.start()

    def terminate(survivors, grace_s=10.0):
        """mpirun discipline, two-step: TERM, then KILL after ONE shared
        grace period — a worker whose SIGTERM handler blocks (checkpoint
        cleanup, stuck collective) must not hang the launcher forever,
        and N stuck ranks must not stack N grace periods."""
        import time

        for j in survivors:
            if procs[j].poll() is None:
                procs[j].send_signal(signal.SIGTERM)
        deadline = time.monotonic() + grace_s
        for j in survivors:
            try:
                procs[j].wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                sys.stderr.write(
                    f"[launcher] rank {args.rank_offset + j} ignored "
                    "SIGTERM; killing\n")
                procs[j].kill()
                procs[j].wait()

    rc = 0
    alive = set(range(len(procs)))
    try:
        while alive:
            for i in sorted(alive):
                r = procs[i].poll()
                if r is None:
                    continue
                alive.discard(i)
                if r != 0 and rc == 0:
                    rc = r
                    sys.stderr.write(
                        f"[launcher] rank {args.rank_offset + i} exited "
                        f"{r}; killing remaining workers\n")
                    terminate(sorted(alive))
                    alive.clear()
            if alive:
                try:
                    procs[next(iter(alive))].wait(timeout=0.2)
                except subprocess.TimeoutExpired:
                    pass
    except KeyboardInterrupt:
        terminate(sorted(alive))
        rc = 130
    for t in threads:
        t.join(timeout=5)
    return rc


if __name__ == "__main__":
    sys.exit(main())
