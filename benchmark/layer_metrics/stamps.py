"""Reader ``stamps``: per-layer metrics from the harness's own stamps."""

from __future__ import annotations

from typing import Optional

from .. import arithmetic


def host_path_ms_p50(ctx) -> Optional[float]:
    """Median, over the frames pushed inside the window, of the time from a
    frame's push to the ``device_dispatch`` hook of the batch that carries it
    (round ``k`` carries frame ``k`` of every stream)."""
    res = ctx.result
    if not res.dispatch_ns:
        return None
    waits = []
    for pushes in res.push_ns:
        for k, tp in enumerate(pushes):
            if res.t0_ns <= tp < res.t1_ns and k < len(res.dispatch_ns):
                waits.append((res.dispatch_ns[k] - tp) / 1e6)
    return arithmetic.percentile(waits, 50) if waits else None
