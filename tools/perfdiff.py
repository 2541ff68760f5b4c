#!/usr/bin/env python
"""perfdiff: typed regression verdicts over persisted performance evidence.

Compares two snapshots of ``COST_MODEL.json`` (per-stage leg aggregates
from the cost-observatory tracer) and emits one typed verdict per
comparable series:

- ``flat``       — the delta sits inside the noise band;
- ``improved``   — current is better (lower µs) by more than the band;
- ``regressed``  — current is worse by more than the band; the verdict
  carries WHICH leg regressed (``dispatch`` / ``device_exec`` /
  ``queue_wait`` / ``wire``), because "the pipeline got slower" is not
  actionable and "the wire leg got slower" is.

The noise band is derived from the evidence itself: stage legs persist
Welford aggregates (count/mean/m2), so the band is
``max(sigmas × sample-std, min_rel × baseline, min_abs)`` — a leg that
historically swings 40% does not page anyone over a 10% delta.

A self-compare (baseline == current) is ``flat`` by construction — the
CI smoke pins that.  The report is NON-FATAL by default (exit 0, it is
an observability artifact, not a gate); ``--strict`` exits 1 when any
verdict regressed.  Every regression also increments
``nnstpu_perf_regression_total{leg}`` so a scrape of a long-lived
process that runs perfdiff periodically shows regression pressure over
time.

Usage::

    python tools/perfdiff.py                       # self-compare (flat)
    python tools/perfdiff.py --baseline old.json --current new.json
    python -m tools.perfdiff --json --strict
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from nnstreamer_tpu.obs import costmodel  # noqa: E402
from nnstreamer_tpu.obs.metrics import REGISTRY  # noqa: E402

DEFAULT_SIGMAS = costmodel.BAND_SIGMAS
DEFAULT_MIN_REL = costmodel.BAND_MIN_REL
DEFAULT_MIN_ABS_US = costmodel.BAND_MIN_ABS_US


def _regression_counter(registry=None):
    registry = registry if registry is not None else REGISTRY
    return registry.counter(
        "nnstpu_perf_regression_total",
        "Regressed perfdiff verdicts, by leg "
        "(dispatch/device_exec/queue_wait/wire)", ("leg",))


def stage_band_us(leg_stat: dict, sigmas: float = DEFAULT_SIGMAS,
                  min_rel: float = DEFAULT_MIN_REL,
                  min_abs_us: float = DEFAULT_MIN_ABS_US) -> float:
    """Noise band (µs) for one persisted stage-leg aggregate — the one
    implementation lives in :func:`costmodel.leg_band_us` (forensics
    scores outliers with the same band)."""
    return costmodel.leg_band_us(leg_stat, sigmas=sigmas, min_rel=min_rel,
                                 min_abs_us=min_abs_us)


def diff_cost_models(baseline: dict, current: dict,
                     sigmas: float = DEFAULT_SIGMAS,
                     min_rel: float = DEFAULT_MIN_REL,
                     min_abs_us: float = DEFAULT_MIN_ABS_US) -> List[dict]:
    """One verdict per (stage, leg) present in BOTH documents."""
    verdicts: List[dict] = []
    b_stages = baseline.get("stages") or {}
    c_stages = current.get("stages") or {}
    for key in sorted(set(b_stages) & set(c_stages)):
        b_legs = b_stages[key].get("legs") or {}
        c_legs = c_stages[key].get("legs") or {}
        for leg in sorted(set(b_legs) & set(c_legs)):
            b = float(b_legs[leg].get("mean_us") or 0.0)
            c = float(c_legs[leg].get("mean_us") or 0.0)
            band = stage_band_us(b_legs[leg], sigmas=sigmas,
                                 min_rel=min_rel, min_abs_us=min_abs_us)
            delta = c - b
            if abs(delta) <= band:
                verdict = "flat"
            elif delta < 0:
                verdict = "improved"
            else:
                verdict = "regressed"
            verdicts.append({
                "kind": "stage", "key": key, "leg": leg,
                "baseline_us": round(b, 3), "current_us": round(c, 3),
                "delta_us": round(delta, 3), "band_us": round(band, 3),
                "verdict": verdict,
            })
    return verdicts


def overall_verdict(verdicts: List[dict]) -> str:
    kinds = {v["verdict"] for v in verdicts}
    if "regressed" in kinds:
        return "regressed"
    if "improved" in kinds:
        return "improved"
    return "flat"


def report(verdicts: List[dict], registry=None) -> dict:
    """Counts + overall verdict; bumps the regression counter per
    regressed leg."""
    counter = _regression_counter(registry)
    regressed_legs: Dict[str, int] = {}
    for v in verdicts:
        if v["verdict"] == "regressed":
            counter.inc(leg=v["leg"])
            regressed_legs[v["leg"]] = regressed_legs.get(v["leg"], 0) + 1
    return {
        "verdict": overall_verdict(verdicts),
        "compared": len(verdicts),
        "flat": sum(1 for v in verdicts if v["verdict"] == "flat"),
        "improved": sum(1 for v in verdicts if v["verdict"] == "improved"),
        "regressed": sum(1 for v in verdicts if v["verdict"] == "regressed"),
        "regressed_legs": regressed_legs,
        "verdicts": verdicts,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="typed perf-regression verdicts over COST_MODEL.json")
    ap.add_argument("--baseline", default=None,
                    help="baseline COST_MODEL.json (default: the "
                         "configured live path — self-compare)")
    ap.add_argument("--current", default=None,
                    help="current COST_MODEL.json (default: the "
                         "configured live path)")
    ap.add_argument("--sigmas", type=float, default=DEFAULT_SIGMAS)
    ap.add_argument("--min-rel", type=float, default=DEFAULT_MIN_REL)
    ap.add_argument("--min-abs-us", type=float, default=DEFAULT_MIN_ABS_US)
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any verdict regressed (default: "
                         "always exit 0 — the report is non-fatal)")
    args = ap.parse_args(argv)

    live = costmodel.cost_model_path()
    base_doc = costmodel.load_cost_model(args.baseline or live)
    cur_doc = costmodel.load_cost_model(args.current or live)
    verdicts = diff_cost_models(base_doc, cur_doc, sigmas=args.sigmas,
                                min_rel=args.min_rel,
                                min_abs_us=args.min_abs_us)

    rep = report(verdicts)
    if args.json:
        print(json.dumps(rep, indent=1, sort_keys=True))
    else:
        for v in verdicts:
            print(f"{v['verdict']:>9}  {v['key']} [{v['leg']}]  "
                  f"{v['baseline_us']} -> {v['current_us']} us  "
                  f"(band {v['band_us']})")
        print(f"# perfdiff: {rep['verdict']} — {rep['compared']} compared, "
              f"{rep['flat']} flat / {rep['improved']} improved / "
              f"{rep['regressed']} regressed"
              + (f" {rep['regressed_legs']}" if rep["regressed_legs"]
                 else ""))
    if args.strict and rep["regressed"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
