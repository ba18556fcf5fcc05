"""Offline goodput / MFU / step-phase report for a training run.

Reads ONE artifact and prints the training-telemetry breakdown a live
scrape would show (OBSERVABILITY.md "Training telemetry"):

- a Prometheus exposition body (`curl :9090/metrics > snap.txt`),
- a registry snapshot JSON (`MetricsRegistry.snapshot()` /
  `Snapshotter` output),
- a flight-recorder bundle (`flightrec-*.json`) — uses the metrics
  snapshot embedded in its `state` and also names the trigger, the
  stuck step and the tail of the event ring.

Run: python tools/goodput_report.py <file>
"""

import argparse
import json
import math
import sys

import _bootstrap  # noqa: F401  (repo path)


def _is_histogram_entry(value) -> bool:
    return isinstance(value, dict) and "count" in value


def _split_name(key):
    """`name{a=x,b=y}` -> (name, "a=x,b=y")."""
    if "{" in key:
        name, rest = key.split("{", 1)
        return name, rest.rstrip("}")
    return key, ""


def _quantile_from_buckets(buckets, count, q):
    """Upper-edge estimate of quantile q from cumulative (le, n)."""
    if not count:
        return math.nan
    target = q * count
    for le, cum in buckets:
        if cum >= target:
            return le
    return buckets[-1][0] if buckets else math.nan


def _flatten_exposition(text):
    """Prometheus text -> (scalars, hists) in snapshot-key format."""
    from paddle_tpu.obs.fleetmetrics import parse_exposition
    scalars, hists = {}, {}
    for name, fam in parse_exposition(text).items():
        if fam.kind == "histogram":
            per = {}
            for suffix, labels, le, value in fam.samples:
                entry = per.setdefault(labels, {"buckets": []})
                if suffix == "_bucket" and le is not None:
                    edge = math.inf if le == "+Inf" else float(le)
                    entry["buckets"].append((edge, value))
                elif suffix == "_sum":
                    entry["sum"] = value
                elif suffix == "_count":
                    entry["count"] = value
            for labels, entry in per.items():
                lbl = ",".join(f"{n}={v}" for n, v in labels)
                k = name + ("{" + lbl + "}" if lbl else "")
                count = entry.get("count", 0)
                buckets = sorted(entry["buckets"])
                hists[k] = {
                    "count": count,
                    "sum": entry.get("sum", 0.0),
                    "mean": (entry.get("sum", 0.0) / count) if count else 0,
                    "p50": _quantile_from_buckets(buckets, count, 0.5),
                    "p99": _quantile_from_buckets(buckets, count, 0.99),
                }
        else:
            for suffix, labels, _, value in fam.samples:
                if suffix:
                    continue
                lbl = ",".join(f"{n}={v}" for n, v in labels)
                scalars[name + ("{" + lbl + "}" if lbl else "")] = value
    return scalars, hists


def _flatten_snapshot(snap):
    scalars, hists = {}, {}
    for key, value in snap.items():
        if _is_histogram_entry(value):
            hists[key] = value
        elif isinstance(value, (int, float)):
            scalars[key] = float(value)
    return scalars, hists


def load(path):
    """Returns (scalars, hists, flightrec_meta_or_None)."""
    with open(path) as f:
        text = f.read()
    stripped = text.lstrip()
    if not stripped.startswith("{"):
        scalars, hists = _flatten_exposition(text)
        return scalars, hists, None
    data = json.loads(text)
    if "trigger" in data and "events" in data:          # flightrec bundle
        state = data.get("state") or {}
        snap = state.get("metrics", state)
        scalars, hists = _flatten_snapshot(
            snap if isinstance(snap, dict) else {})
        meta = {"trigger": data.get("trigger"),
                "context": data.get("context", {}),
                "events": data.get("events", [])}
        return scalars, hists, meta
    return (*_flatten_snapshot(data), None)


def _by_prefix(table, prefix):
    return {k: v for k, v in sorted(table.items())
            if _split_name(k)[0].startswith(prefix)}


def _fmt(v):
    if v is None or (isinstance(v, float) and math.isnan(v)):
        return "-"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def report(scalars, hists, meta, out=sys.stdout):
    w = out.write
    if meta is not None:
        w(f"flight recorder bundle: trigger={meta['trigger']} "
          f"context={json.dumps(meta['context'])}\n")
        tail = meta["events"][-5:]
        if tail:
            w(f"last {len(tail)} events in the ring:\n")
            for rec in tail:
                w(f"  {json.dumps(rec)}\n")
        w("\n")

    w("== goodput ==\n")
    gp = scalars.get("ptpu_train_goodput")
    w(f"goodput:              {_fmt(gp)}\n")
    w(f"productive seconds:   "
      f"{_fmt(scalars.get('ptpu_goodput_productive_seconds_total'))}\n")
    lost = _by_prefix(scalars, "ptpu_goodput_lost_seconds_total")
    for key, value in lost.items():
        _, labels = _split_name(key)
        w(f"lost ({labels or 'total'}):  {_fmt(value)} s\n")
    events = _by_prefix(scalars, "ptpu_goodput_events_total")
    for key, value in events.items():
        _, labels = _split_name(key)
        w(f"events ({labels or 'total'}): {_fmt(value)}\n")

    w("\n== efficiency ==\n")
    w(f"mfu:                  {_fmt(scalars.get('ptpu_train_mfu'))}\n")
    w(f"train compiles:       "
      f"{_fmt(scalars.get('ptpu_train_compiles'))}\n")
    w(f"steps total:          "
      f"{_fmt(scalars.get('ptpu_train_steps_total'))}\n")

    w("\n== step phases (ms) ==\n")
    phase_fams = ("ptpu_train_phase_ms", "ptpu_train_step_ms",
                  "ptpu_train_input_wait_ms")
    any_phase = False
    for fam in phase_fams:
        for key, h in _by_prefix(hists, fam).items():
            any_phase = True
            w(f"{key:44s} n={_fmt(h.get('count'))} "
              f"mean={_fmt(h.get('mean'))} p50={_fmt(h.get('p50'))} "
              f"p99={_fmt(h.get('p99'))}\n")
    if not any_phase:
        w("(no step-phase histograms in this artifact)\n")

    hbm = _by_prefix(scalars, "ptpu_hbm_")
    if hbm:
        w("\n== device memory ==\n")
        for key, value in hbm.items():
            w(f"{key:44s} {_fmt(value)}\n")

    strag = _by_prefix(scalars, "ptpu_train_straggler")
    disp = scalars.get("ptpu_train_step_dispersion")
    if strag or disp is not None:
        w("\n== workers ==\n")
        for key, value in strag.items():
            w(f"{key:44s} {_fmt(value)}\n")
        if disp is not None:
            w(f"step dispersion (max/min): {_fmt(disp)}\n")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("artifact",
                    help="/metrics body, snapshot JSON, or flightrec-*.json")
    args = ap.parse_args()
    scalars, hists, meta = load(args.artifact)
    if not scalars and not hists:
        sys.stderr.write("no metric series found in artifact\n")
        return 1
    report(scalars, hists, meta)
    return 0


if __name__ == "__main__":
    sys.exit(main())
