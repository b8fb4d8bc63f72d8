"""Report over a telemetry run directory (DESIGN.md §11).

``render(run_dir)`` loads the manifest, metric shards and event log written
by ``obs.shards``/``obs.manifest`` and produces a text report with three
sections, each read from the run directory alone:

1. manifest summary (stack versions, backend, config, guard pins count),
2. metric summary (rounds, final/mean loss, probe means where present),
3. wall-time spans: per-chunk us/round with the compile chunk split out
   from steady state, plus p50/p95 over the steady-state chunks.

``tools/obs_report.py`` is the CLI wrapper.
"""

from __future__ import annotations

import glob
import json
import os

import numpy as np

from repro.obs.shards import span_stats


def load_run(run_dir: str) -> dict:
    """Parse a run directory: ``{"manifest": dict, "rows": [dict],
    "events": [dict]}`` (missing pieces come back empty)."""
    manifest = {}
    mpath = os.path.join(run_dir, "manifest.json")
    if os.path.exists(mpath):
        with open(mpath) as f:
            manifest = json.load(f)
    rows = []
    for path in sorted(glob.glob(os.path.join(run_dir, "metrics-*.jsonl"))):
        with open(path) as f:
            for line in f:
                if line.strip():
                    rows.append(json.loads(line))
    events = []
    epath = os.path.join(run_dir, "events.jsonl")
    if os.path.exists(epath):
        with open(epath) as f:
            for line in f:
                if line.strip():
                    events.append(json.loads(line))
    return {"manifest": manifest, "rows": rows, "events": events}


def _manifest_lines(man: dict) -> list[str]:
    if not man:
        return ["  (no manifest.json)"]
    lines = [f"  run={man.get('run', '?')}  jax={man.get('jax', '?')}"
             f"  jaxlib={man.get('jaxlib', '?')}"
             f"  backend={man.get('backend', '?')}"
             f"  devices={man.get('device_count', '?')}"]
    if "mesh" in man:
        axes = "x".join(f"{k}={v}" for k, v in man["mesh"].items())
        lines.append(f"  mesh: {axes}  topology={man.get('topology', '-')}")
    if "sketch" in man:
        sk = man["sketch"]
        lines.append(f"  sketch: kind={sk.get('kind', '?')}"
                     f" ratio={sk.get('ratio', '?')}")
    if "guard_pins" in man:
        lines.append(f"  guard pins embedded: {len(man['guard_pins'])}")
    return lines


def _metric_lines(rows: list[dict]) -> list[str]:
    if not rows:
        return ["  (no metric shards)"]
    # last-wins over t: a supervised run re-emits retried spans
    by_t = {r["t"]: r for r in rows if r.get("kind") == "metrics"}
    ts = sorted(by_t)
    lines = [f"  rounds: {len(ts)} (t {ts[0]}..{ts[-1]};"
             f" {len(rows)} shard rows)"]
    keys = sorted({k for r in by_t.values() for k in r}
                  - {"kind", "t"})
    for k in keys:
        vals = np.asarray([by_t[t][k] for t in ts if k in by_t[t]],
                          np.float64)
        if vals.size == 0:
            continue
        lines.append(f"  {k:12s} final={vals[-1]:12.6g}"
                     f"  mean={np.nanmean(vals):12.6g}"
                     f"  max={np.nanmax(vals):12.6g}")
    return lines


def _span_lines(events: list[dict]) -> list[str]:
    spans = [e for e in events if e.get("kind") == "span"]
    if not spans:
        return ["  (no spans recorded)"]
    lines = []
    steady_per_round = []
    for s in spans:
        n = max(1, int(s["t1"]) - int(s["t0"]))
        per_round = s["seconds"] / n
        tag = "compile+run" if s.get("compile") else "steady"
        lines.append(f"  rounds {s['t0']:>5}..{s['t1']:<5}"
                     f" {s['seconds']*1e3:10.1f}ms"
                     f"  {per_round*1e6:10.0f}us/round  [{tag}]")
        if not s.get("compile"):
            steady_per_round.append(per_round)
    st = span_stats(steady_per_round)
    if st:
        lines.append(f"  steady-state per-round: p50={st['p50_us']:.0f}us"
                     f"  p95={st['p95_us']:.0f}us"
                     f"  ({len(steady_per_round)} chunks)")
    recs = [e for e in events if e.get("kind") == "recovery"]
    for r in recs:
        lines.append(f"  recovery: retry {r.get('retry')}"
                     f" fault<{r.get('t_fault')}"
                     f" resume@{r.get('t_resume')}"
                     f" depth={r.get('depth')} ({r.get('reason', '')})")
    return lines


def render(run_dir: str) -> str:
    run = load_run(run_dir)
    out = [f"== telemetry run report: {run_dir} ==", "", "-- manifest --"]
    out += _manifest_lines(run["manifest"])
    out += ["", "-- metrics --"]
    out += _metric_lines(run["rows"])
    out += ["", "-- wall-time spans --"]
    out += _span_lines(run["events"])
    return "\n".join(out) + "\n"
