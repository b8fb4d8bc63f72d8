#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at the cell's size.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,... \
        [--control-seeds 11,12,13] [--out <file.json>]

In one process, for each seed: the program's first rounds through the timed
path (no window) against the reference -- the sound runs, whose largest
numbers are the lower readings.  For each control seed, the readings that
have to fail: the control, the program's own lower-precision path
(parameters in bfloat16 where the configuration states float32), against
the reference; two faults planted in the reference put in the program's
place, half of each client's batch left out and a desketch with the wrong
key; and a state left unchanged, which needs no run (its readings are
zeros and the reference's losses).  The benchmark's own runs never run
this; ``tests/bench`` keeps it at a small size.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

# the nearest precision below the configuration's, on the program's own path
LOWER = {"float32": "bfloat16"}
FAULTS = ("half_batch", "wrong_key")


def readings(spec, seed, *, dtype=None):
    """The program's readings at ``seed``, driven through its timed path."""
    import gc
    from bench import harness, program
    prog = program.build(spec, seed, dtype=dtype)
    out = harness.readings_of(harness.drive(prog, 0.0))
    del prog
    gc.collect()
    return out


def unchanged(ref: dict) -> dict:
    """What a step that returns its state unchanged would read."""
    import numpy as np
    return dict({k: np.zeros_like(v) for k, v in ref.items()
                 if k != "loss"}, loss=ref["loss"])


def calibrate(spec, seeds, control_seeds, *, log=print) -> dict:
    from bench import compare, harness
    harness.COUNTER = harness.COUNTER or harness.CompileCounter()
    rows = {k: {} for k in ("sound", "control", "state_unchanged") + FAULTS}
    refs = {}
    for s in seeds:
        t0 = time.perf_counter()
        got = readings(spec, s)
        refs[s] = harness.reference_readings(spec, s)
        log(f"reference seconds: {refs[s].pop('seconds')}")
        rows["sound"][s] = compare.numbers(got, refs[s])
        log(f"sound seed {s}: {rows['sound'][s]} "
            f"({time.perf_counter() - t0:.1f} s)")
    for s in control_seeds:
        if s not in refs:
            refs[s] = harness.reference_readings(spec, s)
            refs[s].pop("seconds")
        ref = refs[s]
        ctrl = readings(spec, s, dtype=LOWER[spec.config["dtype"]])
        rows["control"][s] = compare.numbers(ctrl, ref)
        rows["state_unchanged"][s] = compare.numbers(unchanged(ref), ref)
        for f in FAULTS:
            got = harness.reference_readings(spec, s, **{f: True})
            got.pop("seconds")
            rows[f][s] = compare.numbers(got, ref)
        log(f"control seed {s}: " + "; ".join(
            f"{k} {rows[k][s]}" for k in rows if s in rows[k]
            and k != "sound"))
    summary = {}
    for kind, by_seed in rows.items():
        if by_seed:
            summary[kind] = {k: [min(r[k] for r in by_seed.values()),
                                 max(r[k] for r in by_seed.values())]
                             for k in compare.NUMBERS}
    return {"rows": rows, "summary": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    a = ap.parse_args(argv)
    from bench import harness
    spec = harness.load_spec(a.workload)
    harness.require_chips(spec.chips)
    harness.enable_cache()
    ints = lambda s: [int(x) for x in s.split(",") if x]
    res = calibrate(spec, ints(a.seeds), ints(a.control_seeds))
    print(json.dumps(res["summary"]))
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
