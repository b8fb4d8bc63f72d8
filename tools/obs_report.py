"""Render a telemetry run directory as a text report (DESIGN.md §11).

    python tools/obs_report.py RUN_DIR

Sections: manifest summary, per-key metric summary (last-wins over
duplicate rounds from supervised retries), wall-time spans with the
compile chunk split from steady state (p50/p95 per round), and recovery
events.  See ``repro.obs.report`` for the implementation.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print(__doc__)
        return 2
    if not os.path.isdir(argv[0]):
        print(f"# not a run directory: {argv[0]}")
        return 2
    from repro.obs.report import render
    sys.stdout.write(render(argv[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
