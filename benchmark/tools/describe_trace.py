"""Look at a profiler trace by hand: every plane and line with its event
count, and the first events of each device line.

    python3 benchmark/tools/describe_trace.py .bench_scratch/<cell>/profile
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH_DIR.parent))


def main() -> None:
    from benchmark.lib import reduce_trace

    path = reduce_trace.find_xplane(sys.argv[1])
    if path is None:
        raise SystemExit(f"no .xplane.pb under {sys.argv[1]}")
    trace = reduce_trace.load_xplane(path, describe=True)
    print(path, path.stat().st_size, "bytes")
    for plane, line, n in trace["lines"]:
        print(f"{n:9d}  {plane}  |  {line}")
    for plane, dev in trace["devices"].items():
        for kind in ("programs", "ops"):
            names = {}
            for name, _, dur in dev[kind]:
                names[name] = names.get(name, 0.0) + dur / 1e9
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            print(plane, kind, json.dumps(top))
        break
    print("host", json.dumps(trace["host"][:8]))


if __name__ == "__main__":
    main()
