"""Look at one trace by hand: planes, lines, and the operations that
took most time on each device line.

    python3 benchmark/inspect_trace.py [trace_dir] [top]

Default: the newest trace under ``.cache/benchmark/trace``.
"""

from __future__ import annotations

import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv) -> int:
    sys.path.insert(0, ROOT)
    from jax.profiler import ProfileData

    pattern = os.path.join(argv[0] if argv else os.path.join(
        ROOT, ".cache", "benchmark", "trace"), "**", "*.xplane.pb")
    files = glob.glob(pattern, recursive=True)
    if not files:
        print(f"no trace under {pattern}", file=sys.stderr)
        return 1
    top = int(argv[1]) if len(argv) > 1 else 40
    path = max(files, key=os.path.getmtime)
    print("trace", path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            total: dict = {}
            count = 0
            for ev in line.events:
                count += 1
                t = total.setdefault(ev.name, [0.0, 0])
                t[0] += ev.duration_ns
                t[1] += 1
            print(f"  LINE {line.name!r}: {count} events, "
                  f"{len(total)} names")
            if plane.name.startswith("/device:"):
                for name, (ns, k) in sorted(
                        total.items(), key=lambda kv: -kv[1][0])[:top]:
                    print(f"    {ns * 1e-6:12.3f} ms  x{k:<6d} {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
