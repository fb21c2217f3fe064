"""Look at a trace by hand: planes, lines, and each device line's ops by
time.  ``python3 -m chipbench.tools.trace_summary <dir or .xplane.pb> [n]``"""

from __future__ import annotations

import os
import sys

from chipbench import trace_reduce


def main(argv) -> int:
    from jax.profiler import ProfileData
    path = argv[0]
    top = int(argv[1]) if len(argv) > 1 else 25
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    data = ProfileData.from_file(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r} events={len(events)}")
            if not plane.name.startswith("/device:") and \
                    not any(e.name.startswith("chipbench/") for e in events):
                continue
            by_name: dict = {}
            for e in events:
                name = (trace_reduce.op_name(e.name)
                        if plane.name.startswith("/device:") else e.name)
                slot = by_name.setdefault(name, [0, 0.0])
                slot[0] += 1
                slot[1] += e.duration_ns
            for name, (n, ns) in sorted(by_name.items(),
                                        key=lambda kv: -kv[1][1])[:top]:
                print(f"    {ns / 1e6:12.3f} ms x{n:7d}  {name[:120]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
