"""Look at one xplane file by hand: planes, lines, and the names that took
most time on each device line, with the benchmark's host annotations.

    python benchmarks/tools/trace_dump.py <file.xplane.pb> [top]
"""

import sys


def main(path: str, top: int = 25) -> int:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            total, count = {}, {}
            for e in events:
                total[e.name] = total.get(e.name, 0) + e.duration_ns
                count[e.name] = count.get(e.name, 0) + 1
            start = min(e.start_ns for e in events)
            end = max(e.start_ns + e.duration_ns for e in events)
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{len(total)} names, span {(end - start) / 1e6:.3f} ms "
                  f"from {start}")
            if not (plane.name.startswith("/device:")
                    or any(n.startswith("bench.") for n in total)):
                continue
            for name in sorted(total, key=total.get, reverse=True)[:top]:
                if (not plane.name.startswith("/device:")
                        and not name.startswith("bench.")):
                    continue
                print(f"    {total[name] / 1e6:10.3f} ms  x{count[name]:<6d} "
                      f"{name[:150]}")
        if plane.name.startswith("/device:"):
            for line in lines:
                for e in list(line.events)[:1]:
                    print(f"  first event of {line.name!r}: {e.name[:100]!r} "
                          f"stats {[(k, str(v)[:80]) for k, v in e.stats][:12]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25))
