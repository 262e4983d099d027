#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, a sample of events with
their stats, and a census of stat keys. Writes JSON to the path given.

    python benchmark/tools/trace_dump.py <trace dir> <out.json> [events per line]
"""

import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import trace_reduce  # noqa: E402


def _stats(event):
    try:
        return {str(k): v for k, v in event.stats}
    except Exception:  # noqa: BLE001 - a stat that does not decode is skipped
        return {}


def main() -> int:
    import jax

    path = trace_reduce.find_trace(sys.argv[1])
    keep = int(sys.argv[3]) if len(sys.argv) > 3 else 400
    data = jax.profiler.ProfileData.from_file(path)
    out = {"file": path, "bytes": os.path.getsize(path), "planes": []}
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            census = collections.Counter()
            names = collections.Counter()
            sample = []
            for index, event in enumerate(events):
                names[event.name] += 1
                stats = _stats(event)
                census.update(stats.keys())
                if index < keep:
                    sample.append([
                        event.name, event.start_ns, event.duration_ns,
                        {k: str(v)[:120] for k, v in stats.items()},
                    ])
            lines.append({
                "name": line.name, "events": len(events),
                "stat_keys": dict(census),
                "top_names": names.most_common(40),
                "sample": sample,
            })
        out["planes"].append({"name": plane.name, "lines": lines})
    with open(sys.argv[2], "w") as handle:
        json.dump(out, handle)
    print(f"{path}: {out['bytes']} bytes; planes "
          f"{[(p['name'], [(l['name'], l['events']) for l in p['lines']]) for p in out['planes']]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
