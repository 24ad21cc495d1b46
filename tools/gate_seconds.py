#!/usr/bin/env python
"""Rewrite tests/file_seconds.json from the junit file of one whole run of
the tier-1 gate (ROADMAP.md, "Tier-1 verify": ``--junitxml=/tmp/_t1.xml``).

    python tools/gate_seconds.py /tmp/_t1.xml

tests/conftest.py starts the files in the record's order, longest first.
Prints the files by seconds and what a model of ``-n 6 --dist loadfile``
(each worker holds two files, the next file goes to whoever finishes one)
reads for the busiest worker in that order: the gate's wall time less
start-up.
"""

import collections
import json
import os
import sys
import xml.etree.ElementTree as ET

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORD = os.path.join(REPO, "tests", "file_seconds.json")


def file_seconds(junit_path):
    seconds = collections.Counter()
    for case in ET.parse(junit_path).getroot().iter("testcase"):
        # classname is the module's dotted path, plus the class for a method
        parts = case.get("classname").split(".")
        while parts and not os.path.exists(
                os.path.join(REPO, *parts) + ".py"):
            parts.pop()
        if parts:
            seconds["/".join(parts) + ".py"] += float(case.get("time"))
    return {path: round(s, 1) for path, s in seconds.most_common()}


def busiest_worker(seconds_in_order, workers=6, held=2):
    """Seconds until the last worker is done when files are handed out in
    order, ``held`` to each worker at the start and one more to a worker
    each time it finishes one."""
    todo = list(seconds_in_order)
    queues = [[todo.pop(0) for _ in range(held) if todo]
              for _ in range(workers)]
    done_at = [0.0] * workers
    while any(queues):
        w = min((w for w in range(workers) if queues[w]),
                key=lambda w: done_at[w] + queues[w][0])
        done_at[w] += queues[w].pop(0)
        if todo:
            queues[w].append(todo.pop(0))
    return max(done_at)


def main():
    seconds = file_seconds(sys.argv[1])
    with open(RECORD, "w") as f:
        json.dump(seconds, f, indent=0)
        f.write("\n")
    for path, s in seconds.items():
        print(f"{s:8.1f}  {path}")
    print(f"{sum(seconds.values()):8.1f}  case-seconds in {len(seconds)} "
          f"files; busiest of 6 workers in this order: "
          f"{busiest_worker(seconds.values()):.0f} s")


if __name__ == "__main__":
    main()
