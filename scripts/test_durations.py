#!/usr/bin/env python
"""Seconds and cases by test file, from a whole run's junit file.

    python scripts/test_durations.py /tmp/_t1.xml > tests/durations.txt

`tests/durations.txt` is both the table ROADMAP.md's "What the driver runs"
is written from and what `tests/conftest.py` orders the files by: with
`--dist loadfile` a worker takes whole files, so the longest go first."""

import collections
import sys
import xml.etree.ElementTree as ET


def main(path):
    by = collections.defaultdict(lambda: [0.0, 0])
    for case in ET.parse(path).getroot().iter("testcase"):
        name = case.get("classname").split("tests.", 1)[1].replace(".", "/")
        by[name + ".py"][0] += float(case.get("time"))
        by[name + ".py"][1] += 1
    for name, (seconds, cases) in sorted(by.items(), key=lambda kv: -kv[1][0]):
        print(f"{seconds:7.1f} {cases:4d} {name}")


if __name__ == "__main__":
    main(sys.argv[1])
