#!/usr/bin/env python
"""Compare the optimized HLO of a program a graphcheck pre-flight flagged
with its fixed variant, by per-program op histograms and their diff.

Usage:
    python tools/hlo_diff.py --from-graphcheck REPORT.json \\
                             [--against OTHER.json|HLO.txt]
        take the HLO artifact recorded in a graphcheck pre-flight report
        (run training once with MXNET_TPU_PREFLIGHT=1
        MXNET_TPU_PREFLIGHT_HLO=1 to produce it) and diff it against a
        second report's artifact or a raw HLO text file.  This is how a
        flagged program is compared with its fixed variant WITHOUT
        rerunning training; with no --against, prints the single
        program's op histogram.
"""
import collections
import json
import os
import re
import sys


def histogram(path):
    ops = collections.Counter()
    for line in open(path):
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([a-z][\w\-]*)\(", line)
        if m:
            ops[m.group(1)] += 1
    return ops


def hlo_from_report(path):
    """Resolve an HLO text path from a graphcheck/pre-flight report JSON
    (its ``artifacts.hlo`` entry) or pass a raw HLO text path through."""
    if not path.endswith(".json"):
        return path
    with open(path) as f:
        rep = json.load(f)
    hlo = (rep.get("artifacts") or {}).get("hlo")
    if not hlo:
        raise SystemExit(
            "%s records no HLO artifact — rerun the pre-flight with "
            "MXNET_TPU_PREFLIGHT_HLO=1 (see docs/static-analysis.md)"
            % path)
    if not os.path.isfile(hlo):
        raise SystemExit("HLO artifact %s (from %s) is missing"
                         % (hlo, path))
    return hlo


def print_diff(path_a, path_b, label_a, label_b):
    ha, hb = histogram(path_a), histogram(path_b)
    print("%-28s %10s %10s %8s" % ("op", label_a[:10], label_b[:10],
                                   "delta"))
    for op in sorted(set(ha) | set(hb), key=lambda o: -(ha[o] + hb[o])):
        if ha[op] or hb[op]:
            print("%-28s %10d %10d %+8d"
                  % (op, ha[op], hb[op], ha[op] - hb[op]))
    print("\ntotal lines: %s=%d %s=%d"
          % (label_a, len(open(path_a).read().splitlines()),
             label_b, len(open(path_b).read().splitlines())))


def main():
    argv = sys.argv[1:]
    if "--from-graphcheck" not in argv:
        raise SystemExit(__doc__)
    i = argv.index("--from-graphcheck")
    report = argv[i + 1] if i + 1 < len(argv) else None
    if not report:
        raise SystemExit("--from-graphcheck needs a report path")
    flagged = hlo_from_report(report)
    against = None
    if "--against" in argv:
        j = argv.index("--against")
        if j + 1 >= len(argv):
            raise SystemExit("--against needs a report/HLO path")
        against = hlo_from_report(argv[j + 1])
    if against is None:
        h = histogram(flagged)
        print("%-28s %10s" % ("op", "count"))
        for op, n in h.most_common():
            print("%-28s %10d" % (op, n))
        print("\ntotal lines: %d"
              % len(open(flagged).read().splitlines()))
    else:
        print_diff(flagged, against, "flagged", "fixed")


if __name__ == "__main__":
    main()
