"""Run a command and list every process that it started and left alive.

    python3 tools/left_running.py -- python3 benchmark/run.py --workload ...

Takes ``ps -eo pid,ppid,pgid,etimes,args`` before the command and again as
soon as it returns (and once more after ``--settle`` seconds), and reports
every process of the second listing that the first did not have, under any
name: the driver refuses a PR whose benchmark run leaves one (PRs 28, 29).
An entry that ``ps`` marks ``<defunct>`` has ended and only waits for its
new parent to collect it (an orphan of a killed worker, until pid 1 gets
to it): those are listed apart, under ``defunct``, and are not "left
running".  The command's output passes through; the report is the last
line, JSON; the exit code is the command's own, or 3 where that was 0 and
a live process was left.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

PS = ["ps", "-eo", "pid,ppid,pgid,etimes,args"]


def listing() -> dict:
    """pid -> the process's line, without the ``ps`` that made the list."""
    ps = subprocess.Popen(PS, stdout=subprocess.PIPE, text=True)
    out = ps.communicate()[0]
    rows = {}
    for line in out.splitlines()[1:]:
        pid = int(line.split(None, 1)[0])
        if pid != ps.pid:
            rows[pid] = " ".join(line.split())
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--settle", type=float, default=3.0)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    command = args.command[1:] if args.command[:1] == ["--"] \
        else args.command
    before = listing()
    rc = subprocess.call(command)
    at_once = listing()
    time.sleep(args.settle)
    settled = listing()
    mine = os.getpid()
    left, defunct = {}, {}
    for when, rows in (("at_once", at_once), ("settled", settled)):
        new = [row for pid, row in rows.items()
               if pid not in before and pid != mine]
        left[when] = [r for r in new if not r.endswith("<defunct>")]
        defunct[when] = [r for r in new if r.endswith("<defunct>")]
    print(json.dumps({"left_running": left, "defunct": defunct, "rc": rc,
                      "processes_before": len(before)}))
    return rc or (3 if any(left.values()) else 0)


if __name__ == "__main__":
    sys.exit(main())
