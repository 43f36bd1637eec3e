"""Running a cell of the tests' manifest as the driver would, on the CPU
rehearsal path."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = "tests/benchmark/manifest.json"


def run_cell(cell, *extra, seconds="1"):
    with open(os.path.join(ROOT, MANIFEST)) as f:
        command = json.load(f)["command"]
    proc = subprocess.run(
        [sys.executable, *command[1:], "--manifest", MANIFEST,
         "--workload", cell, "--seed", "3000000019", "--seconds", seconds,
         *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, BENCH_RUN="ignored"))
    return proc


def metrics_of(kind, cell):
    with open(os.path.join(ROOT, MANIFEST)) as f:
        manifest = json.load(f)
    return {m["name"]: m["unit"] for m in manifest[kind]
            if cell in m.get("workloads", [cell])}
