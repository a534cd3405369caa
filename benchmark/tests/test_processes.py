"""Nothing a run starts outlives it, on any path out of the launcher."""

import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

ORPHAN = """
import subprocess, sys, time
sys.path.insert(0, {bench!r})
import harness
harness.adopt_orphans()
# a child that leaves a grandchild behind and ends
subprocess.run(["sh", "-c", "sleep 300 & echo $!"], stdout=sys.stderr)
child = subprocess.Popen(["sleep", "300"])
print(sorted(harness.descendants().values()))
print(len(harness.reap_descendants()))
print(harness.descendants())
"""


def test_reaper_ends_children_and_orphans():
    proc = subprocess.run([sys.executable, "-c", ORPHAN.format(bench=BENCH)],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    before, killed, after = proc.stdout.strip().splitlines()
    assert before == "['sleep', 'sleep']"
    assert killed == "2" and after == "{}"
    orphan = int(proc.stderr.strip().splitlines()[0])
    assert not os.path.exists(f"/proc/{orphan}")


def test_checkout_without_the_program_starts_nothing(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for cell in ("served.steady", "catchup.cut10k"):
        proc = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", cell,
             "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
            cwd=tmp_path, capture_output=True, text=True, timeout=60)
        assert proc.returncode != 0
        assert proc.stdout == ""
        assert "not in this checkout" in proc.stderr
