"""Set-up for the benchmark's own tests: ``bench`` imports from the root of
the checkout, and nothing under ``bench/out`` (run outputs, unpacked
checkouts) is collected."""
import sys
from pathlib import Path

ROOT = str(Path(__file__).resolve().parents[1])
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

collect_ignore = ["out"]
