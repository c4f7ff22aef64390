"""The benchmark's modules and the program's package, importable by name."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH / "tests"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
