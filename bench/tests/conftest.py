"""Put the checkout (for `bench`) and `src` (for the program) on the
path; the tests run on the CPU at sizes a test run holds."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
