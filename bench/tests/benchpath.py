"""Put ``bench/`` and the program's ``src/`` on sys.path for the tests."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
FIXTURES = BENCH / "tests" / "fixtures"
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
