"""Test set-up for the benchmark's own tests (run from the checkout's
root: ``python -m pytest bench/tests``).  They run on the CPU at the
configurations' smoke sizes; nothing here needs a chip."""

import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if p not in sys.path:
        sys.path.insert(0, p)
