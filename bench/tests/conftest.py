"""The benchmark's own tests: CPU only, small sizes.  They are not part
of the repository's ``tests/`` suite; run them with
``python -m pytest bench/tests``."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
