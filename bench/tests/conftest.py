"""Import paths for the benchmark's own tests (run them with
``python -m pytest bench/tests``; they run on the CPU)."""
import sys
from pathlib import Path

_BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH.parent / "src"), str(_BENCH), str(_BENCH.parent)]
