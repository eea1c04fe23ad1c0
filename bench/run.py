#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object; the
numbers compared with the reference are the last lines of standard
error.  Exits non-zero, with no result, where JAX finds no accelerator
or fewer chips than the cell asks for.
"""
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()


def steady_allocator() -> None:
    """Keep the host arrays of every pass in memory the process holds.

    By glibc's defaults an array above 128 KiB is mapped afresh when it
    is made and unmapped when freed, and the threshold rises only as
    larger arrays are freed.  Each replay pass makes and frees tens of
    arrays of 1 to 30 MB; on a TPU v5e host, touching fresh pages took
    about two thirds of a ft4-cs.replay pass, less and less as a process
    aged, at a pace that differed from run to run.  A fixed threshold of
    32 MiB (glibc's largest) and no trimming below 1 GiB serve those
    arrays from pages already held, from the first pass on.
    """
    import ctypes

    mallopt = getattr(ctypes.CDLL(None), "mallopt", None)
    if mallopt is not None:
        m_trim_threshold, m_mmap_threshold = -1, -3
        mallopt(m_mmap_threshold, 32 << 20)
        mallopt(m_trim_threshold, 1 << 30)


steady_allocator()
BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]
# The TPU runtime would otherwise log under a fixed directory in /tmp.
os.environ["TPU_LOG_DIR"] = "disabled"

from harness.cell import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
