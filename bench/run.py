"""Run one benchmark cell once; see ``bench/harness.py``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
