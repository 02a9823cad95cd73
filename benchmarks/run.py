"""``python benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one process, one cell, once; the last line of standard
output is the result object (see ``README.md``)."""
import sys
import time

T_START = time.monotonic()          # before jax: set-up counts from here

import os  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
