"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See ``bench/harness.py`` for what a run does and prints.
"""

import os
import sys
import time

T_PROC = time.perf_counter()

# the repository root, not this directory, leads the import path: the
# benchmark is the package ``bench`` (its trace.py must not shadow the
# standard library's ``trace``)
sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(sys.argv[1:], T_PROC))
