"""Run one benchmark cell once:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. Prints the result as the last line of standard output."""

import os
import sys

if __name__ == "__main__":
    # libtpu would otherwise keep its logs under a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import harness

    sys.exit(harness.main())
