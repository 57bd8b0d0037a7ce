#!/usr/bin/env python3
"""The benchmark's command (BENCHMARK.json `command`):

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips, a JAX-free load generator process
and JAX-free data workers beside it. See perfbench/README.md.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from perfbench.lib.harness import main
    sys.exit(main(sys.argv[1:], ROOT))
