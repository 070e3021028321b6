"""Run one harness run with a fault planted in the program underneath.

    JAX_PLATFORMS=cpu python benchmarks/chip/tests/run_with_fault.py FAULT \
        --workload <cell> --seed <n> --seconds <s> --trace 0 --rehearse-on-cpu
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import faults  # noqa: E402
import run  # noqa: E402

if __name__ == "__main__":
    with faults.planted(sys.argv[1]):
        sys.exit(run.main(sys.argv[2:]))
