"""The chip benchmark: one cell, one process.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up, warms up, measures for ``--seconds``, checks what the window
produced against a plain reference, and prints one JSON line last on
standard output.  Exits non-zero, with no result, where JAX finds no TPU
or fewer chips than the cell asks for.  Cells, configurations, traffic
mixes and metrics are found by name (`bench/benchkit/spec.py`).
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchkit.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
