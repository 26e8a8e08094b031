"""The control: a run of a cell with one of the configuration's guarantees
broken, which the judge has to find.

    python benchmark/control.py --workload paxos-3.verdict --seed 5 --seconds 20

The configuration names its control (`control`), the module
`controls/<control>.py`, whose `install(seed)` breaks the guarantee before
the run. Everything else is the cell's own run (`run.py`), on the chip, at
the cell's size. The benchmark's own runs never do this.
"""

from __future__ import annotations

import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import cell as cells  # noqa: E402


def install(config: dict, seed: int) -> None:
    cells.module("controls", config["control"]).install(seed)


def main(argv=None) -> int:
    args = run.parse_args(argv)
    install(cells.resolve(cells.load_benchmark(), args.workload)["config"], args.seed)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
