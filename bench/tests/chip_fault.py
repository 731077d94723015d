"""Read a planted fault on the chip, at the cell's own size.

  python3 bench/tests/chip_fault.py --fault state_unchanged \
      --workload <cell> --seed <n> --seconds <s> --trace 0

Puts one of ``test_bench_faults``' broken decode steps in the program's
place and runs the cell through ``bench/run.py``; the result line's
``checks`` hold the fault's reading beside the cell's limit.
"""
import argparse
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for _p in (HERE, HERE.parents[1] / "src", HERE.parents[1]):
    sys.path.insert(0, str(_p))

import test_bench_faults as F  # noqa: E402
from bench import run as R  # noqa: E402
from repro.models import model as M  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--fault", required=True,
                    choices=("state_unchanged", "half_batch",
                             "token_altered"))
    args, rest = ap.parse_known_args()
    M.decode_step = getattr(F, args.fault)
    return R.main(rest)


if __name__ == "__main__":
    sys.exit(main())
