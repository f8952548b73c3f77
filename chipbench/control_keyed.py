"""``control.py``'s readings for a cell whose driver brings its own plain
reference (``Cell.control_numbers``, as ``drivers/service_keyed.py``
does for keyed operators): the same seeds, faults and lines, with the
control computed by that reference in bfloat16.

    python chipbench/control_keyed.py --workload nexmark-service-b16k --seeds 1,2,3,...
"""

from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import control

    control.control_numbers = lambda spec, cell, dep: cell.control_numbers()
    return control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
