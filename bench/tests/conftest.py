"""The benchmark's tests run on the CPU, with the program on the path."""

import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parents[2]
for _p in (_REPO / "src", _REPO):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))
