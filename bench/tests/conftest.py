"""The benchmark's tests import the program from `src/`, as bench/run.py
does, whether or not PYTHONPATH names it."""
import pathlib
import sys

_SRC = str(pathlib.Path(__file__).resolve().parents[2] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
