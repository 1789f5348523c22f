"""The benchmark's modules and the program's sources, importable by name."""
import os
import sys

BENCH = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
for path in (os.path.join(os.path.dirname(BENCH), "src"), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
