"""The benchmark's tests: its functions on the CPU at tiny sizes, never the
chip-only command.  The repository root goes on the path for ``bench``."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
