"""Puts the benchmark's directory (and through it the program's src/) on
the path for the tests of this directory."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
