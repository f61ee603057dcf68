"""The benchmark's tests import the program from ``src/`` as its runs do."""
import sys

from bench import spec

if str(spec.ROOT / "src") not in sys.path:
    sys.path.insert(0, str(spec.ROOT / "src"))
