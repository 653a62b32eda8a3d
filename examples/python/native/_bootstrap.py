"""Make the in-tree flexflow_tpu importable when the package isn't
installed (examples are runnable straight from a checkout)."""
import os
import sys

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
