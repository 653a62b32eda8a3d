"""The benchmark's own tests: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`
(not part of tier-1). CPU only, eight virtual devices like the repo's tests."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from flexflow_tpu.runtime.platform import force_platform  # noqa: E402

force_platform("cpu", n_host_devices=8)
