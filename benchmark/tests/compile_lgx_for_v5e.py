"""Rehearsal 3 of `lgx_decode_sat` (builder's tool, run by hand, no chip
needed): compile the serving programs of `laguna_xs2_1chip` at their real
sizes for a DESCRIBED v5e and print what `memory_analysis()` says —
`compile_ms4_for_v5e.py`'s twin (the same three programs, this
configuration's builder). No weight is made; nothing runs; a pass here is
not a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_lgx_for_v5e.py [layers]

`LGX_HLO_DIR=<dir>` leaves each program's HLO text there.
"""
import os
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark.tests.compile_for_v5e import _report  # noqa: E402
from benchmark.tests.compile_ms4_for_v5e import programs  # noqa: E402


def main(layers=None):
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = harness.load_config("laguna_xs2_1chip")
    if layers:
        cfg["num_hidden_layers"] = int(layers)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        progs = programs(cfg, one)
        jax.clear_caches()
        for name, (fn, args) in progs.items():
            compiled = fn.lower(*args).compile()
            _report(name, compiled)
            out = os.environ.get("LGX_HLO_DIR")
            if out:
                with open(os.path.join(out, f"{name}.hlo.txt"), "w") as f:
                    f.write(compiled.as_text())


if __name__ == "__main__":
    main(*sys.argv[1:2])
