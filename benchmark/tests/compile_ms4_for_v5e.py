"""Rehearsal 3 of `ms4_decode_sat` (builder's tool, run by hand, no chip
needed): compile the serving programs of `mistral_small4_ep4` at their real
sizes for a DESCRIBED v5e and print what `memory_analysis()` says. No
weight is made (the parameters are shapes: `jax.eval_shape` of the
executor's initialisation); nothing runs; a pass here is not a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_ms4_for_v5e.py [layers]
"""
import os
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark.tests.compile_for_v5e import _report, _shapes  # noqa: E402


def programs(cfg, one):
    """{name: (jitted fn, argument shapes on `one`)} of the batcher's
    decode iteration and its two prefill steps, the model built with shapes
    for parameters."""
    from flexflow_tpu.runtime.executor import Executor

    builder = harness.module_of("configs", cfg["builder"])
    dep = cfg["deployment"]
    real_init = Executor.init_params
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(builder, "install_weights", lambda *a: None), \
            mock.patch.object(
                Executor, "init_params",
                lambda self, key: jax.eval_shape(
                    lambda k: real_init(self, k), key)):
        model = builder.build_model(cfg, 0)
        batcher = builder.build_batcher(model, cfg)
    S, chunk = int(dep["num_slots"]), int(dep["prefill_chunk_tokens"])
    p, st = _shapes(model.params, one), _shapes(model.state, one)
    caches = _shapes(jax.eval_shape(batcher._zero_caches), one)
    small = _shapes(jax.eval_shape(batcher._zero_small), one)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    return {
        "decode_all": (batcher._decode_fn, (
            p, st, caches, i32(S), i32(S),
            jax.ShapeDtypeStruct((S, 2), jnp.uint32, sharding=one))),
        "prefill_chunk": (batcher._chunk_fn, (p, st, small, i32(1, chunk),
                                              i32())),
        "prefill_last_chunk": (batcher._last_chunk_fn, (
            p, st, caches, small, i32(1, chunk), i32(), 0, i32(), i32(),
            key)),
    }


def main(layers=None):
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = harness.load_config("mistral_small4_ep4")
    if layers:
        cfg["num_hidden_layers"] = int(layers)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        progs = programs(cfg, one)
        jax.clear_caches()
        for name, (fn, args) in progs.items():
            compiled = fn.lower(*args).compile()
            _report(name, compiled)
            out = os.environ.get("MS4_HLO_DIR")
            if out:
                with open(os.path.join(out, f"{name}.hlo.txt"), "w") as f:
                    f.write(compiled.as_text())


if __name__ == "__main__":
    main(*sys.argv[1:2])
