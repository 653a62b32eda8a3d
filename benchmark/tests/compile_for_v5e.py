"""Rehearsal 3 (builder's tool, run by hand, no chip needed): compile the
timed programs at their real sizes for a DESCRIBED v5e and print what
`memory_analysis()` says — the ground for the batch and the slot count in
PERF.md section 4. Nothing runs; a pass here is not a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_for_v5e.py train 64 4
    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_for_v5e.py serve
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

from benchmark import harness, traffic  # noqa: E402


def _report(name, compiled):
    ma = compiled.memory_analysis()
    gb = lambda b: round(b / 2**30, 3)
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    text = compiled.as_text()
    print({"program": name, "argument_GiB": gb(ma.argument_size_in_bytes),
           "output_GiB": gb(ma.output_size_in_bytes),
           "alias_GiB": gb(ma.alias_size_in_bytes),
           "temp_GiB": gb(ma.temp_size_in_bytes), "total_GiB": gb(total),
           "tpu_custom_call": text.count("tpu_custom_call")}, flush=True)


def _shapes(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def train(per_chip_batch: int, K: int):
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    cfg = harness.load_config("bert_osdi22")
    cfg["deployment"].update(per_chip_batch=per_chip_batch,
                             steps_per_execution=K)
    tr = traffic.load_traffic("train_packed_512")
    builder = harness.module_of("configs", cfg["builder"])
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        model = builder.build_program(cfg, tr, 1, 0)
        mstep = model._get_multi_step().__wrapped__
        bs, seq = per_chip_batch, int(cfg["sequence_length"])
        rep = dat = SingleDeviceSharding(topo.devices[0])
        name = model.input_ops[0].name
        args = (_shapes(model.params, rep), _shapes(model.opt_state, rep),
                _shapes(model.state, rep),
                {name: jax.ShapeDtypeStruct((K, bs, seq), jnp.int32,
                                            sharding=dat)},
                jax.ShapeDtypeStruct((K, bs, seq, 1), jnp.int32, sharding=dat),
                jax.ShapeDtypeStruct((K, 2), jnp.uint32, sharding=rep))
        jax.clear_caches()
        compiled = mstep.lower(*args).compile()
    _report(f"multi_step batch {per_chip_batch} K={K}", compiled)


def serve():
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = harness.load_config("lm_osdi22w")
    harness.apply_matmul_precision(cfg)
    builder = harness.module_of("configs", cfg["builder"])
    dep = cfg["deployment"]
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        model = builder.build_model(cfg, 0)
        batcher = builder.build_batcher(model, cfg)
        S, chunk = int(dep["num_slots"]), int(dep["prefill_chunk_tokens"])
        p, st = _shapes(model.params, one), _shapes(model.state, one)
        caches = _shapes(batcher._caches, one)
        small = _shapes(batcher._zero_small(), one)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
        jax.clear_caches()
        progs = {
            "decode_all": (batcher._decode_fn, (p, st, caches, i32(S), i32(S),
                           jax.ShapeDtypeStruct((S, 2), jnp.uint32,
                                                sharding=one))),
            "prefill_chunk": (batcher._chunk_fn, (p, st, small, i32(1, chunk),
                                                  i32())),
            "prefill_last_chunk": (batcher._last_chunk_fn, (
                p, st, caches, small, i32(1, chunk), i32(), 0, i32(), i32(),
                key)),
        }
        for name, (fn, args) in progs.items():
            _report(name, fn.lower(*args).compile())


if __name__ == "__main__":
    what = sys.argv[1]
    if what == "serve":
        serve()
    else:
        train(int(sys.argv[2]), int(sys.argv[3]))
