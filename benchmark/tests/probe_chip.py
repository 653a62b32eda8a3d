"""One-off probe (builder's tool, not part of a run; it recorded
data/probe_v5e.xplane.pb): what a TPU trace looks like, what f32 matmuls do at default precision, what memory_stats reports.
Writes chiprun_out/probe/*."""
import glob, json, os, time
import jax, jax.numpy as jnp

out = "chiprun_out/probe"
os.makedirs(out, exist_ok=True)
info = {"env_cache": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "devices": [str(d) for d in jax.devices()],
        "kind": jax.devices()[0].device_kind,
        "memory_stats": {k: int(v) for k, v in (jax.devices()[0].memory_stats() or {}).items()},
        "cpu_count": os.cpu_count()}
k = jax.random.PRNGKey(0)
a = jax.random.normal(k, (2048, 1024), jnp.float32)
b = jax.random.normal(jax.random.fold_in(k, 1), (1024, 4096), jnp.float32)
hi = jnp.dot(a, b, precision="highest")
for name, kw in (("default", {}), ("high", {"precision": "high"}),):
    c = jnp.dot(a, b, **kw)
    info[f"f32_dot_{name}_relerr"] = float(jnp.linalg.norm(c - hi) / jnp.linalg.norm(hi))
c = jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
info["bf16_dot_relerr"] = float(jnp.linalg.norm(c - hi) / jnp.linalg.norm(hi))
f8 = jnp.float8_e4m3fn
c = jnp.dot(a.astype(f8).astype(jnp.bfloat16), b.astype(f8).astype(jnp.bfloat16), preferred_element_type=jnp.float32)
info["fp8_dot_relerr"] = float(jnp.linalg.norm(c - hi) / jnp.linalg.norm(hi))

@jax.jit
def step(x, w, cache, pos):
    with jax.named_scope("linear:ff1"):
        h = jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    with jax.named_scope("multihead_attention:layer0_attn"):
        cache = jax.lax.dynamic_update_slice(cache, h[None, :1, :1024], (pos, 0, 0))
        s = jnp.einsum("bld,md->blm", cache, h[:, :1024])
        y = jax.nn.softmax(s, -1).sum()
    return y, cache, jnp.transpose(cache, (1, 0, 2)) + 1.0

cache = jnp.zeros((8, 256, 1024), jnp.float32)
y, cache2, t = step(a, b, cache, 3)
jax.block_until_ready((y, cache2, t))
tdir = os.path.join(out, "trace")
jax.profiler.start_trace(tdir)
t0 = time.perf_counter()
for i in range(5):
    with jax.profiler.TraceAnnotation("bench.iter", i=i):
        y, cache2, t = step(a, b, cache2, i)
        float(y)
    time.sleep(0.01)
t1 = time.perf_counter()
jax.profiler.stop_trace()
info["traced_window_s"] = t1 - t0
pb = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
info["xplane"] = pb
info["xplane_bytes"] = [os.path.getsize(p) for p in pb]
from jax.profiler import ProfileData
pd = ProfileData.from_file(pb[0])
dump = []
for plane in pd.planes:
    pl = {"plane": plane.name, "lines": []}
    for line in plane.lines:
        evs = list(line.events)
        ex = []
        for e in evs[:6]:
            ex.append({"name": e.name, "start_ns": e.start_ns, "dur_ns": e.duration_ns,
                       "stats": {str(k): str(v)[:200] for k, v in e.stats}})
        names = {}
        for e in evs:
            names[e.name] = names.get(e.name, 0) + 1
        pl["lines"].append({"line": line.name, "n": len(evs), "examples": ex,
                            "names": dict(sorted(names.items(), key=lambda kv: -kv[1])[:40])})
    dump.append(pl)
json.dump(dump, open(os.path.join(out, "xplane_dump.json"), "w"), indent=1)
info["memory_stats_after"] = {k: int(v) for k, v in (jax.devices()[0].memory_stats() or {}).items()}
json.dump(info, open(os.path.join(out, "info.json"), "w"), indent=1)
print(json.dumps(info, indent=1))
