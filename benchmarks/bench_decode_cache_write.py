"""Per-step device time of a decode layer scan's KV-cache handling, for
three ways of holding the stacked cache, at Phi-4-mini's chat shapes
(32 layers x 32 slots x 2048 rows x 8 KV heads x 128, fp8). Each layer
writes one new K and V row per slot and attends its layer's view; the
projections and MLP are left out, so the time is the cache's alone.

  xs        the stacks scanned as xs/ys: every layer's cache sliced out,
            updated and stacked back (the decode step before the cache
            was read in place)
  scatter   the stacks carried through the scan, each layer's rows
            written with one scatter into the carried stack
  in_place  the stacks read-only inside the scan, the new row standing
            in on read, the rows written after the scan with one
            dynamic_update_slice a slot (``models/lm.py`` decode)
  none      the stacks read-only and never written: the attention alone,
            the floor the other three are measured against

    python3 benchmarks/bench_decode_cache_write.py xs scatter in_place none [--kh=2] [--profile]

``--kh=2`` gives a chip's share of Phi-4-mini's 8 KV heads on four chips.
Prints one line per variant (median step ms, compiled temp bytes) and a
JSON line of all; ``--profile`` adds each variant's ten longest device
ops per step from a profiler trace. Needs a TPU: elsewhere the times
mean nothing.
"""
import functools
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp

L, B, T, KH, G, hd = 32, 32, 2048, 8, 3, 128
F8 = jnp.float8_e4m3fn


def attend(q, ck, cv, pos):
    qg = q.reshape(B, KH, G, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, ck.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    m = jnp.arange(T)[None] <= pos[:, None]
    s = jnp.where(m[:, None, None], s / 11.3, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(s, -1).astype(jnp.bfloat16)
    return jnp.einsum("bkgt,btkd->bkgd", w, cv.astype(jnp.bfloat16)).reshape(B, KH * G * hd)


def new_rows(x):
    kn = (x[:, :KH * hd] * 0.5).reshape(B, KH, hd)
    vn = (x[:, KH * hd:2 * KH * hd] * 0.25).reshape(B, KH, hd)
    return kn, vn


def xs(k, v, x, pos):
    def body(x, kv):
        ck, cv = kv
        kn, vn = new_rows(x)
        wr = jax.vmap(lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(c, u, s, axis=0))
        ck = wr(ck, kn[:, None].astype(F8), pos)
        cv = wr(cv, vn[:, None].astype(F8), pos)
        x = x + attend(x, ck, cv, pos)
        return x, (ck, cv)
    x, (k, v) = jax.lax.scan(body, x, (k, v))
    return x, k, v


def scatter(k, v, x, pos):
    def write(c, u, layer):
        return c.at[layer, jnp.arange(B), pos].set(u.astype(c.dtype), mode="clip")

    def body(carry, _):
        k, v, layer, x = carry
        kn, vn = new_rows(x)
        k, v = write(k, kn, layer), write(v, vn, layer)
        ck = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
        x = x + attend(x, ck, cv, pos)
        return (k, v, layer + 1, x), None
    (k, v, _, x), _ = jax.lax.scan(body, (k, v, jnp.int32(0), x), None, length=L)
    return x, k, v


def in_place(k, v, x, pos):
    def view(stack, layer, new):
        c = jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
        hit = (jnp.arange(T)[None] == pos[:, None])[:, :, None, None]
        return jnp.where(hit, new[:, None].astype(c.dtype), c)

    def body(carry, _):
        x, layer = carry
        kn, vn = (r.astype(F8) for r in new_rows(x))
        x = x + attend(x, view(k, layer, kn), view(v, layer, vn), pos)
        return (x, layer + 1), (kn, vn)
    (x, _), (kr, vr) = jax.lax.scan(body, (x, jnp.int32(0)), None, length=L)
    for b in range(B):
        k = jax.lax.dynamic_update_slice(k, kr[:, b][:, None, None], (0, b, pos[b], 0, 0))
        v = jax.lax.dynamic_update_slice(v, vr[:, b][:, None, None], (0, b, pos[b], 0, 0))
    return x, k, v


def none(k, v, x, pos):
    def body(carry, _):
        x, layer = carry
        ck = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
        return (x + attend(x, ck, cv, pos), layer + 1), None
    (x, _), _ = jax.lax.scan(body, (x, jnp.int32(0)), None, length=L)
    return x, k, v


VARIANTS = {"xs": xs, "scatter": scatter, "in_place": in_place, "none": none}


def profile(fn, k, v, x, pos, steps=4):
    """The ten longest device ops of a step, from a profiler trace."""
    import collections
    import glob
    import tempfile

    from jax.profiler import ProfileData

    d = tempfile.mkdtemp(prefix="decode_cache_write_")
    jax.profiler.start_trace(d)
    for _ in range(steps):
        x, k, v = fn(k, v, x, pos)
    x.block_until_ready()
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0])
    tot, cnt = collections.Counter(), collections.Counter()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        tot[e.name] += e.duration_ns
                        cnt[e.name] += 1
    top = [(n, round(t / steps / 1e6, 3), cnt[n] // steps) for n, t in tot.most_common(10)]
    return x, k, v, {"per_step_ms_all_ops": round(sum(tot.values()) / steps / 1e6, 3), "top": top}


def main(argv):
    global KH, G
    for a in argv:
        if a.startswith("--kh="):
            KH = int(a[5:])
            G = 24 // KH
    out = {"device": str(jax.devices()[0].device_kind), "kh": KH}
    for name in [a for a in argv if not a.startswith("--")]:
        fn = jax.jit(VARIANTS[name], donate_argnums=(0, 1))
        k = jnp.zeros((L, B, T, KH, hd), F8)
        v = jnp.zeros((L, B, T, KH, hd), F8)
        x = jax.random.normal(jax.random.PRNGKey(0), (B, KH * G * hd), jnp.bfloat16) * 0.01
        pos = jax.random.randint(jax.random.PRNGKey(1), (B,), 0, T, jnp.int32)
        t0 = time.perf_counter()
        comp = fn.lower(k, v, x, pos).compile()
        compile_s = time.perf_counter() - t0
        times = []
        for _ in range(25):
            t = time.perf_counter()
            x, k, v = fn(k, v, x, pos)
            x.block_until_ready()
            times.append((time.perf_counter() - t) * 1e3)
            pos = (pos + 1) % T
        out[name] = {"step_ms_p50": statistics.median(times[5:]), "min": min(times[5:]),
                     "temp_bytes": comp.memory_analysis().temp_size_in_bytes,
                     "compile_s": compile_s}
        if "--profile" in argv:
            x, k, v, out[name]["profile"] = profile(fn, k, v, x, pos)
        print(name, out[name], flush=True)
        del k, v
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
