"""LoRA bypass cost on TPU: XLA-fused rank-r GEMMs vs the base projection.

Settles VERDICT r4 "next round" #5 with data: the reference ships an
autotuned Triton fused-LoRA kernel (``_peft/lora_kernel.py:175,330,491``);
here the bypass is plain XLA (``models/llama.py::proj``: ``y = x @ W +
s * (x @ A) @ B``).  A fused kernel can at best make the rank-r work free,
so the measurable quantity is the OVERHEAD of the bypass over the frozen
base projection's fwd+grad — if that overhead is close to the rank-r
FLOPs' fair share (2r/H of the base), XLA already fuses well and a Pallas
port buys nothing.

Measures device time (profiler, not wall clock: per-op device durations
exclude dispatch) of fwd + grads-to-(x, A, B) at Llama-1B
bench shapes (T=16384 tokens, H=2048) for r in {8, 16, 64}.

Usage: python -m tools.lora_microbench
"""

from __future__ import annotations

import collections
import glob
import tempfile

import jax
import jax.numpy as jnp

T, H = 16384, 2048
S = 1.0


def device_ms(fn, args, n=8):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    o = fn(*args)
    _ = jax.device_get(jax.tree.leaves(o)[0].ravel()[0])
    td = tempfile.mkdtemp(prefix="lora_mb_")
    jax.profiler.start_trace(td)
    try:
        for _ in range(n):
            o = fn(*args)
        _ = jax.device_get(jax.tree.leaves(o)[0].ravel()[0])
    finally:
        jax.profiler.stop_trace()
    p = glob.glob(td + "/plugins/profile/*/*.xplane.pb")[0]
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(open(p, "rb").read())
    plane = [pl for pl in xs.planes if pl.name == "/device:TPU:0"][0]
    line = [l for l in plane.lines if l.name == "XLA Ops"][0]
    total = sum(ev.duration_ps for ev in line.events) / 1e12
    return total / n * 1000


def main():
    key = jax.random.key(0)
    kx, kw, ka, kb = jax.random.split(key, 4)
    x = jax.random.normal(kx, (T, H), jnp.bfloat16)
    w = jax.random.normal(kw, (H, H), jnp.bfloat16) * 0.02

    def base_loss(x, w):
        y = x @ w
        return jnp.sum(y.astype(jnp.float32) ** 2)

    gbase = jax.jit(jax.value_and_grad(base_loss, argnums=(0,)))
    t_base = device_ms(gbase, (x, w))
    print(f"base proj fwd+dx:          {t_base:7.3f} ms")

    fwd_base = jax.jit(lambda x, w: x @ w)
    t_fwd_base = device_ms(fwd_base, (x, w))
    a8 = jax.random.normal(ka, (H, 8), jnp.bfloat16) * 0.02
    b8 = jnp.zeros((8, H), jnp.bfloat16)
    fwd_lora = jax.jit(lambda x, a, b, w=w: x @ w + S * ((x @ a) @ b))
    t_fwd_lora = device_ms(fwd_lora, (x, a8, b8))
    print(f"fwd only: base {t_fwd_base:7.3f} ms, +lora(r=8) "
          f"{t_fwd_lora:7.3f} ms  (epilogue-fusable share "
          f"{(t_fwd_lora-t_fwd_base)*1000:4.0f} us)")

    for r in (8, 16, 64):
        a = jax.random.normal(ka, (H, r), jnp.bfloat16) * 0.02
        b = jnp.zeros((r, H), jnp.bfloat16)

        def lora_loss(x, a, b, w=w):
            y = x @ w + S * ((x @ a) @ b)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        glora = jax.jit(jax.value_and_grad(lora_loss, argnums=(0, 1, 2)))
        t_lora = device_ms(glora, (x, a, b))
        overhead = t_lora - t_base
        fair = t_base * (2 * r / H) * 1.5  # 6 rank-r gemms vs 2 HxH + dA/dB
        print(f"r={r:3d}: fwd+dx+dA+dB:      {t_lora:7.3f} ms   "
              f"overhead {overhead*1000:6.0f} us "
              f"({100*overhead/t_base:5.1f}% of base; rank-r FLOPs' fair "
              f"share ~{100*fair/t_base:4.1f}%)")


if __name__ == "__main__":
    main()
