#!/usr/bin/env python
"""Component ablation for the Ulysses sp=8 anomaly (r4 VERDICT weak #3).

`lm_ulysses_sp_scaling_cpu8` measured overhead_vs_sp1 0.897 at sp=4 but
1.923 at sp=8 (H=8 heads -> ONE head per device at sp=8). This script
splits one ulysses attention call (parallel/ring.py ulysses_attention)
into its two components and times each per sp on the same virtual CPU
mesh the scaling row used:

  - full:  all_to_all resharding + local full attention + all_to_all back
  - a2a:   the four tiled all_to_alls alone (trivial compute between)
  - attn:  the local attention alone on head-sharded inputs
           (B, S_full, H/sp, D) - no collectives

plus a mesh-free single-device attention timing at each H/sp value, to
separate "the (B, S, 1, D) einsum itself is slow" from "the collective
or its layout transforms blow up at 8 participants".

Timing is fwd+bwd (jax.value_and_grad of a scalar loss), matching the
train-step measurement that exposed the anomaly. Writes
tools/ulysses_diag.json.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/diagnose_ulysses.py
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()
# hard-set, not setdefault: a CPU-mesh diagnostic must never take the chip
os.environ["JAX_PLATFORMS"] = "cpu"


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_neural_network_tpu.parallel.ring import (
        attention,
        ulysses_attention,
    )
    from distributed_neural_network_tpu.utils.timers import hard_block

    B, S, H, D = 2, 2048, 8, 16  # the scaling row's geometry (d_model 128)
    steps = 3
    dev = jax.devices()
    rows = []

    def timeit(name, f, *args):
        out = f(*args)
        hard_block(out)
        t0 = time.perf_counter()
        for _ in range(steps):
            out = f(*args)
        hard_block(out)
        ms = (time.perf_counter() - t0) / steps * 1e3
        row = {"cfg": name, "ms": round(ms, 1)}
        print(json.dumps(row), flush=True)
        rows.append(row)
        return ms

    def fb(fn, axis=None):
        def f(q, k, v):
            def loss(q, k, v):
                return (fn(q, k, v) ** 2).mean()

            l, gs = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
            if axis is not None:  # replicate the scalar for out_specs P()
                l = jax.lax.pmean(l, axis)
            return l, gs[0], gs[1], gs[2]

        return f

    for sp in (2, 4, 8):
        mesh = Mesh(dev[:sp], ("seq",))
        seq_sh = NamedSharding(mesh, P(None, "seq"))
        ks = jax.random.split(jax.random.key(3), 3)
        qkv = [jax.device_put(jax.random.normal(k, (B, S, H, D), jnp.float32),
                              seq_sh) for k in ks]

        def sm(fn):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh,
                in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
                out_specs=(P(), P(None, "seq"), P(None, "seq"),
                           P(None, "seq")),
            ))

        full = sm(fb(functools.partial(ulysses_attention, causal=True),
                     axis="seq"))
        timeit(f"sp{sp}_full_ulysses", full, *qkv)

        def a2a_only(q, k, v):
            a2a = functools.partial(jax.lax.all_to_all, axis_name="seq",
                                    split_axis=2, concat_axis=1, tiled=True)
            back = functools.partial(jax.lax.all_to_all, axis_name="seq",
                                     split_axis=1, concat_axis=2, tiled=True)
            return back(a2a(q) + a2a(k) + a2a(v))

        timeit(f"sp{sp}_a2a_only", sm(fb(a2a_only, axis="seq")), *qkv)

        # local attention on head-sharded inputs: same per-device shapes
        # as inside ulysses after the reshard, zero collectives
        head_sh = NamedSharding(mesh, P(None, None, "seq"))
        qkv_h = [jax.device_put(jax.random.normal(k, (B, S, H, D),
                                                  jnp.float32), head_sh)
                 for k in ks]
        attn_local = jax.jit(jax.shard_map(
            fb(functools.partial(attention, causal=True), axis="seq"),
            mesh=mesh,
            in_specs=(P(None, None, "seq"),) * 3,
            out_specs=(P(), P(None, None, "seq"), P(None, None, "seq"),
                       P(None, None, "seq")),
        ))
        timeit(f"sp{sp}_attn_only_h{H // sp}", attn_local, *qkv_h)

    # mesh-free contrast: one device computing attention at each
    # heads-per-device value (same local shape as the sharded case).
    # The 4-D einsum path is timed EXPLICITLY here - ring.py attention()
    # now routes h==1 through the squeezed 3-D fix this diagnostic
    # motivated, so calling it would no longer reproduce the pathology.
    def generic_attn(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(D))
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    for h in (4, 2, 1):
        ks = jax.random.split(jax.random.key(5), 3)
        qkv1 = [jax.random.normal(k, (B, S, h, D), jnp.float32) for k in ks]
        timeit(f"single_dev_attn4d_h{h}", jax.jit(fb(generic_attn)), *qkv1)
        if h == 1:  # the shipped fix, same shape, for the A/B
            timeit("single_dev_attn_fixed_h1",
                   jax.jit(fb(functools.partial(attention, causal=True))),
                   *qkv1)

    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "ulysses_diag.json")
    with open(out_path, "w") as f:
        json.dump({"shape": {"batch": B, "seq": S, "heads": H, "head_dim": D},
                   "platform": jax.default_backend(),
                   "devices": len(dev), "steps": steps, "rows": rows},
                  f, indent=1)
    print(json.dumps({"wrote": out_path}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
