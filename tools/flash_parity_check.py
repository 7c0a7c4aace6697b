#!/usr/bin/env python
"""On-TPU numerics parity for the framework's own Pallas kernels.

The CPU suite exercises `ops/flash_pallas.py` and `ops/pallas_kernels.py`
through the Pallas *interpreter* (tests/test_flash_pallas.py,
tests/test_pallas.py); Mosaic-compiled behavior is only truly covered on
TPU. This script runs on the chip (one process holds it) and checks:

  1. own flash fwd+bwd, compiled Mosaic vs the Pallas interpreter on the
     SAME f32 inputs (small shape) - the exact "compiled != interpreter"
     question;
  2. own flash fwd+bwd (bf16, production seq 2048, the tuned blocks
     `tuned_blocks()` resolves) vs XLA fused attention - end-to-end
     numerics at the geometry the flagship LM row trains with;
  3. the fused Pallas CNN head (compiled) vs `mlp3_reference` fwd+bwd.

Writes tools/flash_parity_<device>.json: one row per check with a
normalized max-abs error (max|a-b| / (max|b|+eps)) and pass/fail, plus
an overall "ok". Exit 0 iff every row passed.

Usage (on the TPU):  python tools/flash_parity_check.py
"""

from __future__ import annotations

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _err(a, b, eps=1e-12):
    """Normalized max-abs error: comparable across output/grad scales."""
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + eps))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from distributed_neural_network_tpu.ops.flash import tuned_blocks
    from distributed_neural_network_tpu.ops.flash_pallas import flash_mha
    from distributed_neural_network_tpu.ops.pallas_kernels import (
        fused_mlp3,
        mlp3_reference,
    )

    from distributed_neural_network_tpu.runtime import (
        enable_compile_cache,
        on_tpu,
    )

    enable_compile_cache()
    if not on_tpu():
        print(json.dumps({"error": "parity check needs a TPU backend"}))
        return 1

    rows = []

    def check(name, err, tol, extra=None):
        row = {"check": name, "err": round(err, 6), "tol": tol,
               "pass": bool(err <= tol)}
        if extra:
            row.update(extra)
        print(json.dumps(row), flush=True)
        rows.append(row)

    def fb(attn):
        """Forward output + input grads of a scalar loss, one jit."""
        def f(q, k, v):
            def loss(q, k, v):
                return (attn(q, k, v).astype(jnp.float32) ** 2).mean()

            out = attn(q, k, v)
            gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            return out, gq, gk, gv

        return jax.jit(f)

    # --- 1. compiled Mosaic vs Pallas interpreter, f32, small shape ----
    # Multi-block grid on every axis (S=512, ALL blocks 256 - forward
    # and both backward kernels) so the check exercises the block loops
    # and their accumulation carries, not a single-tile special case.
    B, H, S, D = 2, 2, 512, 64
    ks = jax.random.split(jax.random.key(7), 3)
    q32, k32, v32 = (jax.random.normal(k, (B, S, H, D), jnp.float32)
                     for k in ks)
    try:
        from distributed_neural_network_tpu.ops.flash_pallas import (
            FlashBlocks,
        )

        blocks = FlashBlocks(bq=256, bk=256, bq_dq=256, bk_dq=256,
                             bq_dkv=256, bk_dkv=256)
        comp = fb(lambda q, k, v: flash_mha(
            q, k, v, causal=True, blocks=blocks))(q32, k32, v32)
        interp = fb(lambda q, k, v: flash_mha(
            q, k, v, causal=True, blocks=blocks, interpret=True))(
            q32, k32, v32)
        for part, a, b in zip(("out", "dq", "dk", "dv"), comp, interp):
            check(f"flash_compiled_vs_interpreter_f32_{part}",
                  _err(a, b), 2e-4)
    except Exception as e:  # noqa: BLE001 - record, keep checking
        rows.append({"check": "flash_compiled_vs_interpreter_f32",
                     "error": str(e)[:300], "pass": False})
        print(json.dumps(rows[-1]), flush=True)

    # --- 2. own kernel (bf16, production geometry + tuned blocks) vs
    # XLA fused attention (f32 scores) ---------------------------------
    B, H, S, D = 4, 8, 2048, 64
    ks = jax.random.split(jax.random.key(11), 3)
    qb, kb, vb = (jax.random.normal(k, (B, S, H, D), jnp.bfloat16)
                  for k in ks)

    def xla_attn(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s.astype(jnp.float32), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    try:
        tb = tuned_blocks(S, D)
        own = fb(lambda q, k, v: flash_mha(
            q, k, v, causal=True, blocks=tb))(qb, kb, vb)
        ref = fb(xla_attn)(qb, kb, vb)
        # bf16 storage + blockwise-softmax reassociation: loose tol
        for part, a, b in zip(("out", "dq", "dk", "dv"), own, ref):
            check(f"flash_own_vs_xla_bf16_s{S}_{part}", _err(a, b), 3e-2,
                  {"blocks": {f: getattr(tb, f) for f in (
                      "bq", "bk", "bq_dq", "bk_dq", "bq_dkv", "bk_dkv")}}
                  if part == "out" else None)
    except Exception as e:  # noqa: BLE001
        rows.append({"check": "flash_own_vs_xla_bf16", "error": str(e)[:300],
                     "pass": False})
        print(json.dumps(rows[-1]), flush=True)

    # --- 3. fused CNN head (compiled Mosaic) vs plain-jnp reference ----
    din, dh1, dh2, dout, nb = 400, 120, 84, 10, 64
    ks = jax.random.split(jax.random.key(13), 7)
    x = jax.random.normal(ks[0], (nb, din), jnp.float32)
    w1 = jax.random.normal(ks[1], (din, dh1), jnp.float32) * 0.05
    b1 = jax.random.normal(ks[2], (dh1,), jnp.float32) * 0.05
    w2 = jax.random.normal(ks[3], (dh1, dh2), jnp.float32) * 0.05
    b2 = jax.random.normal(ks[4], (dh2,), jnp.float32) * 0.05
    w3 = jax.random.normal(ks[5], (dh2, dout), jnp.float32) * 0.05
    b3 = jax.random.normal(ks[6], (dout,), jnp.float32) * 0.05
    params = (w1, b1, w2, b2, w3, b3)

    def head_fb(head):
        def f(x, *ps):
            def loss(x, *ps):
                return (head(x, *ps) ** 2).mean()

            out = head(x, *ps)
            grads = jax.grad(loss, argnums=tuple(range(7)))(x, *ps)
            return (out,) + grads

        return jax.jit(f)

    try:
        with jax.default_matmul_precision("highest"):
            comp = head_fb(lambda *a: fused_mlp3(*a, interpret=False))(
                x, *params)
            ref = head_fb(mlp3_reference)(x, *params)
        names = ("out", "dx", "dw1", "db1", "dw2", "db2", "dw3", "db3")
        for part, a, b in zip(names, comp, ref):
            check(f"mlp3_compiled_vs_reference_f32_{part}", _err(a, b), 5e-3)
    except Exception as e:  # noqa: BLE001
        rows.append({"check": "mlp3_compiled_vs_reference", "pass": False,
                     "error": str(e)[:300]})
        print(json.dumps(rows[-1]), flush=True)

    ok = bool(rows) and all(r.get("pass") for r in rows)
    dev = jax.devices()[0].device_kind.replace(" ", "_")
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            f"flash_parity_{dev}.json")
    with open(out_path, "w") as f:
        json.dump({"device": dev, "ok": ok, "rows": rows}, f, indent=1)
    print(json.dumps({"wrote": out_path, "ok": ok,
                      "checks": len(rows)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
