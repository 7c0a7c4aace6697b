#!/usr/bin/env python
"""Tune flash-attention block sizes (timed loops fenced with hard_block).

Round 4: the framework's OWN kernels (ops/flash_pallas.py) are the
default flash path, with independently tunable forward and backward
blocks - the r3 MFU diagnosis put the gap in the backward pass (fwd ~45%
MXU efficiency, bwd ~25%), so the sweep is staged: forward blocks first
(fwd-only timing), then a (dq x dkv) grid at the best forward blocks
(fwd+bwd timing). The library kernel and XLA fused attention run as
baselines. Writes tools/flash_tune_<device>_s<seq>.json with `best_own`
in exactly the FlashBlocks-field format `ops/flash.py tuned_blocks()`
loads at run time.

Usage (on real TPU):  python tools/tune_flash.py [--seq 2048] [--batch 16]
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--skip-lib", action="store_true",
                    help="skip the library-kernel baseline rows")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_neural_network_tpu.ops.flash_pallas import (
        FlashBlocks,
        flash_mha,
    )
    from distributed_neural_network_tpu.runtime import (
        enable_compile_cache,
        on_tpu,
    )
    from distributed_neural_network_tpu.utils.timers import hard_block

    enable_compile_cache()
    if not on_tpu():
        print(json.dumps({"error": "flash tuning needs a TPU backend"}))
        return 1

    B, H, S, D = args.batch, args.heads, args.seq, args.head_dim
    # (B, S, H, D) - the framework's layout (own kernel transposes inside)
    q = jax.random.normal(jax.random.key(0), (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (B, S, H, D), jnp.bfloat16)

    def xla_attn(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s.astype(jnp.float32), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def fwdbwd(attn):
        def f(q, k, v):
            def loss(q, k, v):
                return (attn(q, k, v).astype(jnp.float32) ** 2).mean()

            l, gs = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
            return l, gs[0].sum(), gs[1].sum(), gs[2].sum()

        return f

    results = []

    def timeit(name, f):
        g = jax.jit(f)
        try:
            out = g(q, k, v)
            hard_block(out)
            t0 = time.perf_counter()
            for _ in range(args.steps):
                out = g(q, k, v)
            hard_block(out)
            ms = (time.perf_counter() - t0) / args.steps * 1e3
            row = {"cfg": name, "ms": round(ms, 2)}
        except Exception as e:  # noqa: BLE001 - report and continue tuning
            row = {"cfg": name, "error": str(e)[:200]}
        print(json.dumps(row), flush=True)
        results.append(row)
        return row

    def own(blocks):
        return functools.partial(flash_mha, causal=True, blocks=blocks)

    cand = [b for b in (256, 512, 1024) if S % b == 0] or [S]

    # stage 1: forward blocks - the full ASYMMETRIC (bq, bk) grid, not
    # just uniform pairs: the q block sets the scratch/accumulator
    # footprint while the k block sets the inner-step granularity (and
    # the causal-skip resolution), so the best pair need not be square
    # (the r4 hardware sweep found the library kernel fastest at 512
    # uniform while the own kernel preferred 1024 - sweep both axes)
    fwd_rows = {}
    for bq in cand:
        for bk in cand:
            blocks = FlashBlocks(bq=bq, bk=bk)
            fwd_rows[(bq, bk)] = timeit(f"own_fwd_q{bq}k{bk}", own(blocks))
    ok_fwd = {p: r["ms"] for p, r in fwd_rows.items() if "ms" in r}
    best_fwd_pair = (min(ok_fwd, key=ok_fwd.get) if ok_fwd
                     else (cand[0], cand[0]))
    fwd_tag = (f"{best_fwd_pair[0]}" if best_fwd_pair[0] == best_fwd_pair[1]
               else f"{best_fwd_pair[0]}x{best_fwd_pair[1]}")

    # stage 2: backward blocks at the best forward blocks (fwd+bwd
    # timing), staged to keep the grid small: symmetric dq sweep at a
    # fixed dkv, then an ASYMMETRIC (bq_dkv, bk_dkv) sweep (the 3-D-grid
    # dkv kernel's inner q block and outer k block are independent
    # levers), then an asymmetric dq refinement at the best dkv.
    best_own, best_own_ms = None, float("inf")
    _seen = {}

    def try_fb(name, **fields):
        nonlocal best_own, best_own_ms
        blocks = FlashBlocks(bq=best_fwd_pair[0], bk=best_fwd_pair[1],
                             **fields)
        if blocks in _seen:  # identical config under another stage's name
            return _seen[blocks]
        r = timeit(name, fwdbwd(own(blocks)))
        _seen[blocks] = r
        if "ms" in r and r["ms"] < best_own_ms:
            best_own_ms, best_own = r["ms"], blocks
        return r

    mid = cand[len(cand) // 2]
    sweep = {}
    for bdq in cand:
        r = try_fb(f"own_fb_q{fwd_tag}_dq{bdq}_dkv{mid}",
                   bq_dq=bdq, bk_dq=bdq, bq_dkv=mid, bk_dkv=mid)
        if "ms" in r:
            sweep[(bdq, bdq)] = r["ms"]
    best_dq = min(sweep, key=sweep.get) if sweep else (mid, mid)
    sweep = {}
    for bq_dkv in cand:
        for bk_dkv in cand:
            r = try_fb(
                f"own_fb_q{fwd_tag}_dq{best_dq[0]}_"
                f"dkv{bq_dkv}x{bk_dkv}",
                bq_dq=best_dq[0], bk_dq=best_dq[1],
                bq_dkv=bq_dkv, bk_dkv=bk_dkv,
            )
            if "ms" in r:
                sweep[(bq_dkv, bk_dkv)] = r["ms"]
    best_dkv = min(sweep, key=sweep.get) if sweep else (mid, mid)
    for bq_dq in cand:
        for bk_dq in cand:
            # symmetric pairs at THIS dkv were only pre-measured when
            # best_dkv happens to be (mid, mid) - _seen dedupes that case
            try_fb(
                f"own_fb_q{fwd_tag}_dq{bq_dq}x{bk_dq}_"
                f"dkv{best_dkv[0]}x{best_dkv[1]}",
                bq_dq=bq_dq, bk_dq=bk_dq,
                bq_dkv=best_dkv[0], bk_dkv=best_dkv[1],
            )

    # baselines: library kernel (its best uniform blocks) + XLA fused
    if not args.skip_lib:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes,
            flash_attention,
        )

        def uniform(b):
            b = min(b, S)
            return BlockSizes(
                block_q=b, block_k_major=b, block_k=b, block_b=1,
                block_q_major_dkv=b, block_k_major_dkv=b,
                block_q_dkv=b, block_k_dkv=b,
                block_q_dq=b, block_k_dq=b, block_k_major_dq=b,
            )

        def lib(bs):
            def f(q, k, v):
                out = flash_attention(
                    q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), causal=True,
                    sm_scale=1.0 / math.sqrt(D), block_sizes=bs,
                )
                return out.transpose(0, 2, 1, 3)

            return f

        variants = {"defaults": None}
        for b in cand:
            variants[f"uniform{b}"] = uniform(b)
        for name, bs in variants.items():
            timeit(f"lib_fwd_{name}", lib(bs))
            timeit(f"lib_fb_{name}", fwdbwd(lib(bs)))
    timeit("xla_fwd", xla_attn)
    timeit("xla_fb", fwdbwd(xla_attn))

    dev = jax.devices()[0].device_kind.replace(" ", "_")
    # head_dim is part of the filename (D != 64 tunes must not clobber
    # the D=64 file; `tuned_blocks()` globs flash_tune_*.json and matches
    # on the recorded shape, so both spellings load fine)
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"flash_tune_{dev}_s{S}_d{D}.json" if D != 64
        else f"flash_tune_{dev}_s{S}.json",
    )
    lib_fb = [r for r in results
              if r["cfg"].startswith("lib_fb_") and "ms" in r]

    def best_ms(prefix):
        ok = [r["ms"] for r in results
              if r["cfg"].startswith(prefix) and "ms" in r]
        return min(ok) if ok else None

    # per-pass ablation (r3 VERDICT item 2: fwd ~45% / bwd ~25% MXU
    # efficiency with the library kernel - prove where the ceiling is).
    # bwd is derived as fb - fwd (same fwd blocks in both timings).
    # Causal attention FLOPs: fwd = 2 matmuls * 2 flops * B*H*S^2*D / 2
    # (causal half) = 2*B*H*S^2*D; bwd re-forms p and runs 5 matmuls =
    # 2.5x fwd.
    fwd_flops = 2.0 * B * H * S * S * D

    def tflops(flops, ms):
        return None if not ms else round(flops / (ms / 1e3) / 1e12, 2)

    def paired_ms(fwd_p, fb_p):
        """(fwd_ms, fb_ms) from the SAME variant (suffix after the
        prefix), chosen by min fb - deriving bwd as fb - fwd is only
        meaningful when both timings share the forward config."""
        fwd_by = {r["cfg"][len(fwd_p):]: r["ms"] for r in results
                  if r["cfg"].startswith(fwd_p) and "ms" in r}
        fb_by = {r["cfg"][len(fb_p):]: r["ms"] for r in results
                 if r["cfg"].startswith(fb_p) and "ms" in r}
        both = [v for v in fb_by if v in fwd_by]
        if not both:
            return best_ms(fwd_p), best_ms(fb_p), False
        v = min(both, key=fb_by.get)
        return fwd_by[v], fb_by[v], True

    ablation = {}
    for name, fwd_p, fb_p in (("lib", "lib_fwd_", "lib_fb_"),
                              ("xla", "xla_fwd", "xla_fb")):
        f, fb, matched = paired_ms(fwd_p, fb_p)
        bwd = None if f is None or fb is None or not matched else round(
            fb - f, 2)
        ablation[name] = {
            "fwd_ms": f, "fwdbwd_ms": fb, "bwd_ms_derived": bwd,
            "fwd_attn_tflops_per_s": tflops(fwd_flops, f),
            "bwd_attn_tflops_per_s": tflops(2.5 * fwd_flops, bwd),
        }
    # own: every fb config used best_fwd_pair for the forward, so the
    # matching fwd row is exactly own_fwd_q{bq}k{bk} at that pair
    f_own = next((r["ms"] for r in results
                  if r["cfg"] == f"own_fwd_q{best_fwd_pair[0]}k{best_fwd_pair[1]}"
                  and "ms" in r), None)
    fb_own = None if best_own is None else best_own_ms
    bwd_own = None if f_own is None or fb_own is None else round(
        fb_own - f_own, 2)
    ablation["own"] = {
        "fwd_ms": f_own, "fwdbwd_ms": fb_own, "bwd_ms_derived": bwd_own,
        "fwd_attn_tflops_per_s": tflops(fwd_flops, f_own),
        "bwd_attn_tflops_per_s": tflops(2.5 * fwd_flops, bwd_own),
    }

    payload = {
        "shape": {"batch": B, "heads": H, "seq": S, "head_dim": D},
        "device": dev,
        "rows": results,
        "best_own": (
            {f: getattr(best_own, f) for f in
             ("bq", "bk", "bq_dq", "bk_dq", "bq_dkv", "bk_dkv")}
            if best_own else None
        ),
        "best_own_ms": None if best_own is None else best_own_ms,
        "best_lib_fwdbwd": (
            min(lib_fb, key=lambda r: r["ms"]) if lib_fb else None
        ),
        "ablation": ablation,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    print(json.dumps({"wrote": out_path, "best_own": payload["best_own"],
                      "best_own_ms": payload["best_own_ms"],
                      "best_lib_fwdbwd": payload["best_lib_fwdbwd"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
