"""Own flash-attention kernels (ops/flash_pallas.py): fwd + grad parity.

Interpret-mode execution on CPU (the Mosaic-compiled path is exercised on
TPU via lm_train / the bench matrix). Correctness bar: forward matches the
plain attention reference and every input gradient matches `jax.grad` of
the reference through an arbitrary scalar loss, causal and non-causal,
f32 and bf16, at block sizes that tile the sequence both evenly and with
the diagonal crossing block boundaries (bq != bk).

The reference model (`/root/reference/models/model.py`) has no attention;
this pins the beyond-reference long-context family instead (SURVEY.md
section 5.7).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_neural_network_tpu.analysis.trace import _sub_jaxprs
from distributed_neural_network_tpu.ops.flash_pallas import (
    FlashBlocks,
    block_remat_policy,
    flash_mha,
)
from distributed_neural_network_tpu.parallel.ring import attention


def _qkv(b=2, s=256, h=2, d=64, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)) * 0.3, dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [
    FlashBlocks(128, 128, 128, 128, 128, 128),
    FlashBlocks(128, 64, 64, 128, 128, 64),   # diagonal crosses blocks
])
def test_forward_matches_reference(n_devices, causal, blocks):
    q, k, v = _qkv()
    out = flash_mha(q, k, v, causal=causal, blocks=blocks, interpret=True)
    ref = attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize(
    "causal,blocks",
    [
        (True, FlashBlocks(64, 64, 64, 64, 64, 64)),
        (False, FlashBlocks(64, 64, 64, 64, 64, 64)),
        # asymmetric backward pairs - the combos tools/tune_flash.py
        # sweeps on hardware (bq_dq != bk_dq, bq_dkv != bk_dkv) must be
        # numerically pinned before they burn chip time
        (True, FlashBlocks(64, 64, 32, 64, 64, 32)),
        (True, FlashBlocks(64, 64, 64, 32, 32, 64)),
    ],
)
def test_grads_match_reference(n_devices, causal, blocks):
    q, k, v = _qkv(s=128)
    # arbitrary non-uniform scalar loss so every element's cotangent differs
    w = jnp.asarray(
        np.random.default_rng(1).normal(size=q.shape), jnp.float32
    )

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_mha(q, k, v, causal=causal, blocks=blocks, interpret=True)
            * w
        )

    def loss_ref(q, k, v):
        return jnp.sum(attention(q, k, v, causal=causal) * w)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch",
        )


def test_head_dim_128_fwd_and_grads(n_devices):
    """Dh=128 (the MXU-native head geometry the hd128 bench row runs,
    H=4 x Dh=128 at d_model 512): fwd + grad parity in interpret mode -
    pinned before the config burns chip time (same rule as the
    asymmetric-block combos above)."""
    q, k, v = _qkv(s=128, h=1, d=128)
    blocks = FlashBlocks(64, 64, 64, 64, 64, 64)
    out = flash_mha(q, k, v, causal=True, blocks=blocks, interpret=True)
    ref = attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    w = jnp.asarray(
        np.random.default_rng(2).normal(size=q.shape), jnp.float32
    )

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_mha(q, k, v, causal=True, blocks=blocks, interpret=True)
            * w
        )

    def loss_ref(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) * w)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch",
        )


def test_bf16_forward_close(n_devices):
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_mha(q, k, v, causal=True,
                    blocks=FlashBlocks(128, 128, 128, 128, 128, 128),
                    interpret=True)
    ref = attention(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref),
        rtol=2e-2, atol=2e-2,
    )


def test_block_resolution_clamps_to_divisors(n_devices):
    # S=96: no 128-multiple divides it -> falls back to plain divisors
    assert FlashBlocks(512, 512, 512, 512, 512, 512).resolve(96).bq == 96
    assert FlashBlocks(64, 64, 64, 64, 64, 64).resolve(96).bq == 48
    # S=2048 keeps the requested lane-friendly sizes
    r = FlashBlocks().resolve(2048)
    assert (r.bq, r.bk) == (512, 512)
    r = FlashBlocks(384, 384, 384, 384, 384, 384).resolve(2048)
    assert r.bq == 256  # largest 128-multiple divisor <= 384


def test_tuned_blocks_file_matching(tmp_path, monkeypatch):
    """tuned_blocks picks tune files by device kind, head_dim, and seq
    (exact wins over divisor; mismatched head_dim/device never load) -
    the guard the retracted r2 sweep lacked (ops/flash.py docstring)."""
    import json

    from distributed_neural_network_tpu.ops import flash

    def write(name, seq, head_dim, bq, device="cpu"):
        payload = {
            "shape": {"batch": 1, "heads": 1, "seq": seq,
                      "head_dim": head_dim},
            "device": device,
            "best_own": {"bq": bq, "bk": bq, "bq_dq": bq, "bk_dq": bq,
                         "bq_dkv": bq, "bk_dkv": bq},
        }
        (tmp_path / name).write_text(json.dumps(payload))

    monkeypatch.setattr(flash, "_TUNE_DIR", str(tmp_path))
    flash.tuned_blocks.cache_clear()
    try:
        # no files -> defaults
        assert flash.tuned_blocks(2048, 64) == FlashBlocks()
        flash.tuned_blocks.cache_clear()
        # divisor-seq file applies; exact-seq file wins over it
        write("flash_tune_cpu_s1024.json", 1024, 64, 256)
        assert flash.tuned_blocks(2048, 64).bq == 256
        flash.tuned_blocks.cache_clear()
        write("flash_tune_cpu_s2048.json", 2048, 64, 1024)
        assert flash.tuned_blocks(2048, 64).bq == 1024
        flash.tuned_blocks.cache_clear()
        # head_dim-qualified file loads only at ITS head_dim (the d128
        # filename spelling tune_flash.py writes for D != 64)
        write("flash_tune_cpu_s2048_d128.json", 2048, 128, 512)
        assert flash.tuned_blocks(2048, 128).bq == 512
        flash.tuned_blocks.cache_clear()
        assert flash.tuned_blocks(2048, 64).bq == 1024  # d64 file intact
        flash.tuned_blocks.cache_clear()
        # divisor files still apply at larger seqs (2048 divides 4096)
        assert flash.tuned_blocks(4096, 64).bq == 1024
        flash.tuned_blocks.cache_clear()
        # wrong device kind never loads (seq 3000: no cpu file matches)
        write("flash_tune_other_s3000.json", 3000, 64, 128, device="TPU_x")
        assert flash.tuned_blocks(3000, 64) == FlashBlocks()
    finally:
        flash.tuned_blocks.cache_clear()


def _kernel_calls(jaxpr, counts=None):
    """{kernel name: pallas_call equations} over a jaxpr and all it nests."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            counts[name] = counts.get(name, 0) + 1
            continue
        for sub, _ in _sub_jaxprs(eqn):
            _kernel_calls(sub, counts)
    return counts


@pytest.mark.parametrize(
    "policy,quant,fwd_calls",
    [
        # a dots-saving policy keeps the forward kernel's (o, lse) too:
        # one forward a block, none in the backward pass
        ("dots_saveable", None, 2),
        ("dots_with_no_batch_dims_saveable", None, 2),
        ("dots_saveable", "int8", 2),
        # full recomputation keeps nothing (the hybrid cell's memory
        # contract): the backward pass runs the forward kernel again
        ("", None, 4),
        ("nothing_saveable", None, 4),
    ],
)
def test_remat_policy_keeps_forward_kernel_output(n_devices, policy, quant,
                                                  fwd_calls):
    """Two checkpointed blocks of projection + flash_mha + projection: how
    often the gradient runs the forward kernel follows from the policy's
    name alone (`block_remat_policy`), and a kept value is the recomputed
    one - the gradient equals the un-checkpointed one bit for bit."""
    b, s, h, d = 1, 128, 2, 64
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(b, s, h * d)) * 0.3, jnp.float32)
    ws = [tuple(jnp.asarray(rng.normal(size=shape) * 0.05, jnp.float32)
                for shape in ((h * d, 3 * h * d), (h * d, h * d)))
          for _ in range(2)]
    blocks = FlashBlocks(64, 64, 64, 64, 64, 64)

    def block(x, w):
        q, k, v = jnp.moveaxis((x @ w[0]).reshape(b, s, 3, h, d), 2, 0)
        o = flash_mha(q, k, v, blocks=blocks, interpret=True, quant=quant)
        return x + o.reshape(b, s, h * d) @ w[1]

    def grad_of(block):
        def loss(x, ws):
            for w in ws:
                x = block(x, w)
            return jnp.sum(x * x)
        return jax.grad(loss, argnums=(0, 1))

    kept = grad_of(jax.checkpoint(block, policy=block_remat_policy(policy)))
    calls = _kernel_calls(jax.make_jaxpr(kept)(x, ws).jaxpr)
    fwd = "flash_fwd_quant" if quant else "flash_fwd"
    assert calls == {fwd: fwd_calls, "flash_bwd_dq": 2, "flash_bwd_dkv": 2}
    for a, e in zip(jax.tree.leaves(kept(x, ws)),
                    jax.tree.leaves(grad_of(block)(x, ws))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(e))
