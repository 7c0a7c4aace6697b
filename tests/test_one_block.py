"""The GPT-2 block is written once (models/transformer.py `block_qkv` /
`block_out`, with `final_logits` after the last one), and every caller
runs it: the training forward, `generate`, and the serving engine's four
bucket families, which keep only how they address their cache.

With `block_out` and `final_logits` replaced by spies that count at RUN
time (a callback in the program, so a scanned layer counts once a layer),
each caller must go through the block once a layer of each forward pass it
makes, and through the head once a pass. A caller that spells the block
itself goes through neither."""

import jax
import jax.numpy as jnp
import pytest

from distributed_neural_network_tpu.models import transformer as tfm
from distributed_neural_network_tpu.serve.engine import (
    EngineConfig,
    ServeEngine,
)

CFG = tfm.TransformerConfig(
    vocab_size=32, d_model=32, n_heads=4, n_layers=3, d_ff=64
)
SPEC_K, DRAFT_LAYERS, CHUNK, B, W = 2, 2, 4, 2, 1


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.key(0), CFG)


@pytest.fixture
def spies(monkeypatch):
    """name -> the shapes of x the spied function ran on, one entry a run."""
    seen = {"block_out": [], "final_logits": []}

    def spy(name, x_of):
        real = getattr(tfm, name)

        def wrapped(*args, **kw):
            shape = tuple(x_of(args).shape)
            jax.debug.callback(lambda: seen[name].append(shape))
            return real(*args, **kw)

        monkeypatch.setattr(tfm, name, wrapped)

    spy("block_out", lambda args: args[0])      # (x, o, lp, cfg, ...)
    spy("final_logits", lambda args: args[1])   # (params, x, dt)
    return seen


def _engine_program(params, family):
    """One dispatch of one bucket program, as `ServeEngine.warmup` makes
    it (every write lands in the scratch block)."""
    eng = ServeEngine(params, CFG, EngineConfig(
        max_batch=B, num_blocks=16, block_size=16, max_seq_len=W * 16,
        prefill_chunk=CHUNK, spec_decode=SPEC_K,
        spec_draft_layers=DRAFT_LAYERS,
    ))

    def zeros(*shape):
        return jnp.zeros(shape, jnp.int32)

    if family == "decode":
        eng._run_writer(
            eng._decode_fn(B, W), zeros(B), zeros(B), zeros(B, W),
            jnp.zeros((B,), jnp.float32), jnp.zeros((B, 2), jnp.uint32))
    elif family == "prefill":
        eng._run_writer(
            eng._prefill_fn(CHUNK, W), zeros(CHUNK), jnp.int32(0), zeros(W),
            jnp.int32(CHUNK))
    elif family == "draft":
        eng._draft_fn(B, W)(
            eng.draft_params, *eng._pools(), zeros(B), zeros(B), zeros(B, W))
    else:
        eng._run_writer(
            eng._verify_fn(B, W), zeros(B, SPEC_K + 1), zeros(B), zeros(B, W))


# caller -> (forward passes it makes, layers a pass, x of a block, x of the
# head): decode, draft and generate run one position a pass, the head on it
L, d = CFG.n_layers, CFG.d_model
CALLERS = {
    "apply": (1, L, (2, 8, d), (2, 8, d)),
    "generate": (5, L, (2, 1, d), (2, d)),
    "decode": (1, L, (B, 1, d), (B, d)),
    "prefill": (1, L, (1, CHUNK, d), (CHUNK, d)),
    "draft": (SPEC_K, DRAFT_LAYERS, (B, 1, d), (B, d)),
    "verify": (1, L, (B, SPEC_K + 1, d), (B, SPEC_K + 1, d)),
}


@pytest.mark.parametrize("caller", list(CALLERS))
def test_caller_runs_the_models_block(params, spies, caller):
    if caller == "apply":
        tfm.apply(params, jnp.zeros((2, 8), jnp.int32), CFG, attn_impl="full")
    elif caller == "generate":
        tfm.generate(params, jnp.zeros((2, 3), jnp.int32), CFG,
                     max_new_tokens=2)
    else:
        _engine_program(params, caller)
    jax.effects_barrier()
    passes, layers, x_block, x_head = CALLERS[caller]
    assert spies["block_out"] == [x_block] * (passes * layers)
    assert spies["final_logits"] == [x_head] * passes
