"""`correct` comes out false when it should: the control (the reference at
the precision below the configuration's, put in the program's place) reads
well above the sound program, and each fault a cell can have, planted under
the harness's own run, makes the run not correct. Once for every family under
`benchmark/families/`, at its own tiny sizes.

Limits here are set as the cells' are (three times the sound reading at
this size, from the sound run of this very test), since a cell's own limits
belong to its own size.
"""
import jax
import jax.numpy as jnp
import pytest

import tiny
from lib import compare, harness, train, weights


@pytest.fixture(scope="module", params=tiny.FAMILIES)
def family(request):
    return request.param


@pytest.fixture(scope="module")
def sound_train(family):
    line = tiny.run_train(tiny.train_spec(family))
    assert line["correct"], line["compared"]
    return tiny.values(line)


def limits_from(sound, floor=1e-6):
    return {k: max(3.0 * v, floor) for k, v in sound.items()
            if k in tiny.TRAIN_NUMBERS}


def test_control_fp8_comes_out_not_correct(family, sound_train):
    """The reference at float8 operands in the program's place, against the
    float32 reference, through the harness's own comparison: not correct,
    and the sound run's own numbers correct under the same limits."""
    spec = tiny.train_spec(family)
    fam, model, tr = spec["family"], spec["config"], spec["traffic"]
    bf = weights.make_batch_fn(7, batch=tr["batch"], seq=tr["seq"],
                               vocab=fam.weights.vocab(model))
    ref = train.reference_steps(7, fam, model, tr, bf, "f32",
                                keep_first_grad=True)
    low = train.reference_steps(7, fam, model, tr, bf, "fp8",
                                against=ref.pop("first_grad"))
    limits = limits_from(sound_train)
    held = compare.with_limits(compare.train_numbers(low, ref), limits)
    assert harness.judge(held) is False, (held, sound_train)
    sound = {k: (v, "") for k, v in sound_train.items()}
    assert harness.judge(compare.with_limits(sound, limits)) is True


def copies(tree):
    return jax.tree.map(jnp.copy, tree)


def state_unchanged(step):
    def broken(p, m, tok, tgt):
        out = step(copies(p), copies(m), tok, tgt)
        return p, m, out[2]
    return broken


def half_batch(step):
    def broken(p, m, tok, tgt):
        h = tok.shape[0] // 2
        return step(p, m, jnp.concatenate([tok[:h], tok[:h]]),
                    jnp.concatenate([tgt[:h], tgt[:h]]))
    return broken


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_training_fault_makes_the_run_not_correct(family, sound_train, fault):
    spec = tiny.train_spec(family, limits_from(sound_train))
    line = tiny.run_train(spec, wrap_step=fault)
    assert line["correct"] is False, line["compared"]
    if fault is state_unchanged:
        assert tiny.values(line)["change_norm_gap"] == pytest.approx(1.0)


def test_exchange_between_chips_left_out_makes_the_run_not_correct(
        family, monkeypatch):
    """dp 2 x tp 2 on four virtual devices: sound first, then with every
    tensor-parallel sum taking chip 0's part alone."""
    across = dict(dp=2, tp=2, optimizer="sgd", lr=0.01)
    sound = tiny.run_train(tiny.train_spec(family, **across))
    assert sound["correct"], sound["compared"]
    psum = jax.lax.psum

    def chip0_alone(x, axis_name, **kw):
        if axis_name == "model":
            first = jax.lax.axis_index("model") == 0
            x = jax.tree.map(lambda a: jnp.where(first, a, 0), x)
        return psum(x, axis_name, **kw)

    monkeypatch.setattr(jax.lax, "psum", chip0_alone)
    spec = tiny.train_spec(family, limits_from(tiny.values(sound)), **across)
    line = tiny.run_train(spec)
    assert line["correct"] is False, line["compared"]


def test_sound_serving_run_is_correct_and_its_control_is_not(family, tmp_path):
    # some hundreds of tokens, as a cell compares: float8 then puts another
    # token first at several positions, which a few dozen need not show
    many = dict(check_requests=48)
    sound = tiny.values(tiny.run_serve(
        tiny.serve_spec(family, tmp_path, **many)))
    # on the CPU the served tokens are the reference's own: the gap reads 0
    limit = max(3 * sound["served_logit_gap"], 1e-3)
    spec = tiny.serve_spec(family, tmp_path, limits={
        "served_logit_gap": limit, "requests_short": 0}, **many)
    line = tiny.run_serve(spec, precision="fp8")
    assert line["correct"] and line["failed"] == 0
    assert tiny.values(line)["tokens_compared"] >= 200
    held = line["control"]["compared"]["served_logit_gap"]
    assert line["control"]["correct"] is False and held["value"] > limit


def test_altered_token_makes_the_serving_run_not_correct(family, tmp_path):
    spec = tiny.serve_spec(family, tmp_path)
    vocab = spec["family"].weights.vocab(spec["config"])

    def alter(engine):
        emit = engine._emit

        def wrong(seq, tok):
            # every request's third token is replaced where it is produced
            return emit(seq, (tok + 1) % vocab if len(seq.out) == 2 else tok)
        engine._emit = wrong

    sound = tiny.values(tiny.run_serve(spec))
    limit = max(3 * sound["served_logit_gap"], 0.02)
    spec = tiny.serve_spec(family, tmp_path, limits={
        "served_logit_gap": limit, "requests_short": 0})
    line = tiny.run_serve(spec, wrap_engine=alter)
    assert line["correct"] is False, line["compared"]


def test_open_loop_times_requests_from_when_they_were_due(family, tmp_path):
    """The generator's other loop through the same harness: Poisson arrivals
    from a fixed pool of senders, latencies from the due time, lateness
    reported."""
    spec = tiny.serve_spec(family, tmp_path, loop="open", rate_per_s=8.0,
                           gap_block=16, senders=8)
    line = tiny.run_serve(spec)
    assert line["correct"] and line["failed"] == 0
    assert 6 <= line["attempted"] <= 16          # 1.5 s at 8 a second
    assert line["lag_p95_ms"] is not None and line["ttft_p50_ms"] > 0
