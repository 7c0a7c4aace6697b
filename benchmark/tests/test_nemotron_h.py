"""What `test_correct.py` covers for a family that trains under tensor
parallelism and is served, covered for `nemotron_h`, which trains under data
parallelism alone and is not served yet: across two chips the sound run is
correct, and with the data-parallel gradient sum left out (every sum over the
data axis takes chip 0's part alone) it is not. The one-chip cases (sound run,
float8 control, state unchanged, half batch) are `test_correct.py`'s own and
pass for this family; its tensor-parallel case and its three serving cases
cannot (`PERF.md` section 7)."""
import jax
import jax.numpy as jnp

import tiny

FAMILY = "nemotron_h"
ACROSS = dict(dp=2, tp=1, optimizer="sgd", lr=0.01)


def limits_from(sound, floor=1e-6):
    return {k: max(3.0 * v, floor) for k, v in sound.items()
            if k in tiny.TRAIN_NUMBERS}


def test_sound_run_on_two_chips_is_correct_and_follows_one_chip():
    one = tiny.values(tiny.run_train(tiny.train_spec(
        FAMILY, **dict(ACROSS, dp=1))))
    line = tiny.run_train(tiny.train_spec(FAMILY, **ACROSS))
    assert line["correct"], line["compared"]
    two = tiny.values(line)
    # each gap is rounding against the reference, so the two runs' differ by
    # their own size; two chips may not lie twice as far off as one
    for name in ("grad_norm_gap", "change_norm_gap", "grad_diff_norm"):
        assert two[name] <= 2.0 * one[name] + 1e-6, name


def test_gradient_sum_between_chips_left_out_makes_the_run_not_correct(
        monkeypatch):
    sound = tiny.run_train(tiny.train_spec(FAMILY, **ACROSS))
    assert sound["correct"], sound["compared"]
    psum = jax.lax.psum

    def chip0_alone(x, axis_name, **kw):
        names = axis_name if isinstance(axis_name, tuple) else (axis_name,)
        if "data" in names:
            first = jax.lax.axis_index("data") == 0

            def alone(a):
                a = jnp.where(first, a, 0)
                rest = tuple(n for n in names if n not in jax.typeof(a).vma)
                return jax.lax.pcast(a, rest, to="varying") if rest else a

            x = jax.tree.map(alone, x)
        return psum(x, axis_name, **kw)

    monkeypatch.setattr(jax.lax, "psum", chip0_alone)
    spec = tiny.train_spec(FAMILY, limits_from(tiny.values(sound)), **ACROSS)
    line = tiny.run_train(spec)
    assert line["correct"] is False, line["compared"]
