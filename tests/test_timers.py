"""hard_block / PhaseTimers (utils/timers.py).

hard_block is the fence every timed phase ends on (`jax.block_until_ready`;
`chip_smoke.py`'s fence phase checks on the chip that it waits as long as a
value fetch does). These tests pin its contract on ordinary trees so a
refactor cannot silently break the fence the timings rest on.
"""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_neural_network_tpu.utils import timers as T


def test_hard_block_handles_mixed_trees():
    tree = {
        "f32": jnp.ones((4, 4)),
        "int": jnp.arange(5),
        "bool": jnp.ones((3,), bool),
        "scalar": jnp.float32(2.0),
        "empty": jnp.zeros((0, 7)),
        "py": 3.5,
        "none": None,
    }
    T.hard_block(tree)  # must not raise on any leaf kind


def test_hard_block_none_and_empty():
    T.hard_block(None)
    T.hard_block({})
    T.hard_block({"only_empty": jnp.zeros((0,))})


def test_hard_block_sharded_tree(n_devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()).reshape(-1), ("d",))
    x = jax.device_put(
        jnp.arange(16.0).reshape(8, 2), NamedSharding(mesh, P("d"))
    )
    T.hard_block({"x": x})


def test_phase_timers_accumulate_and_fence():
    timers = T.PhaseTimers()
    with timers.phase(T.TRAINING) as t:
        t.value = jnp.ones((8, 8)) @ jnp.ones((8, 8))
    with timers.phase(T.TRAINING):
        pass
    assert timers.get(T.TRAINING) > 0.0
    assert set(timers.summary()) == {T.TRAINING}


def test_phase_timers_merge_accumulates_and_returns_self():
    a = T.PhaseTimers()
    a.add(T.TRAINING, 1.0)
    a.add(T.COMMUNICATION, 0.5)
    b = T.PhaseTimers()
    b.add(T.TRAINING, 2.0)
    b.add("custom_phase", 0.25)
    out = a.merge(b)
    assert out is a
    assert a.get(T.TRAINING) == 3.0
    assert a.get(T.COMMUNICATION) == 0.5
    assert a.get("custom_phase") == 0.25
    assert b.get(T.TRAINING) == 2.0  # merge source untouched


def test_phase_timers_report_canonical_order_and_labels():
    timers = T.PhaseTimers()
    timers.add(T.COMMUNICATION, 0.5)
    timers.add(T.TRAINING, 2.0)
    timers.add("zz_extra", 0.1)
    lines = timers.report().splitlines()
    # canonical phases lead in the reference's order/phrasing, always all
    # of them (evaluation/data_loading print 0.0 even though never timed)
    assert lines[0] == "Train data loading time: 0.0"
    assert lines[1] == "Time spent on training: 2.0"
    assert lines[2] == "Time spent on evaluation: 0.0"
    assert lines[3] == (
        "Time spent on parent communication and param sync: 0.5"
    )
    assert lines[4] == "zz_extra: 0.1"
    assert len(lines) == 5
    assert tuple(T.CANONICAL_PHASES) == (
        T.DATA_LOADING, T.TRAINING, T.EVALUATION, T.COMMUNICATION
    )
