"""Supervised elastic mini-trainer for the multi-process supervisor tests.

A REAL jax multi-process workload (coordinator handshake, global mesh,
cross-process collectives, multi-process-safe checkpointing, elastic
resume onto whatever world size the supervisor relaunches with) that
deliberately avoids shard_map, so - unlike lm_train.py - it executes on
the pinned CI container's jax too. The state carries one leaf of each
multi-process checkpoint flavor:

- ``w``   (4, 4) f32, replicated  -> saved via the local-replica read
- ``acc`` (12,)  f32, P('data')   -> saved via process_allgather

Each step i adds deterministic, step-indexed values, so the final state
is a pure function of the step count alone - any kill/shrink/resume
schedule that preserves the cursor must land on the same numbers, which
is exactly what the parent test asserts.

Argv: <ckpt_dir> <stop_at_step> [step_sleep_s]
Env (set by train/supervisor.py): JAX_COORDINATOR_ADDRESS /
JAX_NUM_PROCESSES / JAX_PROCESS_ID, DNN_TPU_HEARTBEAT_FILE,
DNN_TPU_SUPERVISOR. Prints one "SV_RESULT {json}" line on completion;
exits PREEMPT_RC (75) on a cooperative SIGTERM preemption.
"""

import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ACC_LEN = 12  # divisible by every world size the tests use (1/2/3/4/6)


def main() -> int:
    from distributed_neural_network_tpu.parallel.distributed import (
        distribute_host_data,
        initialize,
    )

    initialize()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_neural_network_tpu.train.monitor import attach_monitor
    from distributed_neural_network_tpu.train.supervisor import PREEMPT_RC
    from distributed_neural_network_tpu.utils.checkpoint import (
        TreeCheckpointer,
    )

    ckpt_dir = sys.argv[1]
    stop_at = int(sys.argv[2])
    step_sleep = float(sys.argv[3]) if len(sys.argv) > 3 else 0.0

    n_dev = jax.device_count()
    assert ACC_LEN % n_dev == 0, (ACC_LEN, n_dev)
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    w_sh = NamedSharding(mesh, P())
    acc_sh = NamedSharding(mesh, P("data"))

    monitor = attach_monitor(metrics_port=None, log=print)
    registry = monitor.registry

    preempted = {"flag": False}

    def on_term(signum, frame):
        preempted["flag"] = True

    signal.signal(signal.SIGTERM, on_term)

    @jax.jit
    def step_fn(w, acc, x):
        # w is replicated, acc/x are data-sharded; the scalar reduction
        # crosses every process in the group
        return w + x.sum() * 0.001, acc + x

    ck = TreeCheckpointer(ckpt_dir, backend="npz", registry=registry)
    w = jax.device_put(jnp.zeros((4, 4), jnp.float32), w_sh)
    acc = jax.device_put(jnp.zeros((ACC_LEN,), jnp.float32), acc_sh)
    step0 = 0
    template = {
        "w": jax.ShapeDtypeStruct((4, 4), jnp.float32),
        "acc": jax.ShapeDtypeStruct((ACC_LEN,), jnp.float32),
    }
    restored = ck.restore_latest(template, {"w": w_sh, "acc": acc_sh})
    if restored is not None:
        state, meta, last = restored
        w, acc = state["w"], state["acc"]
        step0 = last + 1
        print(f"(sv_worker: resumed from step {last}; world {n_dev})",
              flush=True)

    i = step0
    while i < stop_at:
        x = distribute_host_data(
            np.full((ACC_LEN,), float(i), np.float32), mesh, P("data")
        )
        w, acc = step_fn(w, acc, x)
        jax.block_until_ready(w)
        registry.beat(i)
        # checkpoint EVERY step: the chaos kill can land anywhere and the
        # survivors must still find a consistent save to shrink from
        ck.save(i, {"w": w, "acc": acc}, {"step": i, "world": n_dev})
        if preempted["flag"]:
            print(f"(sv_worker: preempted after step {i}; emergency "
                  "checkpoint is on disk)", flush=True)
            monitor.close()
            if os.environ.get("DNN_TPU_SUPERVISOR"):
                # skip the jax distributed-runtime shutdown barrier: the
                # peers are still mid-step and would hold this exit (and
                # with it the supervisor's restart) for the barrier's
                # multi-minute timeout; state is already on disk
                sys.stdout.flush()
                os._exit(PREEMPT_RC)
            return 0
        if step_sleep:
            time.sleep(step_sleep)
        i += 1

    # jit-reduced scalars are fully replicated, so float() reads the
    # local replica even when the arrays span processes
    final = float(jax.jit(jnp.sum)(w)) + float(jax.jit(jnp.sum)(acc))
    print("SV_RESULT " + json.dumps({
        "process": int(jax.process_index()),
        "nprocs": int(jax.process_count()),
        "devices": n_dev,
        "start_step": step0,
        "final": final,
    }), flush=True)
    monitor.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
