"""Subprocess worker for the multi-process distributed + resume tests
(mirrors the reference harness: tests/unittests/test_dist_base.py:35-540
forks localhost pserver/trainer processes and pickles losses back).

Modes:
  dist    <trainer_id>  — join a 2-process jax.distributed CPU cluster via
                          init_distributed_env, train data-parallel over the
                          GLOBAL mesh, dump per-step losses.
  dist_tp <trainer_id>  — join a 2-process cluster and train TENSOR
                          parallel (dp=2 x tp=2 over the 4 global devices,
                          Megatron column/row split of the MLP) via
                          ShardedProgram; dump per-step losses.
  train   <steps> <out_dir> [load_dir]
                        — single-process train (optionally resuming from a
                          checkpoint); saves persistables + losses.
  train_tp_ref <out>    — single-process reference trajectory for dist_tp
                          (same model/batches, no sharding).
"""

import json
import os
import sys

# Workers run on the backend JAX_PLATFORMS names, the CPU when unset; the
# config update is authoritative as long as it runs before device init
# (as in tests/conftest.py).
import jax

jax.config.update("jax_platforms", os.environ.get("JAX_PLATFORMS", "cpu"))


def build_model():
    import paddle_tpu as pt
    from paddle_tpu import layers

    x = layers.data(name="x", shape=[8], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(x, size=16, act="tanh")
    pred = layers.fc(h, size=1)
    loss = layers.mean(layers.square(pred - y))
    opt = pt.optimizer.MomentumOptimizer(learning_rate=0.05, momentum=0.9)
    opt.minimize(loss)
    return loss


def build_tp_model():
    """MLP with Megatron-style named params: col_w column-parallel,
    row_w row-parallel (tensor parallel over mesh axis 'model')."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.param_attr import ParamAttr

    x = layers.data(name="x", shape=[8], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(x, size=16, act="tanh",
                  param_attr=ParamAttr(name="tp_col_w"),
                  bias_attr=ParamAttr(name="tp_col_b"))
    h2 = layers.fc(h, size=8, act="tanh",
                   param_attr=ParamAttr(name="tp_row_w"),
                   bias_attr=ParamAttr(name="tp_row_b"))
    pred = layers.fc(h2, size=1)
    loss = layers.mean(layers.square(pred - y))
    opt = pt.optimizer.MomentumOptimizer(learning_rate=0.05, momentum=0.9)
    opt.minimize(loss)
    return loss


def _tp_plan(n_global):
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.sharding import ShardingPlan

    return ShardingPlan(
        mesh_axes={"data": n_global // 2, "model": 2},
        param_rules=[
            (r"tp_col_w", P(None, "model")),
            (r"tp_col_b", P("model")),
            (r"tp_row_w", P("model", None)),
        ],
    )


def run_dist_tp(trainer_id):
    import numpy as np

    from paddle_tpu.parallel.distributed import init_distributed_env

    init_distributed_env()
    import jax

    assert jax.process_count() == 2, jax.process_count()

    import paddle_tpu as pt
    from paddle_tpu.parallel.sharding import ShardedProgram

    loss = build_tp_model()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    plan = _tp_plan(jax.device_count())
    sharded = ShardedProgram(pt.default_main_program(), plan,
                             loss_name=loss.name)
    losses = []
    for step in range(6):
        (lv,) = exe.run(sharded, feed=batch(step), fetch_list=[loss])
        losses.append(float(np.asarray(lv)))
    if trainer_id == 0:
        with open(os.environ["DIST_OUT"], "w") as f:
            json.dump({"losses": losses, "devices": jax.device_count()}, f)


def run_train_tp_ref(out):
    import numpy as np

    import paddle_tpu as pt

    loss = build_tp_model()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    losses = []
    for step in range(6):
        (lv,) = exe.run(feed=batch(step), fetch_list=[loss])
        losses.append(float(np.asarray(lv)))
    with open(out, "w") as f:
        json.dump({"losses": losses}, f)


def batch(step, n=16):
    import numpy as np

    rng = np.random.RandomState(1000 + step)
    x = rng.randn(n, 8).astype("float32")
    y = (x.sum(axis=1, keepdims=True) * 0.3).astype("float32")
    return {"x": x, "y": y}


def run_dist(trainer_id):
    import numpy as np

    from paddle_tpu.parallel.distributed import init_distributed_env

    env = init_distributed_env()
    assert env.num_trainers == 2

    import jax

    assert jax.process_count() == 2, jax.process_count()

    import paddle_tpu as pt

    loss = build_model()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    compiled = pt.CompiledProgram(
        pt.default_main_program()
    ).with_data_parallel(loss_name=loss.name)

    losses = []
    for step in range(6):
        (lv,) = exe.run(compiled, feed=batch(step), fetch_list=[loss])
        losses.append(float(np.asarray(lv)))

    if trainer_id == 0:
        with open(os.environ["DIST_OUT"], "w") as f:
            json.dump({"losses": losses, "devices": jax.device_count()}, f)


def run_train(steps, out_dir, load_dir=None):
    import numpy as np

    import paddle_tpu as pt

    loss = build_model()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    start = 0
    if load_dir:
        pt.io.load_persistables(exe, load_dir)
        with open(os.path.join(load_dir, "meta.json")) as f:
            start = json.load(f)["step"]
    losses = []
    for step in range(start, start + steps):
        (lv,) = exe.run(feed=batch(step), fetch_list=[loss])
        losses.append(float(np.asarray(lv)))
    os.makedirs(out_dir, exist_ok=True)
    pt.io.save_persistables(exe, out_dir)
    with open(os.path.join(out_dir, "meta.json"), "w") as f:
        json.dump({"step": start + steps}, f)
    with open(os.path.join(out_dir, "losses.json"), "w") as f:
        json.dump(losses, f)


if __name__ == "__main__":
    mode = sys.argv[1]
    if mode == "dist":
        run_dist(int(sys.argv[2]))
    elif mode == "dist_tp":
        run_dist_tp(int(sys.argv[2]))
    elif mode == "train_tp_ref":
        run_train_tp_ref(sys.argv[2])
    elif mode == "train":
        run_train(int(sys.argv[2]), sys.argv[3],
                  sys.argv[4] if len(sys.argv) > 4 else None)
    else:
        raise SystemExit(f"unknown mode {mode}")
