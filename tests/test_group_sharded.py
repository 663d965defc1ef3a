"""group_sharded_parallel (ZeRO levels) veneer.

Parity: test/collective/fleet dygraph_group_sharded_* tests — train-loss
parity between sharded and unsharded runs.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn, optimizer as opt


def _data():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 16), np.float32)
    y = rng.standard_normal((8, 4), np.float32)
    return x, y


def _train(level):
    s = dist.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 4, "mp_degree": 1}
    dist.fleet.init(is_collective=True, strategy=s)
    try:
        paddle.seed(3)
        model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
        optimizer = opt.AdamW(1e-2, parameters=model.parameters())
        if level is not None:
            model, optimizer, _ = dist.sharding.group_sharded_parallel(
                model, optimizer, level)
        x, y = _data()
        losses = []
        for _ in range(5):
            pred = model(paddle.to_tensor(x))
            loss = ((pred - paddle.to_tensor(y)) ** 2).mean()
            loss.backward()
            optimizer.step()
            optimizer.clear_grad()
            losses.append(float(loss.numpy()))
        return losses
    finally:
        dist.set_hybrid_communicate_group(None)


@pytest.mark.parametrize("level", ["os", "os_g", "p_g_os"])
def test_group_sharded_loss_parity(level):
    ref = _train(None)
    got = _train(level)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-6)
    assert got[-1] < got[0]  # actually trained


def test_group_sharded_bad_level_and_offload():
    s = dist.DistributedStrategy()
    dist.fleet.init(is_collective=True, strategy=s)
    try:
        model = nn.Linear(4, 4)
        optimizer = opt.AdamW(1e-2, parameters=model.parameters())
        with pytest.raises(ValueError, match="level"):
            dist.sharding.group_sharded_parallel(model, optimizer, "zz")
        with pytest.raises(NotImplementedError):
            dist.sharding.group_sharded_parallel(model, optimizer, "os",
                                                 offload=True)
    finally:
        dist.set_hybrid_communicate_group(None)


def test_save_group_sharded_model(tmp_path):
    s = dist.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 4}
    dist.fleet.init(is_collective=True, strategy=s)
    try:
        paddle.seed(3)
        model = nn.Linear(8, 8)
        optimizer = opt.AdamW(1e-2, parameters=model.parameters())
        model, optimizer, _ = dist.sharding.group_sharded_parallel(
            model, optimizer, "p_g_os")
        dist.sharding.save_group_sharded_model(model, str(tmp_path), optimizer)
        sd = paddle.load(str(tmp_path / "model.pdparams"))
        assert set(sd) == set(model.state_dict())
    finally:
        dist.set_hybrid_communicate_group(None)


def test_zero3_keeps_tensor_parallel_placements():
    """ZeRO-3 over ``sharding`` must ADD a shard to a tensor-parallel
    weight, not replace its ``mp`` shard: under mp2 x sharding2 a device
    holds a quarter of each parallel matrix (the rewrite used to start
    from all-Replicate and left every weight whole across ``mp``)."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.parallel_layers import (ColumnParallelLinear,
                                                        RowParallelLinear)

    s = dist.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 1, "mp_degree": 2, "sharding_degree": 2}
    s.sharding_configs = {"stage": 3}
    dist.fleet.init(is_collective=True, strategy=s)
    try:
        model = nn.Sequential(ColumnParallelLinear(16, 32, has_bias=False,
                                                   gather_output=False),
                              RowParallelLinear(32, 16, has_bias=False,
                                                input_is_parallel=True))
        model = dist.fleet.distributed_model(model)
        col, row = (p._array for p in model.parameters())
        assert col.sharding.spec == P(None, ("sharding", "mp"))
        assert row.sharding.spec == P(("sharding", "mp"))
        for arr in (col, row):
            assert len(arr.sharding.device_set) == 4
            assert arr.addressable_shards[0].data.nbytes * 4 == arr.nbytes
    finally:
        dist.set_hybrid_communicate_group(None)
