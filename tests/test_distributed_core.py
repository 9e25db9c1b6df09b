"""Distributed core: topology, groups, collectives on the 8-device CPU mesh.

Mirrors the reference's collective-op tests
(`/root/reference/python/paddle/fluid/tests/unittests/test_collective_api_base.py`)
which assert numerical results of allreduce/allgather/… across local ranks —
here ranks are the 8 virtual devices of the conftest mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.topology import (
    CommunicateTopology, HybridCommunicateGroup, build_mesh)


class TestTopology:
    def test_communicate_topology(self):
        # reference topology.py:36 semantics
        topo = CommunicateTopology(["data", "pipe", "model"], [2, 2, 2])
        assert topo.world_size() == 8
        assert topo.get_hybrid_group_names() == ["dp", "pp", "mp"]
        assert topo.get_dim("model") == 2
        assert topo.get_rank(dp=1, pp=0, mp=1) == 5
        assert topo.get_coord(5) == (1, 0, 1)
        assert topo.get_axis_list("dp", 0) == [0, 1, 2, 3]
        comm = topo.get_comm_list("mp")
        assert [0, 1] in comm and [6, 7] in comm and len(comm) == 4
        assert topo.get_rank_from_stage(0, pp=1) == 2

    def test_build_mesh_axis_order(self):
        mesh = build_mesh({"dp": 2, "mp": 2, "pp": 2})
        assert mesh.axis_names == ("dp", "pp", "mp")
        assert mesh.devices.shape == (2, 2, 2)

    def test_build_mesh_absorb_remaining(self):
        mesh = build_mesh({"mp": 2})
        assert mesh.axis_names == ("dp", "mp")
        assert mesh.devices.shape == (4, 2)

    def test_hcg(self):
        hcg = HybridCommunicateGroup(dims={"dp": 2, "mp": 4})
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_model_parallel_world_size() == 4
        assert hcg.get_pipe_parallel_world_size() == 1
        assert hcg.get_model_parallel_group().nranks == 4
        assert hcg.get_parallel_mode() == "model_parallel"


class TestEagerCollectives:
    """Eager collectives over sharded/replicated Tensors."""

    def setup_method(self, _):
        mesh = build_mesh({"dp": 8})
        hcg = HybridCommunicateGroup(mesh=mesh)
        dist.set_hybrid_communicate_group(hcg)
        dist.destroy_process_group()
        self.mesh = mesh
        self.group = dist.new_group(axis_name="dp")

    def teardown_method(self, _):
        dist.set_hybrid_communicate_group(None)
        dist.destroy_process_group()

    def _sharded(self, arr):
        return jax.device_put(arr, NamedSharding(self.mesh, P("dp")))

    def test_all_reduce_sum_sharded(self):
        # per-"rank" rows 0..7; all_reduce over a per-rank scalar view
        vals = np.arange(8, dtype=np.float32)
        x = paddle.to_tensor(self._sharded(vals))
        dist.all_reduce(x, group=self.group)
        np.testing.assert_allclose(x.numpy(), np.full(8, 28.0))

    def test_all_reduce_max_min(self):
        vals = np.arange(8, dtype=np.float32)
        x = paddle.to_tensor(self._sharded(vals.copy()))
        dist.all_reduce(x, op=dist.ReduceOp.MAX, group=self.group)
        np.testing.assert_allclose(x.numpy(), np.full(8, 7.0))
        y = paddle.to_tensor(self._sharded(vals.copy()))
        dist.all_reduce(y, op=dist.ReduceOp.MIN, group=self.group)
        np.testing.assert_allclose(y.numpy(), np.zeros(8))

    def test_all_reduce_replicated_counts_ranks(self):
        x = paddle.to_tensor(np.ones((4,), np.float32))
        dist.all_reduce(x, group=self.group)
        np.testing.assert_allclose(x.numpy(), np.full(4, 8.0))

    def test_broadcast(self):
        vals = np.arange(8, dtype=np.float32)
        x = paddle.to_tensor(self._sharded(vals))
        dist.broadcast(x, src=3, group=self.group)
        np.testing.assert_allclose(x.numpy(), np.full(8, 3.0))

    def test_all_gather(self):
        vals = np.arange(8, dtype=np.float32)
        x = paddle.to_tensor(self._sharded(vals))
        outs = []
        dist.all_gather(outs, x, group=self.group)
        assert len(outs) == 8
        for i, o in enumerate(outs):
            np.testing.assert_allclose(np.asarray(o), [float(i)])

    def test_reduce_scatter(self):
        # each rank holds [8] row -> after reduce_scatter each holds sum/8th
        vals = np.tile(np.arange(8, dtype=np.float32), (8, 1))  # [8,8]
        x = jax.device_put(vals, NamedSharding(self.mesh, P("dp", None)))
        out = paddle.to_tensor(np.zeros(8, np.float32))
        dist.reduce_scatter(out, paddle.to_tensor(x), group=self.group)
        # rank i gets sum over ranks of row-chunk i = 8 * i
        np.testing.assert_allclose(out.numpy(), 8.0 * np.arange(8))

    def test_barrier_and_wait(self):
        dist.barrier(self.group)
        t = paddle.to_tensor([1.0])
        assert dist.wait(t) is t


class TestInTraceCollectives:
    """SPMD path: collectives inside shard_map (the hot path)."""

    def test_psum_inside_shard_map(self):
        mesh = build_mesh({"dp": 8})
        g = dist.Group(mesh, ("dp",))

        def f(x):
            t = paddle.to_tensor(x)
            dist.all_reduce(t, group=g)
            return t.data

        vals = np.arange(8, dtype=np.float32).reshape(8, 1)
        out = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))(
            jnp.asarray(vals))
        np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 28.0))

    def test_ppermute_ring(self):
        mesh = build_mesh({"pp": 8})
        g = dist.Group(mesh, ("pp",))

        def f(x):
            return dist.ppermute(x, group=g)

        vals = np.arange(8, dtype=np.float32).reshape(8, 1)
        out = shard_map(f, mesh=mesh, in_specs=P("pp"), out_specs=P("pp"))(
            jnp.asarray(vals))
        expect = np.roll(vals, 1, axis=0)
        np.testing.assert_allclose(np.asarray(out), expect)

    def test_alltoall_in_trace(self):
        mesh = build_mesh({"mp": 8})
        g = dist.Group(mesh, ("mp",))

        def f(x):
            return dist.alltoall(x, group=g)

        # rank r holds rows [r*8 .. r*8+7]; chunk c goes to rank c
        vals = np.arange(64, dtype=np.float32).reshape(64, 1)
        out = shard_map(f, mesh=mesh, in_specs=P("mp"), out_specs=P("mp"))(
            jnp.asarray(vals))
        got = np.asarray(out).reshape(8, 8)
        expect = np.arange(64).reshape(8, 8).T  # transpose of rank/chunk grid
        np.testing.assert_allclose(got, expect)


class TestParallelEnvAndDP:
    def test_parallel_env_defaults(self):
        env = dist.init_parallel_env()
        assert env.rank == 0
        assert dist.get_rank() == 0
        assert dist.get_world_size() >= 1

    def test_data_parallel_matches_single_device(self):
        from paddle_tpu import nn, optimizer
        from paddle_tpu.nn import functional as F

        mesh = build_mesh({"dp": 8})
        dist.set_hybrid_communicate_group(HybridCommunicateGroup(mesh=mesh))
        try:
            paddle.seed(7)
            net = nn.Linear(16, 4)
            ref_w = net.weight.numpy().copy()
            X = np.random.RandomState(0).randn(32, 16).astype(np.float32)
            Y = np.random.RandomState(1).randint(0, 4, (32,)).astype(np.int32)

            # single-device reference step
            opt = optimizer.SGD(learning_rate=0.1,
                                parameters=net.parameters())
            loss = F.cross_entropy(net(paddle.to_tensor(X)),
                                   paddle.to_tensor(Y))
            loss.backward()
            opt.step()
            ref_after = net.weight.numpy().copy()
            ref_loss = float(loss)

            # DP step: same math, batch sharded over 8 devices
            paddle.seed(7)
            net2 = nn.Linear(16, 4)
            np.testing.assert_allclose(net2.weight.numpy(), ref_w)
            dp = dist.DataParallel(net2)
            opt2 = optimizer.SGD(learning_rate=0.1,
                                 parameters=dp.parameters())
            xb = dist.shard_batch(paddle.to_tensor(X), mesh=mesh)
            yb = dist.shard_batch(paddle.to_tensor(Y), mesh=mesh)
            loss2 = F.cross_entropy(dp(xb), yb)
            loss2.backward()
            opt2.step()
            assert abs(float(loss2) - ref_loss) < 1e-5
            np.testing.assert_allclose(net2.weight.numpy(), ref_after,
                                       rtol=1e-5, atol=1e-6)
        finally:
            dist.set_hybrid_communicate_group(None)
            dist.destroy_process_group()


class TestEagerAllReduceSemantics:
    """Single-controller all_reduce semantics (docstring contract): a tensor
    SHARDED over the group axis reduces per-shard values — the case real
    data-parallel pipelines hit; a replicated tensor sums N equal copies."""

    def test_sharded_input_reduces_per_shard_values(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        dist.init_parallel_env()
        g = dist.new_group(list(range(8)))
        mesh = g.mesh
        # 8 shards, shard r holds value r: sum must be 0+1+...+7 = 28
        per_rank = np.arange(8, dtype=np.float32).reshape(8, 1)
        x = paddle.to_tensor(per_rank)
        x.data = jax.device_put(x.data, NamedSharding(mesh, P(g.axis)))
        dist.all_reduce(x)
        np.testing.assert_allclose(np.asarray(x.data),
                                   np.full((8, 1), 28.0, np.float32))

    def test_replicated_input_counts_group_size(self):
        dist.init_parallel_env()
        g = dist.new_group(list(range(8)))
        x = paddle.to_tensor(np.full((4,), 2.0, np.float32))
        dist.all_reduce(x)
        np.testing.assert_allclose(np.asarray(x.data),
                                   np.full((4,), 16.0, np.float32))
