"""Collective + mesh tests (analog of ray: python/ray/util/collective/tests/)."""

import numpy as np
import pytest

import ray_tpu

pytestmark = pytest.mark.collective


@ray_tpu.remote
class CollectiveWorker:
    def _rt_init_collective(self, world_size, rank, backend, group_name):
        from ray_tpu.util import collective as col

        col.init_collective_group(world_size, rank, backend, group_name)
        return rank

    def do_allreduce(self, value, group_name):
        from ray_tpu.util import collective as col

        arr = np.full((4,), float(value))
        out = col.allreduce(arr, group_name)
        return out

    def do_allgather(self, value, group_name):
        from ray_tpu.util import collective as col

        return col.allgather(np.full((2,), float(value)), group_name)

    def do_broadcast(self, value, group_name):
        from ray_tpu.util import collective as col

        arr = np.full((3,), float(value))
        return col.broadcast(arr, src_rank=0, group_name=group_name)

    def do_reducescatter(self, value, group_name):
        from ray_tpu.util import collective as col

        arr = np.full((4, 2), float(value))
        return col.reducescatter(arr, group_name)

    def do_barrier(self, group_name):
        from ray_tpu.util import collective as col

        col.barrier(group_name)
        return True


def test_collective_store_backend(ray_start_regular):
    from ray_tpu.util import collective as col

    workers = [CollectiveWorker.remote() for _ in range(2)]
    col.create_collective_group(workers, 2, [0, 1], backend="store",
                                group_name="g1")
    outs = ray_tpu.get(
        [w.do_allreduce.remote(i + 1, "g1") for i, w in enumerate(workers)],
        timeout=60,
    )
    for out in outs:
        np.testing.assert_allclose(out, np.full((4,), 3.0))
    gathered = ray_tpu.get(
        [w.do_allgather.remote(i + 1, "g1") for i, w in enumerate(workers)],
        timeout=60,
    )
    for g in gathered:
        assert len(g) == 2
        np.testing.assert_allclose(g[0], np.full((2,), 1.0))
        np.testing.assert_allclose(g[1], np.full((2,), 2.0))
    bc = ray_tpu.get(
        [w.do_broadcast.remote(i + 10, "g1") for i, w in enumerate(workers)],
        timeout=60,
    )
    np.testing.assert_allclose(bc[0], np.full((3,), 10.0))
    np.testing.assert_allclose(bc[1], np.full((3,), 10.0))
    rs = ray_tpu.get(
        [w.do_reducescatter.remote(i + 1, "g1") for i, w in enumerate(workers)],
        timeout=60,
    )
    np.testing.assert_allclose(rs[0], np.full((2, 2), 3.0))
    np.testing.assert_allclose(rs[1], np.full((2, 2), 3.0))
    assert all(
        ray_tpu.get([w.do_barrier.remote("g1") for w in workers], timeout=60)
    )


@ray_tpu.remote
class XlaCollectiveWorker:
    """A rank in a jax.distributed gang — the real backend="xla" path."""

    def setup(self, coordinator, world_size, rank):
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.distributed.initialize(
            coordinator, num_processes=world_size, process_id=rank
        )
        from ray_tpu.util import collective as col

        col.init_collective_group(world_size, rank, backend="xla",
                                  group_name="xg")
        return rank

    def do_ops(self, rank):
        import numpy as np

        from ray_tpu.util import collective as col

        out = {}
        out["ar"] = col.allreduce(np.full((4,), float(rank + 1)), "xg")
        out["ag"] = col.allgather(np.full((2,), float(rank + 1)), "xg")
        out["bc"] = col.broadcast(np.full((3,), float(rank + 10)), src_rank=0,
                                  group_name="xg")
        out["rs"] = col.reducescatter(
            np.arange(8, dtype=np.float32).reshape(4, 2) * (rank + 1), "xg"
        )
        col.barrier("xg")
        return out


def test_collective_xla_backend(ray_start_regular):
    """backend="xla": ops run as compiled shard_map programs over a global
    mesh spanning the jax.distributed gang (reference analog: the NCCL group
    in ray: util/collective/collective_group/nccl_collective_group.py)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    coordinator = f"127.0.0.1:{port}"

    workers = [XlaCollectiveWorker.remote() for _ in range(2)]
    ray_tpu.get(
        [w.setup.remote(coordinator, 2, i) for i, w in enumerate(workers)],
        timeout=300,
    )
    outs = ray_tpu.get(
        [w.do_ops.remote(i) for i, w in enumerate(workers)], timeout=300
    )
    for out in outs:
        np.testing.assert_allclose(out["ar"], np.full((4,), 3.0))
        np.testing.assert_allclose(out["ag"][0], np.full((2,), 1.0))
        np.testing.assert_allclose(out["ag"][1], np.full((2,), 2.0))
        np.testing.assert_allclose(out["bc"], np.full((3,), 10.0))
    reduced = np.arange(8, dtype=np.float32).reshape(4, 2) * 3
    np.testing.assert_allclose(outs[0]["rs"], reduced[:2])
    np.testing.assert_allclose(outs[1]["rs"], reduced[2:])


def test_mesh_and_ingraph_collectives():
    import jax
    import jax.numpy as jnp

    from ray_tpu import parallel

    assert len(jax.devices()) == 8, "conftest must force 8 virtual devices"
    mesh = parallel.create_mesh({"data": 4, "model": 2})
    assert mesh.shape == {"data": 4, "model": 2}

    mesh2 = parallel.auto_mesh(model=2)
    assert mesh2.shape["model"] == 2 and mesh2.shape["data"] == 4

    # compiled allreduce: psum over data axis
    ar = parallel.compiled_allreduce(mesh, "data")
    x = jnp.arange(8.0)
    out = ar(x)
    # each data shard of size 2 is summed across 4 data ranks; model axis
    # replicates. Sum over the data axis of the per-shard values:
    x_resh = x.reshape(4, 2)
    expected = jnp.tile(x_resh.sum(axis=0), 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected))


def test_fsdp_param_sharding():
    import jax.numpy as jnp

    from ray_tpu import parallel

    mesh = parallel.create_mesh({"data": 2, "fsdp": 4})
    params = {
        "big": jnp.zeros((1024, 256)),
        "small": jnp.zeros((4,)),
    }
    shardings = parallel.shard_params_fsdp(params, mesh)
    assert "fsdp" in str(shardings["big"].spec)
    assert shardings["small"].spec == ()


@pytest.mark.parametrize("shape,spec", [
    # GPT-2 XL's kernels: rows keep whole tiles (400 = 50 x 8), a quarter of
    # the columns does not (1200, 1600 and 400 are no multiple of 128)
    ((1600, 4800), ("fsdp", None)), ((1600, 6400), ("fsdp", None)),
    ((6400, 1600), ("fsdp", None)), ((1600, 1600), ("fsdp", None)),
    # 50257 rows do not divide: the columns, though 400 cuts a tile
    ((50257, 1600), (None, "fsdp")),
    # GPT-2 small: 192 rows a shard; where rows and columns both keep
    # whole tiles the first wins
    ((768, 2304), ("fsdp", None)), ((768, 3072), ("fsdp", None)),
    # rows cut a tile (4 x 6), columns do not (4 x 128)
    ((24, 4096), (None, "fsdp")),
    # nothing divides: replicated
    ((50257, 1601), ()),
])
def test_fsdp_splits_the_dimension_that_keeps_whole_tiles(shape, spec):
    import jax

    from ray_tpu import parallel

    mesh = parallel.create_mesh({"fsdp": 4}, devices=jax.devices()[:4])
    x = jax.ShapeDtypeStruct(shape, np.float32)
    assert tuple(parallel.shard_params_fsdp({"w": x}, mesh)["w"].spec) == spec


def test_create_mesh_raises_what_the_device_mesh_refuses(monkeypatch):
    """Over every device the layout is ``create_device_mesh``'s, and what
    it refuses is raised, not reshaped over; a partial device set is the
    caller's own choice and is reshaped in the order given."""
    import jax
    from jax.experimental import mesh_utils as jmu

    from ray_tpu import parallel

    def refuse(*args, **kwargs):
        raise NotImplementedError("no assignment of these axes")

    monkeypatch.setattr(jmu, "create_device_mesh", refuse)
    with pytest.raises(NotImplementedError, match="no assignment"):
        parallel.create_mesh({"data": 4, "fsdp": 2})
    some = jax.devices()[4:]
    mesh = parallel.create_mesh({"data": 2, "fsdp": 2}, devices=some)
    assert list(mesh.devices.flat) == some
