"""Collective communication API (analog of
python/paddle/distributed/communication/).

TPU-native semantics: a distributed tensor whose per-rank value has shape S
is a single jax array of shape (nranks, *S) sharded over the group's mesh
axis on dim 0 ("rank-major layout"). Each collective is ONE compiled
shard_map program whose body is the XLA collective (psum / all_gather /
ppermute / all_to_all) riding ICI — the ProcessGroupNCCL role
(reference collective/process_group.h:53, process_group_nccl.cc) collapses
into compiled programs; there is no stream/event management to do.

These same primitives are usable inside compiled train steps (they trace).
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from .env import get_mesh

def shard_map(f, mesh, in_specs, out_specs, check=True):
    # check=False: the static replication checker can't always prove
    # collectives' outputs replicated (e.g. all_gather); disable
    # per-program
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=bool(check))


P = jax.sharding.PartitionSpec


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communicator: a 1-D mesh over the member devices."""

    _next_id = 0

    def __init__(self, mesh=None, axis=None, ranks=None, devices=None):
        from jax.sharding import Mesh

        if mesh is not None:
            self.mesh = mesh
            self.axis = axis
        else:
            devices = devices if devices is not None else jax.devices()
            if ranks is not None:
                devices = [devices[r] for r in ranks]
            Group._next_id += 1
            self.axis = f"_g{Group._next_id}"
            self.mesh = Mesh(np.asarray(devices), (self.axis,))
        self.ranks = list(ranks) if ranks is not None else \
            list(range(self.mesh.devices.size))
        # rank = position of this process's first addressable device in the
        # group (0 in single-controller where every device is local;
        # meaningful under multi-process jax.distributed). Non-members get
        # -1, paddle's convention for "not in this group".
        local = {d.id for d in jax.local_devices()}
        self.rank = next(
            (i for i, d in enumerate(self.mesh.devices.reshape(-1))
             if getattr(d, "id", None) in local), -1)
        self.nranks = int(np.prod([self.mesh.shape[a] for a in
                                   ([self.axis] if self.axis else
                                    self.mesh.axis_names)]))

    @property
    def world_size(self):
        return self.nranks

    @property
    def process_group(self):
        return self

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(axis={self.axis}, nranks={self.nranks})"


_default_group: Optional[Group] = None


def _get_group(group) -> Group:
    global _default_group
    if group is not None:
        return group
    if _default_group is None:
        mesh = get_mesh()
        ax = mesh.axis_names[0]
        _default_group = Group(mesh=mesh, axis=ax) if len(mesh.axis_names) == 1 \
            else Group(devices=list(mesh.devices.flat))
    return _default_group


def new_group(ranks=None, backend=None, timeout=None) -> Group:
    """paddle.distributed.new_group analog."""
    return Group(ranks=ranks)


def get_group(gid=0):
    return _get_group(None)


def _as_rank_major(tensor, g: Group):
    """Validate/shard a rank-major (nranks, *S) array over the group axis."""
    from jax.sharding import NamedSharding

    v = tensor._data if isinstance(tensor, Tensor) else jnp.asarray(tensor)
    if v.shape[0] != g.nranks:
        raise ValueError(
            f"rank-major collective input needs leading dim == nranks "
            f"({g.nranks}); got shape {tuple(v.shape)}. Each index along dim 0 "
            f"is one rank's value.")
    return jax.device_put(v, NamedSharding(g.mesh, P(g.axis)))


@functools.lru_cache(maxsize=256)
def _collective_program(kind, axis, mesh, op="sum", src=0):
    def body_all_reduce(x):
        red = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin,
               }.get(op)
        if red is None:
            if op == "avg":
                return jax.lax.psum(x, axis) / jax.lax.psum(
                    jnp.ones((), x.dtype), axis)
            raise ValueError(f"unsupported reduce op {op}")
        return red(x, axis)

    def body_all_gather(x):
        return jax.lax.all_gather(x, axis)  # [nranks, *S] on every rank

    def body_broadcast(x):
        full = jax.lax.all_gather(x, axis)
        return full[src]

    def body_reduce_scatter(x):
        # x per rank: [nranks, *S]; out per rank: [*S]
        return jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=False)

    def body_all_to_all(x):
        # x per rank: [nranks, *S] -> swap rank/chunk dims
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=False)

    bodies = {"all_reduce": body_all_reduce, "all_gather": body_all_gather,
              "broadcast": body_broadcast, "reduce_scatter": body_reduce_scatter,
              "all_to_all": body_all_to_all}
    body = bodies[kind]

    if kind == "all_gather":
        # result is replicated: every rank holds the full [nranks, *S]
        def per_shard(x):
            return body(x[0])

        out_spec = P()
    else:
        # per-shard result re-stacks into the rank-major global [nranks, *S]
        def per_shard(x):
            return body(x[0])[None]

        out_spec = P(axis)
    fn = shard_map(per_shard, mesh, in_specs=(P(axis),), out_specs=out_spec,
                   check=kind != "all_gather")
    return jax.jit(fn)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """Rank-major all_reduce: every rank slot receives the reduction."""
    g = _get_group(group)
    v = _as_rank_major(tensor, g)
    out = _collective_program("all_reduce", g.axis, g.mesh, op=op)(v)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return Tensor(out)


def all_gather(tensor_list: List, tensor, group=None, sync_op=True):
    """Each rank's value gathered; returns/fills list of per-rank Tensors."""
    g = _get_group(group)
    v = _as_rank_major(tensor, g)
    full = _collective_program("all_gather", g.axis, g.mesh)(v)
    if tensor_list is not None:
        tensor_list.clear()
        tensor_list.extend(Tensor(full[i]) for i in range(g.nranks))
    return Tensor(full)


def all_gather_object(obj_list, obj, group=None):
    obj_list.clear()
    obj_list.append(obj)  # single-controller: all ranks share the process
    return obj_list


def broadcast(tensor, src=0, group=None, sync_op=True):
    g = _get_group(group)
    v = _as_rank_major(tensor, g)
    out = _collective_program("broadcast", g.axis, g.mesh, src=src)(v)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return Tensor(out)


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    g = _get_group(group)
    v = _as_rank_major(tensor, g)
    summed = _collective_program("all_reduce", g.axis, g.mesh, op=op)(v)
    # paddle reduce: only dst rank holds the result; others keep input
    idx = jnp.arange(g.nranks).reshape((-1,) + (1,) * (v.ndim - 1))
    out = jnp.where(idx == dst, summed, v)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return Tensor(out)


def reduce_scatter(tensor, tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """tensor_list: rank-major [nranks, nranks, *S] or list of per-rank
    stacks; out rank i gets sum_j in[j][i]."""
    g = _get_group(group)
    if isinstance(tensor_list, (list, tuple)):
        stacked = jnp.stack([t._data if isinstance(t, Tensor) else t
                             for t in tensor_list], axis=1)
    else:
        stacked = tensor_list._data if isinstance(tensor_list, Tensor) \
            else tensor_list
    v = _as_rank_major(Tensor(stacked), g)
    out = _collective_program("reduce_scatter", g.axis, g.mesh)(v)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return Tensor(out)


def alltoall(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    g = _get_group(group)
    if isinstance(in_tensor_list, (list, tuple)):
        stacked = jnp.stack([t._data if isinstance(t, Tensor) else t
                             for t in in_tensor_list], axis=0)
    else:
        stacked = in_tensor_list._data
    v = _as_rank_major(Tensor(stacked), g)
    out = _collective_program("all_to_all", g.axis, g.mesh)(v)
    if out_tensor_list is not None:
        out_tensor_list.clear()
        out_tensor_list.extend(Tensor(out[i]) for i in range(g.nranks))
    return Tensor(out)


all_to_all = alltoall


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """Single-controller scatter: the controller holds every rank's data, so
    `src` only needs validation (in the reference only rank `src` supplies
    tensor_list; here the one controller supplies it on src's behalf)."""
    g = _get_group(group)
    if not (0 <= src < g.nranks):
        raise ValueError(f"scatter: src={src} out of range for group of "
                         f"{g.nranks}")
    if tensor_list is None and g.nranks > 1:
        raise ValueError(
            "scatter: tensor_list is required in the single-controller "
            "model (the controller supplies src's data)")
    if tensor_list is not None:
        stacked = jnp.stack([t._data if isinstance(t, Tensor) else t
                             for t in tensor_list])
    else:
        stacked = tensor._data
    out = _as_rank_major(Tensor(stacked), g)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return Tensor(out)


def barrier(group=None):
    g = _get_group(group)
    v = jnp.ones((g.nranks,), jnp.int32)
    _collective_program("all_reduce", g.axis, g.mesh)(
        _as_rank_major(Tensor(v), g))


def send(tensor, dst=0, group=None, sync_op=True):
    """Host-level p2p: the payload is MOVED to rank `dst`'s device (a real
    ICI transfer on hardware, not a python-list hand-off). Single-controller
    pairing: send(dst=k) matches recv(src=k) FIFO per channel; in-trace p2p
    uses ppermute (the compiled ICI path, reference
    pp_utils/p2p_communication.py:298)."""
    g = _get_group(group)
    if not (0 <= dst < g.nranks):
        raise ValueError(f"send: dst={dst} out of range for group of "
                         f"{g.nranks}")
    data = tensor._data if isinstance(tensor, Tensor) else jnp.asarray(tensor)
    dst_dev = g.mesh.devices.reshape(-1)[dst]
    moved = jax.device_put(data, dst_dev)
    if not hasattr(g, "_p2p_buf"):
        g._p2p_buf = {}
    g._p2p_buf.setdefault(dst, []).append(moved)
    return tensor


def recv(tensor, src=0, group=None, sync_op=True):
    """Receives the oldest pending send on channel `src`; the value already
    resides on the destination device (moved by send)."""
    g = _get_group(group)
    chan = getattr(g, "_p2p_buf", {}).get(src)
    if not chan:
        raise RuntimeError(
            f"recv(src={src}): no pending send on channel {src} "
            "(single-controller pairing: send(dst=k) matches recv(src=k))")
    tensor._data = jnp.asarray(chan.pop(0), tensor._data.dtype)
    return tensor


def get_global_group():
    return _get_group(None)


def destroy_process_group(group=None):
    global _default_group
    if group is None:
        _default_group = None


# ---------------------------------------------------------------------------
# In-trace primitives: use inside shard_map'd / compiled code (TP/EP/SP).
# These are the building blocks the mp_ops/moe_utils of the reference
# implement as custom CUDA ops (_c_identity/_mp_allreduce/global_scatter…).
# ---------------------------------------------------------------------------
def psum(x, axis_name):
    v = x._data if isinstance(x, Tensor) else x
    return Tensor(jax.lax.psum(v, axis_name)) if isinstance(x, Tensor) \
        else jax.lax.psum(v, axis_name)


def pgather(x, axis_name, axis=0, tiled=True):
    v = x._data if isinstance(x, Tensor) else x
    out = jax.lax.all_gather(v, axis_name, axis=axis, tiled=tiled)
    return Tensor(out) if isinstance(x, Tensor) else out


def ppermute(x, axis_name, perm):
    v = x._data if isinstance(x, Tensor) else x
    out = jax.lax.ppermute(v, axis_name, perm)
    return Tensor(out) if isinstance(x, Tensor) else out


def pall_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    v = x._data if isinstance(x, Tensor) else x
    out = jax.lax.all_to_all(v, axis_name, split_axis=split_axis,
                             concat_axis=concat_axis, tiled=tiled)
    return Tensor(out) if isinstance(x, Tensor) else out


def axis_index(axis_name):
    return jax.lax.axis_index(axis_name)


class _CompletedTask:
    """Future for the async API — execution is XLA-async already, so the
    task is complete at return (reference ProcessGroup Task)."""

    def __init__(self, tensor=None):
        self._tensor = tensor

    def wait(self):
        if self._tensor is not None and hasattr(self._tensor, "_data"):
            self._tensor._data.block_until_ready()
        return True

    def is_completed(self):
        return True


def isend(tensor, dst=0, group=None):
    """Async send (reference communication/isend): XLA dispatch is already
    asynchronous, so this is send + a completed-task future."""
    send(tensor, dst=dst, group=group, sync_op=False)
    return _CompletedTask(tensor)


def irecv(tensor, src=0, group=None):
    recv(tensor, src=src, group=group, sync_op=False)
    return _CompletedTask(tensor)


def wait(tensor, group=None, use_calc_stream=True):
    """Block until the tensor's value is materialized (reference
    communication/wait over stream events; XLA equivalent is
    block_until_ready)."""
    if hasattr(tensor, "_data"):
        tensor._data.block_until_ready()
    return tensor


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    """Single-tensor all-to-all (reference communication/all_to_all.py
    alltoall_single). Rank-major convention: in_tensor is
    (nranks, nranks*k, *S) — rank s's rows split into nranks chunks of k;
    out[r] = concat over sources of their r-th chunk."""
    g = _get_group(group)
    inp = in_tensor._data if isinstance(in_tensor, Tensor) \
        else jnp.asarray(in_tensor)
    n = g.nranks
    if inp.shape[0] != n or inp.shape[1] % n:
        raise ValueError(
            f"alltoall_single expects rank-major (nranks, nranks*k, ...); "
            f"got {tuple(inp.shape)} for nranks={n}")
    k = inp.shape[1] // n
    in_list = [Tensor(inp[s].reshape((n, k) + inp.shape[2:]))
               for s in range(n)]
    out_list: list = []
    alltoall(out_list, in_list, group=group)
    vals = jnp.stack([o._data for o in out_list], axis=0) \
        .reshape((n, n * k) + inp.shape[2:])
    if out_tensor is not None and hasattr(out_tensor, "_data"):
        out_tensor._data = vals.astype(out_tensor._data.dtype)
        return out_tensor
    return Tensor(vals)


def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """Gather to rank dst (reference communication/gather): built on
    all_gather; single-controller: the provided list receives the
    per-rank values."""
    outs: list = []
    full = all_gather(outs, tensor, group=group)
    if gather_list is not None:
        gather_list.clear()
        gather_list.extend(outs)
        return gather_list
    return full


def _pickle_to_tensor(obj):
    import pickle

    raw = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
    return Tensor(jnp.asarray(raw)), raw.size


def _tensor_to_obj(t, size):
    import pickle

    return pickle.loads(bytes(np.asarray(t._data[:size], np.uint8)))


def broadcast_object_list(object_list, src=0, group=None):
    """Broadcast picklable objects (reference
    communication/broadcast_object_list): pickle -> rank-major uint8
    tensor -> broadcast -> unpickle the (now shared) src row."""
    g = _get_group(group)
    for i, obj in enumerate(object_list):
        t, size = _pickle_to_tensor(obj)
        rm = Tensor(jnp.tile(t._data[None], (g.nranks, 1)))
        out = broadcast(rm, src=src, group=group)
        object_list[i] = _tensor_to_obj(Tensor(out._data[src]), size)
    return object_list


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    """Scatter picklable objects (reference scatter_object_list)."""
    g = _get_group(group)
    objs = in_object_list or []
    if len(objs) != g.nranks:
        raise ValueError(
            f"in_object_list must have {g.nranks} entries")
    # single-controller: rank r's slot is objs[r] after the exchange
    out_object_list.clear()
    out_object_list.append(objs[g.rank if g.rank >= 0 else 0])
    return out_object_list


def get_backend(group=None):
    """The data-plane backend name: XLA collectives over ICI/DCN
    (reference returns NCCL/GLOO/...)."""
    return "XLA"


def is_available():
    """Distributed is always available — the mesh backend is part of the
    runtime (reference checks compile flags)."""
    return True


def gloo_init_parallel_env(rank_id, rank_num, server_endpoint):
    """Host-side (control-plane) parallel env over TCPStore — the gloo
    role (reference gloo_init_parallel_env)."""
    from .store import TCPStore

    host, port = server_endpoint.rsplit(":", 1)
    return TCPStore(host, int(port), is_master=(rank_id == 0),
                    world_size=rank_num)


def gloo_barrier():
    barrier()


def gloo_release():
    """Host control-plane teardown (store sockets close with the store)."""
