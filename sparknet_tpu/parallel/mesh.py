"""Device mesh construction + multi-host initialization.

Replaces the reference's entire cluster control plane (Spark driver +
executors + `spark-submit`, reference `apps/CifarApp.scala:31-49`,
`ec2/spark_ec2.py`) with the JAX single-controller model: every host runs the
same program, `jax.distributed.initialize` forms the global runtime, and a
`jax.sharding.Mesh` over all devices is the communication fabric — collectives
ride ICI (and DCN across slices) instead of driver TCP.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax import shard_map
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def shard_map_unchecked(f, **kw):
    """`shard_map` with replication (vma) checking OFF — required whenever
    the body may trace a `pallas_call`, which has no shard_map replication
    rule."""
    return shard_map(f, check_vma=False, **kw)


def make_mesh(n_devices: Optional[int] = None,
              axis_names: Sequence[str] = (DATA_AXIS,),
              shape: Optional[Tuple[int, ...]] = None) -> Mesh:
    """Build a mesh over the first `n_devices` devices (default: all).

    1-D (data,) meshes cover the reference's pure-DP world; pass
    axis_names=("data","model") + shape for DP×TP hybrid layouts.
    """
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    if shape is None:
        shape = (len(devices),) if len(axis_names) == 1 else None
        assert shape is not None, "multi-axis mesh needs an explicit shape"
    arr = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(arr, axis_names=tuple(axis_names))


_COORDINATOR_ENV_HINTS = ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS",
                          "MEGASCALE_COORDINATOR_ADDRESS")


def _multihost_configured() -> bool:
    """True only when the environment describes a >1-host world: an explicit
    coordinator address, or a TPU hostname list with MULTIPLE entries
    (single-host TPU VMs set TPU_WORKER_HOSTNAMES=localhost — that is a
    1-host world and must not trigger distributed init)."""
    if any(os.environ.get(k) for k in _COORDINATOR_ENV_HINTS):
        return True
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hosts.split(",") if h.strip()]) > 1


def initialize_multihost(coordinator: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> bool:
    """Form the multi-host runtime. Must be called BEFORE any other JAX use
    (backend init pins the process world — do not touch jax.devices() or
    jax.process_count() first).

    Returns True if a multi-host world was formed, False for a deliberate
    single-process run (no coordinator configured). Real initialization
    failures PROPAGATE — a pod run silently degrading to per-host training
    would be wrong results with no error.
    """
    if os.environ.get("SPARKNET_TPU_DIST_INIT"):
        return True
    explicit = coordinator is not None
    configured = explicit or _multihost_configured()
    if not configured:
        return False  # single-process (tests, single TPU VM)
    kwargs = {}
    if explicit:
        kwargs = dict(coordinator_address=coordinator,
                      num_processes=num_processes, process_id=process_id)
    jax.distributed.initialize(**kwargs)
    os.environ["SPARKNET_TPU_DIST_INIT"] = "1"
    return True


def host_id_count() -> Tuple[int, int]:
    """(process_index, process_count): the host-sharding key. The reference's
    analogue was the Spark partition id per executor; here every host runs
    the same program and takes its slice by process index."""
    return jax.process_index(), jax.process_count()


def scan_unroll(length: int) -> int:
    """`unroll=` for a τ/worker scan whose body contains convolutions.

    XLA:CPU executes convolution ops inside a while-loop body on a
    pathologically slow path — measured 26x (r5): a 3-step cifar10_quick
    train scan runs 24.8 s rolled vs 0.95 s fully unrolled on one core,
    while the identical body as a bare jitted step takes 0.51 s. On the
    CPU backend (the virtual-mesh test/CI configuration) fully unroll;
    on TPU the rolled scan compiles faster and runs at the same speed,
    so keep it (partial unrolls don't help: any residual while-loop puts
    every conv back on the slow path)."""
    return length if jax.default_backend() == "cpu" else 1


def local_device_rows(mesh: Mesh) -> list:
    """Positions along the flattened mesh device axis owned by THIS process
    (not assumed contiguous — TPU mesh construction may reorder devices for
    ICI topology)."""
    pi = jax.process_index()
    return [i for i, d in enumerate(mesh.devices.flat)
            if d.process_index == pi]


def put_device_axis(arr, mesh: Mesh, spec: P):
    """Place a host array onto the mesh with `spec`.

    Single-process: plain device_put. Multi-host: `arr` is this process's
    LOCAL slice along the sharded axis and the global array is assembled via
    jax.make_array_from_process_local_data — each host contributes only the
    rows its devices own (disjoint host data, the multi-host data path)."""
    sh = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(arr, sh)
    return jax.make_array_from_process_local_data(sh, np.asarray(arr))


def place_global_state(tree, mesh: Mesh, spec: P):
    """Place a pytree whose leaves carry a leading GLOBAL device axis (shape
    [n_global_devices, ...], identical on every host — e.g. a freshly tiled
    or checkpoint-restored TrainState). Multi-host: each host slices out its
    own devices' rows and contributes only those."""
    if jax.process_count() == 1:
        return jax.device_put(tree, NamedSharding(mesh, spec))
    rows = local_device_rows(mesh)

    def put(x):
        return put_device_axis(np.asarray(x)[rows], mesh, spec)

    return jax.tree.map(put, tree)


def fetch_global(tree):
    """Materialize (possibly multi-host-sharded) arrays as host numpy on
    EVERY process — the collective the checkpoint writer needs (momentum is
    worker-local state, so this is a real allgather, not a replica read).

    Since r8 this is the MONOLITHIC FALLBACK: the default checkpoint
    path is `fetch_state_shards` below, which never materializes the
    full state on any host — each worker fetches only the distinct
    pieces its own devices hold and writes its own shard file. This
    full gather remains for the graph backend, single-device runs, and
    `checkpoint_sharded="off"`.

    Single-process, the device->host copies for ALL leaves are started
    asynchronously FIRST (`copy_to_host_async`), then materialized: the
    transfers overlap each other (and whatever the device is still
    computing) instead of serializing one blocking `np.asarray` per leaf —
    the checkpoint stage-1 fetch is the main beneficiary (BENCH_r07
    non-blocking-collect arm)."""
    if jax.process_count() == 1:
        for leaf in jax.tree.leaves(tree):
            if isinstance(leaf, jax.Array):
                try:
                    leaf.copy_to_host_async()
                except Exception:
                    pass  # fetch still correct via the blocking asarray
        return jax.tree.map(np.asarray, tree)
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(tree, tiled=True)


def fetch_state_shards(tree, mesh: Mesh, own_data: bool = True) -> dict:
    """Stage 1 of a SHARDED checkpoint save — the gather-free replacement
    for `fetch_global`: instead of allgathering the full state to every
    host, fetch only the DISTINCT pieces of each leaf (one representative
    per replica group, owner = the lowest-ranked device holding it in
    mesh order) and tag each with the shard FILE it belongs to (file id =
    owning device's rank). Fully-replicated leaves are chunked along
    their leading dim across the files so no byte is written twice and
    the files stay balanced — total bytes across shard files equal the
    monolithic layout's exactly.

    Device→host copies for every piece are started asynchronously first
    (`copy_to_host_async`, the r7 stage-1 overlap), then materialized.
    `own_data=True` (the default) deep-copies any leaf whose host view
    still aliases a device buffer — the async stage-2 writer overlaps
    later rounds, and the round's donation may reuse that buffer (same
    OWNDATA rule as the monolithic writer path).

    Multi-host: each process materializes only the pieces its own devices
    own (`pieces` carry arr=None for foreign ones — `checkpoint.
    save_sharded` writes my files, process 0 commits the manifest), so
    per-host stage-1 bytes are O(state/n_processes) for sharded leaves.
    Returns the snapshot dict `checkpoint.save_sharded` consumes:
    {"n_shards", "owners": {file: process}, "process_index",
    "process_count", "leaves": {key: {"shape", "dtype", "pieces":
    [(file_id, offsets, shape, arr|None), ...]}}}."""
    from ..utils.checkpoint import _path_str  # no cycle: checkpoint is leaf

    devices = list(mesh.devices.flat)
    n = len(devices)
    rank = {d: i for i, d in enumerate(devices)}
    my_pi = jax.process_index()
    owners = {i: int(d.process_index) for i, d in enumerate(devices)}

    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat["/".join(_path_str(p) for p in path)] = leaf

    def owned(a: np.ndarray) -> np.ndarray:
        if own_data and not a.flags["OWNDATA"]:
            return np.array(a)
        return a

    # pass 1: plan every leaf's pieces + start the async D2H copies
    plans = {}
    for key, leaf in flat.items():
        if isinstance(leaf, jax.Array) and not isinstance(leaf, np.ndarray):
            local = {s.device: s for s in leaf.addressable_shards}
            idx_map = leaf.sharding.devices_indices_map(leaf.shape)
            groups: dict = {}  # normalized index -> owner device
            for d, idx in idx_map.items():
                if d not in rank:
                    continue  # a sharding over a sub-mesh never happens,
                    # but never mis-file a foreign device's piece
                norm = tuple(
                    (int(s.start or 0),
                     int(s.stop if s.stop is not None else dim))
                    for s, dim in zip(idx, leaf.shape))
                cur = groups.get(norm)
                if cur is None or rank[d] < rank[cur]:
                    groups[norm] = d
            replicated = (len(groups) == 1 and all(
                lo == 0 and hi == dim for (lo, hi), dim in
                zip(next(iter(groups)), leaf.shape)))
            if replicated:
                src = local.get(next(iter(groups.values())),
                                next(iter(local.values()), None))
                if src is not None:
                    try:
                        src.data.copy_to_host_async()
                    except Exception:
                        pass
                plans[key] = ("replicated", leaf, src)
            else:
                mine = []
                for norm, d in sorted(groups.items(),
                                      key=lambda kv: rank[kv[1]]):
                    sh = local.get(d)
                    if sh is not None:
                        try:
                            sh.data.copy_to_host_async()
                        except Exception:
                            pass
                    mine.append((norm, d, sh))
                plans[key] = ("sharded", leaf, mine)
        else:
            plans[key] = ("replicated", np.asarray(leaf), None)

    # pass 2: materialize + assemble the piece lists
    leaves = {}
    for key, plan in plans.items():
        kind, leaf, info = plan
        shape, dtype = tuple(leaf.shape), np.dtype(leaf.dtype)
        pieces = []
        if kind == "sharded":
            for norm, d, sh in info:
                offsets = tuple(lo for lo, _ in norm)
                pshape = tuple(hi - lo for lo, hi in norm)
                arr = (owned(np.asarray(sh.data))
                       if sh is not None and owners[rank[d]] == my_pi
                       else None)
                pieces.append((rank[d], offsets, pshape, arr))
        else:
            full = None
            if info is not None:  # jax leaf: one local replica
                full = owned(np.asarray(info.data))
            elif isinstance(leaf, np.ndarray):
                full = leaf
            if shape == () or (shape and shape[0] == 0) or n == 1:
                arr = full if owners[0] == my_pi else None
                pieces.append((0, (0,) * len(shape), shape, arr))
            else:
                # chunk the replicated value across the shard files:
                # contiguous leading-dim blocks, sizes differing by <= 1
                lo = 0
                for j, chunk in enumerate(
                        np.array_split(np.arange(shape[0]),
                                       min(n, shape[0]))):
                    size = len(chunk)
                    if not size:
                        continue
                    arr = (full[lo:lo + size]
                           if full is not None and owners[j] == my_pi
                           else None)
                    pieces.append((j, (lo,) + (0,) * (len(shape) - 1),
                                   (size,) + shape[1:], arr))
                    lo += size
        leaves[key] = {"shape": shape, "dtype": dtype, "pieces": pieces}
    return {"n_shards": n, "owners": owners,
            "process_index": int(my_pi),
            "process_count": int(jax.process_count()),
            "leaves": leaves}


def per_device_state_bytes(state) -> dict:
    """At-rest bytes ONE device holds for this TrainState's params and
    momentum — the HBM ledger the ZeRO state_sharding modes exist to
    shrink (`sharding.shard_shape` is the allocator's view, exact on any
    backend). One definition shared by the BENCH_r07 acceptance ledger
    (bench.py --sharding) and the tier-1 byte pin (tests/test_sharded.py)
    so the two cannot drift."""
    out = {}
    for name, tree in (("params", state.params),
                       ("momentum", state.momentum)):
        out[name] = sum(
            int(np.prod(leaf.sharding.shard_shape(leaf.shape)))
            * leaf.dtype.itemsize for leaf in jax.tree.leaves(tree))
    return out


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharded(mesh: Mesh, ndim: int, axis: int = 0) -> NamedSharding:
    spec = [None] * ndim
    spec[axis] = DATA_AXIS
    return NamedSharding(mesh, P(*spec))
