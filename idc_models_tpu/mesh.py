"""Device-mesh construction and axis bookkeeping.

This is the foundation the rest of the framework compiles against — the
TPU-native replacement for the reference's `tf.distribute` strategy objects
(`MirroredStrategy` at dist_model_tf_vgg.py:115, device lists at
dist_model_tf_dense.py:16-24). Instead of a strategy that owns the step,
we build a `jax.sharding.Mesh` and express placement with `PartitionSpec`s;
XLA inserts the ICI/DCN collectives.

Axis conventions used throughout the framework:

- ``"data"``    batch / data-parallel axis (reference D1)
- ``"model"``   tensor-parallel axis — channel-wise weight sharding via
  GSPMD (tp.py, CLI --model-parallel); beyond reference parity
- ``"client"``  federated-client axis — one client per device (reference D3)
- ``"seq"``     sequence-parallel axis — long-context ring attention
  (ring_attention.py); beyond reference parity
"""

from __future__ import annotations

import contextlib
import os
from collections.abc import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
CLIENT_AXIS = "client"
SEQ_AXIS = "seq"


def force_host_devices(n: int) -> None:
    """Ask XLA to expose `n` virtual CPU devices (must run before jax init).

    Test-time stand-in for a TPU pod, mirroring how the reference's federated
    code simulates clients inside one process (fed_model.py:184).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    kept = [f for f in flags.split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    kept.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(kept)


def force_cpu_pod(n: int) -> None:
    """Force this process onto `n` virtual CPU devices.

    Must run before the first device query (backend creation). jax reads
    JAX_PLATFORMS once, at import, so a process that already imported jax
    (this module does) cannot change platform through the environment
    alone: the platform is also flipped through jax.config. The variable
    is still set for child processes; the XLA_FLAGS below are honored
    because the CPU backend is only created on first use.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    force_host_devices(n)
    jax.config.update("jax_platforms", "cpu")
    # Initialize the backend now and confirm the pod actually materialized:
    # if a backend was already live, the platform flip above was silently
    # ignored and callers would otherwise run on whatever was there.
    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < n:
        import warnings

        warnings.warn(
            f"force_cpu_pod({n}) ineffective: a jax backend was already "
            f"initialized ({len(devs)} {devs[0].platform} device(s)); "
            f"call it before any jax use", stacklevel=2)


def pallas_interpret(mesh: Mesh | None = None) -> bool:
    """THE platform rule for every Pallas call site: interpret if and
    only if the devices the call runs on are not TPU. The devices are
    the mesh's when the caller has one (a CPU-device mesh in a
    TPU-backed process must interpret, not lower Mosaic for CPU), else
    the process's default devices. On TPU nothing interprets and
    nothing falls back to a jnp reference — a kernel Mosaic refuses is
    an error."""
    devices = mesh.devices.flat if mesh is not None else jax.devices()
    return devices[0].platform != "tpu"


def make_mesh(
    axes: dict[str, int] | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a named device mesh.

    ``axes`` maps axis name -> size; one size may be ``-1`` meaning "all
    remaining devices". Default is a 1-D data-parallel mesh over every
    visible device — the analogue of `MirroredStrategy()` enumerating GPUs.
    """
    devices = list(devices) if devices is not None else list(jax.devices())
    if axes is None:
        axes = {DATA_AXIS: len(devices)}
    names = list(axes.keys())
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise ValueError("at most one mesh axis may be -1")
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        if len(devices) % known:
            raise ValueError(
                f"{len(devices)} devices not divisible by fixed axes {axes}"
            )
        sizes[sizes.index(-1)] = len(devices) // known
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} devices, "
                         f"have {len(devices)}")
    grid = np.asarray(devices[:total], dtype=object).reshape(sizes)
    return Mesh(grid, axis_names=tuple(names))


def data_mesh(n: int | None = None) -> Mesh:
    """1-D data-parallel mesh (axis "data") over n (default: all) devices."""
    devs = jax.devices()
    if n is not None:
        devs = devs[:n]
    return make_mesh({DATA_AXIS: len(devs)}, devices=devs)


def client_mesh(n_clients: int | None = None) -> Mesh:
    """1-D federated mesh (axis "client"), one client per device."""
    devs = jax.devices()
    if n_clients is not None:
        devs = devs[:n_clients]
    return make_mesh({CLIENT_AXIS: len(devs)}, devices=devs)


def seq_mesh(n: int | None = None) -> Mesh:
    """1-D sequence-parallel mesh (axis "seq") over n (default: all)
    devices — the ring for `ring_attention` over context-sharded
    sequences."""
    devs = jax.devices()
    if n is not None:
        devs = devs[:n]
    return make_mesh({SEQ_AXIS: len(devs)}, devices=devs)


def data_seq_mesh(n_seq: int, n_data: int | None = None) -> Mesh:
    """2-D ("data", "seq") mesh: batch shards over "data", the sequence
    (ring-attention) axis over "seq". With n_data omitted, every
    remaining device joins the data axis — n_seq must then be a
    positive divisor of the device count (silently idling leftover
    devices would skew any throughput measurement; pass n_data
    explicitly to use a subset on purpose). Lay the seq axis innermost
    so ring hops ride ICI neighbors."""
    devs = jax.devices()
    if n_data is None:
        if n_seq < 1 or len(devs) % n_seq:
            raise ValueError(
                f"n_seq {n_seq} must be a positive divisor of the "
                f"device count ({len(devs)}); pass n_data explicitly "
                f"to deliberately use a device subset")
        n_data = len(devs) // n_seq
    return make_mesh({DATA_AXIS: n_data, SEQ_AXIS: n_seq},
                     devices=devs[:n_data * n_seq])


def largest_dividing_mesh(n_clients: int, n_devices: int | None = None) -> int:
    """The largest device count <= n_devices that divides n_clients —
    the mesh size for k-clients-per-device programs whose aggregation
    cannot absorb weight-0 padding (the unweighted secure mean)."""
    if n_devices is None:
        n_devices = len(jax.devices())
    return max(d for d in range(1, min(n_clients, n_devices) + 1)
               if n_clients % d == 0)


def sharding(mesh: Mesh, *spec) -> NamedSharding:
    """NamedSharding for `spec` over `mesh` (e.g. sharding(mesh, "data"))."""
    return NamedSharding(mesh, P(*spec))


def batch_seq_spec(mesh: Mesh, axis: str = SEQ_AXIS,
                   trailing: int = 2) -> P:
    """THE sequence-parallel activation layout, defined once: batch over
    every non-`axis` mesh axis, the sequence dimension over `axis`,
    `trailing` unsharded dims after it. Shared by the ring op's
    shard_map specs ([B,T,H,D]: trailing=2), the attention model's
    residual-stream pin ([B,T,E]: trailing=1), and the decode cache
    sharding (trailing=0: either stored form, the rest whole) — one
    definition so the three surfaces cannot diverge.

    The "model" axis is excluded from the batch group: it is reserved
    for WEIGHT sharding (tp.py, partition.py rules), so activations
    and KV caches stay unsharded over it — params and KV shard
    independently on a ("data", "model", "seq") mesh."""
    bo = batch_axes(mesh, axis)
    return P(bo, axis, *([None] * trailing))


def batch_axes(mesh: Mesh, axis: str = SEQ_AXIS):
    """The axis group a leading batch dimension shards over on a
    sequence-parallel mesh: every axis except the ring `axis` and the
    weight-reserved "model" axis — None when no such axis exists. The
    one definition `batch_seq_spec` and the ring folds' shard_map
    specs share, so activations/KV and weights cannot end up fighting
    over "model"."""
    others = tuple(a for a in mesh.axis_names
                   if a not in (axis, MODEL_AXIS))
    return others if others else None


def batch_seq_sharding(mesh: Mesh, axis: str = SEQ_AXIS,
                       trailing: int = 2) -> NamedSharding:
    """`batch_seq_spec` as a NamedSharding — the one construction site
    for the [B, T, ...] activation/cache layout (the ring model's
    residual pin, ring_decode's cache layout, the serve engine's
    canonical cache spelling all call this)."""
    return NamedSharding(mesh, batch_seq_spec(mesh, axis, trailing))


def fsdp_tp_mesh(fsdp: int = 1, tp: int = 1, seq: int = 1) -> Mesh:
    """3-D ("data", "model", "seq") mesh for sharded LM configs: FSDP
    shards params + optimizer state over "data" (the batch axis — the
    gradient allreduce becomes reduce-scatter/all-gather), tensor
    parallelism shards them over "model" (partition.py rules), and
    "seq" carries the ring. Size-1 axes are kept in the mesh — the
    partition rules drop them at adaptation time, so one rule set
    serves every (fsdp, tp, seq) combination.

    Uses exactly fsdp*tp*seq devices — the degrees are the caller's
    EXPLICIT request (no -1/absorb axis), so leftover devices idle by
    design. Don't compare wall-clock against an all-devices
    `data_seq_mesh` run: the device counts differ; the sharded-config
    comparisons this mesh exists for are per-device CAPACITY
    (peak_hbm_bytes) and same-mesh step time."""
    for name, v in (("fsdp", fsdp), ("tp", tp), ("seq", seq)):
        if v < 1:
            raise ValueError(f"{name} degree must be >= 1, got {v}")
    n = len(jax.devices())
    if fsdp * tp * seq > n:
        raise ValueError(
            f"mesh fsdp={fsdp} x tp={tp} x seq={seq} needs "
            f"{fsdp * tp * seq} devices, have {n}")
    return make_mesh({DATA_AXIS: fsdp, MODEL_AXIS: tp, SEQ_AXIS: seq})


def batch_axis(mesh: Mesh, axis: str | None = None) -> str:
    """The axis a leading batch dimension shards over: `axis` if given,
    else "data" when present, else the mesh's only axis (so eval and
    prefetch work on a "client" mesh too)."""
    if axis is not None:
        return axis
    if DATA_AXIS in mesh.axis_names:
        return DATA_AXIS
    if len(mesh.axis_names) == 1:
        return mesh.axis_names[0]
    raise ValueError(f"cannot infer batch axis from mesh axes "
                     f"{mesh.axis_names}; pass axis=...")


def put_with_sharding(a, sh: NamedSharding, *, may_alias: bool | None = None):
    """Host array -> device(s) under `sh`, multi-process safe.

    `jax.device_put` onto a sharding that spans other processes' devices
    runs a cross-process value-equality collective (and requires every
    process to hold the full array); production multi-host wants each
    host to feed only its local shards anyway. `make_array_from_callback`
    does exactly that: this process materializes only the index slices
    belonging to its addressable devices.

    `may_alias=False` is for a caller that will write to `a` again (a
    recycled staging buffer): the placed array never shares its memory.
    An accelerator copies anyway, and needs `a` unchanged only until the
    array is ready.
    """
    if isinstance(a, jax.Array) and a.sharding == sh:
        return a  # already placed — don't round-trip through host
    if sh.is_fully_addressable:
        if may_alias is False and sh.mesh.devices.flat[0].platform == "cpu":
            # jax 0.9.0 drops `may_alias` for a numpy argument, and its
            # CPU client keeps a 64-byte-aligned host buffer as the
            # array's own memory: there the copy has to be made here
            a = np.array(a)
        return jax.device_put(a, sh, may_alias=may_alias)
    arr = np.asarray(a)
    return jax.make_array_from_callback(arr.shape, sh,
                                        lambda idx: arr[idx])


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Context manager installing `mesh` as the ambient mesh."""
    with mesh:
        yield mesh


def local_device_count() -> int:
    return jax.local_device_count()


def process_index() -> int:
    return jax.process_index()


def initialize_multihost(coordinator: str | None = None,
                         num_processes: int | None = None,
                         process_id: int | None = None) -> None:
    """Initialize `jax.distributed` for multi-host (DCN) pods.

    Replaces the reference's implicit single-process assumption: the
    reference never runs multi-node (SURVEY.md §4); here multi-host is
    first-class — after this call, `jax.devices()` spans the pod and every
    mesh built above rides ICI within a host and DCN across hosts.
    No-ops when running single-process (e.g. tests, a single-chip run).
    """
    if num_processes is None and coordinator is None:
        return  # single-process
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
