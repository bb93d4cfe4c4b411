"""Device mesh management and sharding helpers.

The SparkContext analog (reference: workflow/WorkflowContext.scala:25-45).
A `MeshContext` owns a `jax.sharding.Mesh` with two named axes:

  - ``data``  — batch-dimension parallelism (rows of users/items/events);
                the analog of Spark's RDD partitioning.
  - ``model`` — parameter sharding (embedding-table rows, hidden dims);
                no Spark analog (MLlib block ALS plays this role).

Kernels request shardings by logical spec; XLA/GSPMD inserts the ICI/DCN
collectives. Multi-host initialization goes through `jax.distributed` —
`init_distributed` is the `spark-submit --master` analog.
"""

from __future__ import annotations

import contextlib
import logging
import math
import os
import threading
from typing import Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

_local = threading.local()


class DeviceUnavailable(RuntimeError):
    """The process resolved a backend other than a TPU without having
    been told to run on the CPU."""


_platform_lock = threading.Lock()
_platform: Optional[dict] = None


def _jax():
    import jax
    from predictionio_tpu.compile.cache import enable_persistent_cache
    enable_persistent_cache()
    return jax


def device_platform() -> dict:
    """The platform this process computes on, resolved ONCE — the single
    place every device-using entry point (``pio train`` / ``deploy`` /
    ``update`` / ``eval`` / ``run``, ``EngineServer``, ``ServingHost``)
    initializes the JAX backend. Returns ``{"platform", "device_kind",
    "n"}`` as JAX reports them.

    JAX registers its TPU backend ``fail_quietly``: a process that
    cannot get the chip (absent, or held by another process) continues
    on the CPU with one INFO line. A trainer or server doing that looks
    alive and is useless, so anything other than a TPU is an error here
    that names what JAX reported. The one exception is an explicit
    ``JAX_PLATFORMS=cpu`` (tests, the ``*_smoke.sh`` scripts,
    ``chip_smoke.py --tiny``)."""
    global _platform
    with _platform_lock:
        if _platform is not None:
            return _platform
        jax = _jax()
        requested = (jax.config.jax_platforms or "").strip().lower()
        hint = (" A chip belongs to one process at a time — check for "
                "a live `pio deploy` or trainer holding it. Set "
                "JAX_PLATFORMS=cpu only to run on the CPU on purpose.")
        try:
            devices = jax.devices()
        except RuntimeError as e:
            # JAX_PLATFORMS names the TPU and it could not be had
            raise DeviceUnavailable(
                f"no TPU with JAX_PLATFORMS={requested}: {e}.{hint}"
            ) from e
        info = {"platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "n": len(devices)}
        if info["platform"] != "tpu" and requested != "cpu":
            # JAX_PLATFORMS unset: the TPU backend failed quietly
            from jax._src import xla_bridge
            why = xla_bridge._backend_errors.get("tpu")
            raise DeviceUnavailable(
                f"no TPU: JAX resolved platform {info['platform']!r} "
                f"({info['n']} x {info['device_kind']}) with "
                f"JAX_PLATFORMS={requested or '<unset>'}"
                + (f"; TPU backend init reported: {why}" if why else "")
                + "." + hint)
        _platform = info
        logger.info("device platform=%s device_kind=%s n=%d",
                    info["platform"], info["device_kind"], info["n"])
        return info


def device_stats() -> dict:
    """The ``/stats.json`` rendering of :func:`device_platform` plus the
    pid that owns the device — what ``pio status``, ``pio update`` and
    ``chip_smoke.py`` read to tell who holds the chip."""
    device = device_platform()
    return {"pid": os.getpid(), "platform": device["platform"],
            "deviceKind": device["device_kind"],
            "deviceCount": device["n"]}


def host_only() -> None:
    """Pin THIS process to the CPU backend before any device use: the
    event server, dashboard, admin server, ``pio status`` and the
    storage/app verbs own no device and must never create a TPU client
    (one would take the chip from the trainer or server that needs
    it)."""
    import jax
    jax.config.update("jax_platforms", "cpu")


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host bring-up (jax.distributed.initialize). No-op when
    single-process. Driven by PIO_COORDINATOR/PIO_NUM_PROCESSES/PIO_PROCESS_ID
    or explicit args — the env-passthrough analog of Runner.scala:105-108."""
    jax = _jax()
    coordinator = coordinator or os.environ.get("PIO_COORDINATOR")
    if coordinator is None:
        return
    num_processes = num_processes or int(os.environ["PIO_NUM_PROCESSES"])
    process_id = process_id or int(os.environ["PIO_PROCESS_ID"])
    jax.distributed.initialize(coordinator, num_processes, process_id)
    logger.info("jax.distributed initialized: process %d/%d via %s",
                process_id, num_processes, coordinator)


class MeshContext:
    """A named-axis device mesh plus sharding constructors."""

    DATA_AXIS = "data"
    MODEL_AXIS = "model"

    def __init__(self, mesh):
        self.mesh = mesh

    # -- constructors -------------------------------------------------------
    @staticmethod
    def create(devices=None, model_parallelism: int = 1) -> "MeshContext":
        jax = _jax()
        if devices is None:
            # the ambient mesh is where library callers (pio-shell, the
            # examples) first touch a device: same rule as the verbs
            device_platform()
        devices = list(devices if devices is not None else jax.devices())
        n = len(devices)
        if n % model_parallelism != 0:
            raise ValueError(
                f"model_parallelism {model_parallelism} does not divide "
                f"device count {n}")
        dp = n // model_parallelism
        arr = np.array(devices).reshape(dp, model_parallelism)
        mesh = jax.sharding.Mesh(
            arr, (MeshContext.DATA_AXIS, MeshContext.MODEL_AXIS))
        return MeshContext(mesh)

    # -- properties ---------------------------------------------------------
    @property
    def n_devices(self) -> int:
        return int(math.prod(self.mesh.devices.shape))

    @property
    def data_parallelism(self) -> int:
        return self.mesh.shape[self.DATA_AXIS]

    @property
    def model_parallelism(self) -> int:
        return self.mesh.shape[self.MODEL_AXIS]

    # -- sharding constructors ---------------------------------------------
    def sharding(self, *axis_per_dim) -> "object":
        """NamedSharding with the given mesh axis (or None) per array dim."""
        jax = _jax()
        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(*axis_per_dim))

    def replicated(self):
        jax = _jax()
        return jax.sharding.NamedSharding(self.mesh,
                                          jax.sharding.PartitionSpec())

    def batch_sharded(self, ndim: int = 1):
        """First dim sharded over the data axis, rest replicated."""
        return self.sharding(self.DATA_AXIS, *([None] * (ndim - 1)))

    def model_sharded(self, ndim: int = 1):
        """First dim sharded over the model axis (embedding-table rows)."""
        return self.sharding(self.MODEL_AXIS, *([None] * (ndim - 1)))

    # -- data movement ------------------------------------------------------
    def put(self, x, sharding):
        """Host array -> device array with the given sharding. Single
        process uses device_put; multi-process goes through
        make_array_from_callback, where each process materializes only its
        addressable shards — device_put's cross-process assert_equal
        collective both costs an allgather of the full array and (observed
        on the gloo CPU backend) false-positives on identical inputs."""
        jax = _jax()
        if jax.process_count() == 1:
            return jax.device_put(x, sharding)
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, sharding,
                                            lambda idx: x[idx])

    def put_batch(self, x):
        """Host array -> device array sharded on dim 0 over the data axis.
        dim 0 must be divisible by data_parallelism (use pad_to_multiple)."""
        return self.put(x, self.batch_sharded(np.ndim(x)))

    def put_replicated(self, x):
        return self.put(x, self.replicated())

    def put_stacked(self, x, axes=None):
        """Host array -> device array sharded on dim 1 over the data axis
        (or over `axes`, a name or a tuple of names: ops/als.plan_axes
        adds the model axis where the tables are row-sharded): the layout
        of stacked same-shape batch groups [N, B, ...] that a `lax.scan`
        consumes along dim 0, each slice staying sharded."""
        ndim = np.ndim(x)
        return self.put(
            x, self.sharding(None, axes or self.DATA_AXIS,
                             *([None] * (ndim - 2))))

    def put_model_sharded(self, x):
        """Rows sharded over the model axis (embedding tables)."""
        return self.put(x, self.model_sharded(np.ndim(x)))

    def pad_to_multiple(self, x: np.ndarray, axis: int = 0,
                        multiple: Optional[int] = None,
                        fill=0) -> Tuple[np.ndarray, int]:
        """Pad so dim `axis` divides the data-axis size; returns (padded,
        original_len). The ragged->fixed-shape edge (SURVEY hard part #3)."""
        multiple = multiple or self.data_parallelism
        n = x.shape[axis]
        target = ((n + multiple - 1) // multiple) * multiple
        if target == n:
            return x, n
        pad_width = [(0, 0)] * x.ndim
        pad_width[axis] = (0, target - n)
        return np.pad(x, pad_width, constant_values=fill), n


def host_fetch(x) -> np.ndarray:
    """Device array -> host numpy, multi-process safe: a replicated array
    spanning remote processes is not fully addressable, but every local
    shard holds the complete value."""
    if getattr(x, "is_fully_addressable", True):
        return np.asarray(x)
    shard = x.addressable_data(0)
    if shard.shape != x.shape:
        raise ValueError(
            f"host_fetch needs a replicated array; got sharded shape "
            f"{shard.shape} of global {x.shape} — use "
            f"host_fetch_sharded to gather the per-shard slices this "
            f"process can address")
    return np.asarray(shard)


def host_fetch_sharded(x):
    """Device array sharded on dim 0 -> the per-shard host slices this
    process can address, as ``(offsets, slices)`` sorted by global row
    offset. Replicas (e.g. a model-sharded table's copies across the
    data axis) are deduplicated by offset — each row range is fetched
    once. The sharded sibling of :func:`host_fetch`: where that gathers
    one complete value, this hands back exactly the slices a
    ``ShardedTable`` host mirror wants, with no cross-shard gather and
    no remote-process traffic."""
    shards = getattr(x, "addressable_shards", None)
    if shards is None:
        return [0], [np.asarray(x)]
    by_offset = {}
    for sh in shards:
        index = sh.index or (slice(None),)
        # only dim-0 partitioning is a row sharding: an array split on
        # a LATER dim has every shard at row offset 0, and deduping by
        # that offset would silently return one partial shard as the
        # whole value — refuse instead (host_fetch's loud-misuse
        # discipline)
        for d, dim_slice in enumerate(index[1:], start=1):
            full = (dim_slice.start in (None, 0)
                    and dim_slice.stop in (None, x.shape[d]))
            if not full:
                raise ValueError(
                    f"host_fetch_sharded needs an array sharded on "
                    f"dim 0 only; got shard index {index} of global "
                    f"{x.shape}")
        rows = index[0]
        start = rows.start or 0
        if start not in by_offset:
            by_offset[start] = np.asarray(sh.data)
    offsets = sorted(by_offset)
    return offsets, [by_offset[o] for o in offsets]


def make_mesh(devices=None, model_parallelism: int = 1) -> MeshContext:
    return MeshContext.create(devices, model_parallelism)


def current_mesh() -> MeshContext:
    """The active mesh; lazily creates a full-device 1x data mesh."""
    ctx = getattr(_local, "mesh", None)
    if ctx is None:
        ctx = make_mesh()
        _local.mesh = ctx
    return ctx


_model_mesh_lock = threading.Lock()
_model_meshes: dict = {}


def model_mesh(n_shards: int) -> MeshContext:
    """A mesh whose model axis is ``n_shards`` wide — the mesh a
    model-sharded table serves and folds on. The thread's active mesh
    wins when its model axis already matches (tests and explicit
    ``use_mesh`` scopes); otherwise a PROCESS-wide mesh per shard
    count is built and cached, so every server thread resolves the
    SAME mesh for the same layout (``current_mesh``'s thread-local
    default would hand each HTTP handler thread its own 1-wide model
    axis and silently re-replicate a sharded table)."""
    ctx = getattr(_local, "mesh", None)
    if ctx is not None and ctx.model_parallelism == n_shards:
        return ctx
    with _model_mesh_lock:
        ctx = _model_meshes.get(n_shards)
        if ctx is None:
            ctx = make_mesh(model_parallelism=n_shards)
            _model_meshes[n_shards] = ctx
        return ctx


@contextlib.contextmanager
def use_mesh(ctx: MeshContext):
    prev = getattr(_local, "mesh", None)
    _local.mesh = ctx
    try:
        yield ctx
    finally:
        _local.mesh = prev
