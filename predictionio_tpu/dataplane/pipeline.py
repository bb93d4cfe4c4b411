"""The bulk-load executor: events -> device-resident arrays, overlapped.

``BulkLoadExecutor.run`` wires the three stages into one stream:

* **read** — a :class:`~predictionio_tpu.dataplane.reader.ChunkReader`
  thread drains the store's chunked cursor into a bounded queue;
* **decode** — the caller's ``decode`` callable turns each wire chunk
  into model-ready host columns (e.g. the recommendation data source's
  ratings conversion), accumulated for the exact-parity host product;
* **upload** — the caller's ``encode`` callable picks the numeric
  columns to stage and a :class:`DeviceStager` double-buffers them to
  the device, hiding transfer time behind the NEXT chunk's decode.

Chunk N+1 is being read while chunk N decodes while chunk N-1 uploads:
the wall clock of a bulk load approaches max(read, decode, upload)
instead of their sum — the serial-drain behavior the TPU capture
showed (product_read_s 24.4 s + fetch 8.7 s in a row).

The run report attributes XLA compiles observed during the steady
streaming phase (from the jaxmon counters): the staging path is
compile-free by construction (``device_put`` onto pow2 buckets), so a
non-zero steady count is a regression signal, surfaced not guessed.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from predictionio_tpu.obs import get_registry
from predictionio_tpu.dataplane.reader import ChunkReader
from predictionio_tpu.dataplane.upload import DeviceStager, StagedSegment


@dataclass
class BulkLoadStats:
    """Stage accounting for one bulk load."""
    wall_s: float = 0.0
    read_s: float = 0.0
    decode_s: float = 0.0
    upload_submit_s: float = 0.0
    upload_wait_s: float = 0.0
    rows: int = 0
    chunks: int = 0
    read_bytes: int = 0
    h2d_bytes: int = 0
    h2d_overlap_frac: float = 1.0
    read_mb_s: float = 0.0
    #: compile requests / their seconds observed DURING the steady
    #: streaming phase (obs/costmon's family deltas) — expected 0
    steady_compiles: int = 0
    steady_compile_s: float = 0.0


@dataclass
class BulkLoadResult:
    """Everything a bulk load produced: the accumulated host-side
    decoded chunks (exact-parity input to the existing train path) and
    the device-resident staged segments (transfer complete)."""
    decoded: List[object] = field(default_factory=list)
    segments: List[StagedSegment] = field(default_factory=list)
    stats: BulkLoadStats = field(default_factory=BulkLoadStats)


#: stats of the most recent completed bulk load in this process —
#: callers that trigger a streamed read indirectly (e.g. bootstrap
#: driving run_train, where the load happens inside the data source)
#: read their attribution here
last_stats: Optional[BulkLoadStats] = None


class BulkLoadExecutor:
    """Streaming bulk-read executor over an app-name-keyed event store
    (``PEventStore`` by default)."""

    def __init__(self, store=None, chunk_rows: Optional[int] = None,
                 queue_depth: int = 2, slots: int = 2):
        if store is None:
            from predictionio_tpu.data.store.event_store import PEventStore
            store = PEventStore
        self.store = store
        self.chunk_rows = chunk_rows
        self.queue_depth = queue_depth
        self.slots = slots
        # install the jax.monitoring listener HERE, not in run():
        # registration (COST003) belongs at init, and run() is a
        # hot-path root — its per-chunk loop must stay alloc-free
        from predictionio_tpu.obs import jaxmon
        jaxmon.install()
        reg = get_registry()
        self._m_decode_s = reg.counter(
            "pio_dataplane_decode_seconds_total",
            "Seconds the dataplane decode stage spent converting wire "
            "chunks to model-ready columns")
        self._m_loads = reg.counter(
            "pio_dataplane_loads_total",
            "Completed dataplane bulk-load runs")

    def run(self, app_name: str, channel_name: Optional[str] = None,
            property_field: Optional[str] = None,
            decode: Optional[Callable[[Dict[str, "object"]], object]] = None,
            encode: Optional[Callable[[object], Optional[
                Dict[str, "object"]]]] = None,
            stage: bool = True, **filters) -> BulkLoadResult:
        """Stream one bulk load.

        ``decode(chunk_cols) -> decoded`` runs per chunk on this
        thread (overlapped with the reader thread's NEXT chunk);
        its results accumulate into ``result.decoded`` in stream
        order. ``encode(decoded) -> {name: numeric ndarray} | None``
        selects what to stage; None/missing skips staging for that
        chunk. With no ``decode`` the wire chunk itself is
        accumulated; with no ``encode`` (and ``stage=True``) the
        numeric wire columns (``t``, ``prop``) are staged.
        """
        result = BulkLoadResult()
        stager = DeviceStager(slots=self.slots) if stage else None
        reader = ChunkReader(
            self.store, app_name, channel_name=channel_name,
            property_field=property_field, chunk_rows=self.chunk_rows,
            queue_depth=self.queue_depth, **filters)
        from predictionio_tpu.obs import costmon
        compiled0 = costmon.compile_totals()
        t_start = time.perf_counter()
        with reader:
            for chunk in reader:
                t0 = time.perf_counter()
                decoded = decode(chunk) if decode is not None else chunk
                dt = time.perf_counter() - t0
                result.stats.decode_s += dt
                self._m_decode_s.inc(dt)
                if decoded is None:
                    continue
                result.decoded.append(decoded)
                if stager is not None:
                    if encode is not None:
                        cols = encode(decoded)
                    else:
                        cols = {k: v for k, v in chunk.items()
                                if k in ("t", "prop")}
                    if cols:
                        stager.stage(cols)
        # end of steady phase: everything past here is finalize
        compiled = costmon.compile_totals()
        steady_compiles = compiled["requests"] - compiled0["requests"]
        steady_compile_s = compiled["seconds"] - compiled0["seconds"]
        if stager is not None:
            result.segments = stager.finish()
        st = result.stats
        st.wall_s = time.perf_counter() - t_start
        st.read_s = reader.read_s
        st.rows = reader.rows
        st.chunks = reader.chunks
        st.read_bytes = reader.bytes
        if stager is not None:
            st.upload_submit_s = stager.stats.submit_s
            st.upload_wait_s = stager.stats.wait_s
            st.h2d_bytes = stager.stats.h2d_bytes
            st.h2d_overlap_frac = stager.stats.overlap_frac
        st.read_mb_s = ((st.read_bytes / 1e6) / st.read_s
                        if st.read_s > 0 else 0.0)
        st.steady_compiles = int(steady_compiles)
        st.steady_compile_s = float(steady_compile_s)
        self._m_loads.inc(1)
        global last_stats
        last_stats = st
        return result
