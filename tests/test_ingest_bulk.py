"""ISSUE 7 ingest acceptance: the group-commit write plane under
concurrency, and the durability contract when a writer dies mid-group.

Tier-2 (slow): timing comparisons and a subprocess SIGKILL don't belong
in the tier-1 lane.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from predictionio_tpu.data import Event
from predictionio_tpu.data.storage.nativelog import StorageClient
from predictionio_tpu.data.storage.registry import StorageClientConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _store(tmp_path, name, partitions=1):
    c = StorageClient(StorageClientConfig(
        "TEST", "nativelog", {"PATH": str(tmp_path / name),
                              "PARTITIONS": str(partitions)}))
    ev = c.get_data_object("events", "t")
    ev.init(1)
    return c, ev


def _event(tag, i):
    return Event(event="rate", entity_type="user",
                 entity_id=f"{tag}-u{i}")


@pytest.mark.slow
class TestConcurrentIngestBeatsSerial:
    """The contention regression bar: 8 concurrent writers must complete
    with zero lost/duplicated events and aggregate throughput >= the
    serial run (the group committer batches them instead of convoying
    on the append lock)."""

    N = 2000

    def _serial_rate(self, tmp_path):
        c, ev = _store(tmp_path, "serial")
        try:
            t0 = time.perf_counter()
            ids = [ev.insert(_event("s", i), 1) for i in range(self.N)]
            rate = self.N / (time.perf_counter() - t0)
            assert len(set(ids)) == self.N
            return rate
        finally:
            c.close()

    def _concurrent_rate(self, tmp_path, tag):
        c, ev = _store(tmp_path, f"conc{tag}")
        try:
            per = self.N // 8
            out: list = [None] * 8
            errs: list = []

            def worker(w):
                try:
                    out[w] = [ev.insert(_event(f"c{w}", i), 1)
                              for i in range(per)]
                except Exception as e:   # pragma: no cover
                    errs.append(e)

            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(8)]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            rate = (per * 8) / (time.perf_counter() - t0)
            assert not errs, errs
            ids = [i for w in out for i in w]
            # zero lost, zero duplicated — every ack names a live event
            assert len(ids) == len(set(ids)) == per * 8
            found = {e.event_id for e in ev.find(1, limit=-1)}
            assert set(ids) <= found
            return rate
        finally:
            c.close()

    def test_concurrent8_not_slower_than_serial(self, tmp_path):
        serial = self._serial_rate(tmp_path)
        conc = self._concurrent_rate(tmp_path, "a")
        if conc < serial:
            # one re-measure: this asserts a real throughput ordering on
            # a shared CI box, so give scheduler noise a second sample
            conc = max(conc, self._concurrent_rate(tmp_path, "b"))
            serial = min(serial, self._serial_rate(tmp_path))
        assert conc >= serial, (
            f"concurrent-8 {conc:,.0f} ev/s < serial {serial:,.0f} ev/s "
            "— the append-lock contention regression is back")


_KILL_CHILD = r"""
import sys, threading
sys.path.insert(0, {repo!r})
from predictionio_tpu.data import Event
from predictionio_tpu.data.storage.nativelog import StorageClient
from predictionio_tpu.data.storage.registry import StorageClientConfig

c = StorageClient(StorageClientConfig(
    "TEST", "nativelog", {{"PATH": {path!r}, "PARTITIONS": "2"}}))
ev = c.get_data_object("events", "t")
ev.init(1)
lock = threading.Lock()

def writer(w):
    i = 0
    while True:
        eid = ev.insert(Event(event="rate", entity_type="user",
                              entity_id=f"w{{w}}-u{{i}}"), 1)
        # the ack line IS the contract: printed (and flushed) only
        # after insert returned, i.e. after the group's flush-to-OS
        with lock:
            print(eid, flush=True)
        i += 1

for w in range(4):
    threading.Thread(target=writer, args=(w,), daemon=True).start()
threading.Event().wait()
"""


@pytest.mark.slow
class TestKillMidGroupCommit:
    def test_acked_events_survive_sigkill(self, tmp_path):
        """Durability bar: SIGKILL the writer process mid-stream (group
        commits in flight on 4 threads) — every event it ACKed must be
        readable after reopening the logs. The ack barrier is the
        group's flush-to-OS, so a process kill may lose in-flight
        (unacked) records and a torn tail, never an acked one."""
        path = str(tmp_path / "log")
        child = subprocess.Popen(
            [sys.executable, "-c",
             _KILL_CHILD.format(repo=REPO, path=path)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        acked = []
        deadline = time.time() + 30
        try:
            while len(acked) < 400 and time.time() < deadline:
                line = child.stdout.readline().strip()
                if line:
                    acked.append(line)
            assert len(acked) >= 400, "child produced too few acks"
        finally:
            child.send_signal(signal.SIGKILL)
            child.wait(timeout=10)
        # drain acks that were already in the pipe when the kill landed:
        # they were flushed by the child AFTER their insert returned, so
        # they are acked too
        rest = child.stdout.read() or ""
        acked += [ln.strip() for ln in rest.splitlines() if ln.strip()]

        c = StorageClient(StorageClientConfig(
            "TEST", "nativelog", {"PATH": path, "PARTITIONS": "2"}))
        ev = c.get_data_object("events", "t")
        try:
            missing = [eid for eid in acked if ev.get(eid, 1) is None]
            assert not missing, (
                f"{len(missing)}/{len(acked)} ACKED events lost after "
                f"SIGKILL (first: {missing[:3]})")
            # and the reopened log is coherent: a full scan works and
            # yields at least every acked record
            assert len(list(ev.find(1, limit=-1))) >= len(set(acked))
        finally:
            c.close()
