"""Shared-memory mutable-object channels — analog of the reference's
python/ray/experimental/channel.py (:16-56 _create_channel_ref — mutable
plasma objects) + src/ray/core_worker/experimental_mutable_object_manager.h.

A Channel is a single-slot SPSC mailbox in POSIX shared memory: the writer
blocks until the reader has acked the previous value (the reference's
"mutable object" write-acquire/read-release protocol), so repeated compiled
DAG invocations reuse one buffer with zero allocation and zero RPC.

Wakeup design: payload + seq/ack live in shm (peeks are ~350ns); each
direction additionally has a named-FIFO *doorbell*. A waiter spins a short
window (microsecond latency when cores are free) and then parks in
select() on the doorbell — a kernel wakeup, which is the only thing that
works on an oversubscribed host (pure spinning burns whole scheduler quanta
on a 1-core box, and sched_yield is a near no-op under EEVDF).

Header layout (24 bytes): seq u64 | ack u64 | payload_len u64. A seq of
2**64-1 marks the channel closed. Each word has ONE writer (seq and
payload_len the writer's, ack the reader's) and is stored whole through a
u64 view of the header: `struct.pack_into` zeroes its whole span before it
fills it, and a peer that spins on the header then reads seq 0 (a message
that was never sent) or ack == seq == 0 (a slot that is not free)."""
from __future__ import annotations

import os
import select
import struct
import tempfile
import time
from multiprocessing import shared_memory
from typing import Optional, Tuple

_HDR = struct.Struct("<QQQ")
_CLOSED = (1 << 64) - 1
DEFAULT_CAPACITY = 16 * 1024 * 1024
# ~70us busy window before parking — but only when a spare core can be
# burning it; on a 1-core host spinning just delays the peer's schedule.
_SPIN_LIMIT = 200 if (os.cpu_count() or 1) > 1 else 0
_PARK_SLICE_S = 0.05       # select timeout; doorbell normally wakes us first


class ChannelClosedError(Exception):
    pass


class Channel:
    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 _attach_name: Optional[str] = None):
        self.capacity = capacity
        if _attach_name is None:
            self._shm = shared_memory.SharedMemory(
                create=True, size=_HDR.size + capacity)
            self._shm.buf[:_HDR.size] = _HDR.pack(0, 0, 0)  # no peer yet
            self._owner = True
            for path in (self._fifo_path("d"), self._fifo_path("a")):
                os.mkfifo(path)
        else:
            self._shm = shared_memory.SharedMemory(name=_attach_name)
            self._owner = False
        # [seq, ack, payload_len]; released before the segment is closed
        self._hdr = self._shm.buf[:_HDR.size].cast("Q")
        self._fd_data: Optional[int] = None
        self._fd_ack: Optional[int] = None

    @property
    def name(self) -> str:
        return self._shm.name

    def _fifo_path(self, tag: str) -> str:
        return os.path.join(tempfile.gettempdir(),
                            f"rtpu_{self._shm.name.lstrip('/')}_{tag}.fifo")

    def _fd(self, tag: str) -> int:
        # O_RDWR so open never blocks/ENXIOs regardless of peer state (Linux
        # allows it on FIFOs) and a doorbell is never lost for lack of reader.
        attr = "_fd_data" if tag == "d" else "_fd_ack"
        fd = getattr(self, attr)
        if fd is None:
            fd = os.open(self._fifo_path(tag), os.O_RDWR | os.O_NONBLOCK)
            setattr(self, attr, fd)
        return fd

    def _ring(self, tag: str) -> None:
        try:
            os.write(self._fd(tag), b"\x01")
        except (BlockingIOError, OSError):  # full pipe still wakes the peer
            pass

    def _park(self, tag: str, deadline: Optional[float]) -> None:
        slice_s = _PARK_SLICE_S
        if deadline is not None:
            slice_s = min(slice_s, max(0.0, deadline - time.monotonic()))
        fd = self._fd(tag)
        r, _, _ = select.select([fd], [], [], slice_s)
        if r:
            try:
                os.read(fd, 4096)  # drain doorbell bytes
            except (BlockingIOError, OSError):
                pass

    def __reduce__(self):
        return (Channel, (self.capacity, self._shm.name))

    # -- writer side --------------------------------------------------------
    def write(self, payload: bytes, timeout: Optional[float] = None) -> None:
        if len(payload) > self.capacity:
            raise ValueError(
                f"value of {len(payload)} bytes exceeds channel capacity "
                f"{self.capacity}; recompile with a larger "
                f"buffer_size_bytes")
        deadline = None if timeout is None else time.monotonic() + timeout
        spins = 0
        hdr = self._hdr
        while True:
            seq, ack = hdr[0], hdr[1]
            if seq == _CLOSED:
                raise ChannelClosedError
            if ack == seq:  # previous value consumed — slot free
                break
            spins += 1
            if spins > _SPIN_LIMIT:
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError(
                        "channel writer timed out waiting for ack")
                self._park("a", deadline)
        self._shm.buf[_HDR.size:_HDR.size + len(payload)] = payload
        hdr[2] = len(payload)
        hdr[0] = seq + 1  # publishes the payload: last
        self._ring("d")

    # -- reader side --------------------------------------------------------
    def read(self, last_seq: int, timeout: Optional[float] = None
             ) -> Tuple[int, bytes]:
        deadline = None if timeout is None else time.monotonic() + timeout
        spins = 0
        hdr = self._hdr
        while True:
            seq = hdr[0]
            if seq == _CLOSED:
                raise ChannelClosedError
            if seq != last_seq:
                data = bytes(self._shm.buf[_HDR.size:_HDR.size + hdr[2]])
                hdr[1] = seq  # ack
                self._ring("a")
                return seq, data
            spins += 1
            if spins > _SPIN_LIMIT:
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError("channel reader timed out")
                self._park("d", deadline)

    # -- lifecycle ----------------------------------------------------------
    def close(self) -> None:
        try:
            self._hdr[0] = _CLOSED
            self._ring("d")
            self._ring("a")
        except Exception:  # noqa: BLE001 — already unlinked
            pass

    def release(self) -> None:
        for attr in ("_fd_data", "_fd_ack"):
            fd = getattr(self, attr)
            if fd is not None:
                try:
                    os.close(fd)
                except OSError:
                    pass
                setattr(self, attr, None)
        try:
            self._hdr.release()
            self._shm.close()
        except Exception:  # noqa: BLE001
            pass

    def destroy(self) -> None:
        self.close()
        self.release()
        if self._owner:
            try:
                self._shm.unlink()
            except Exception:  # noqa: BLE001
                pass
            for tag in ("d", "a"):
                try:
                    os.unlink(self._fifo_path(tag))
                except OSError:
                    pass
