"""Python bindings for the native shared-memory batch ring.

Parity reference: atorch/atorch/data/shm_context.py:139 (ShmDataContext),
shm_dataloader.py:138 (ShmDataloader), create_coworker_shm_context:527.

The ring itself is C++ (csrc/shm_ring.cpp — process-shared robust mutex +
condvars in one shm segment); this module compiles it on demand with g++
(ctypes, no pybind11 per the environment) and layers the batch protocol:
numpy arrays are framed with a tiny header (no pickle on the hot path;
arbitrary pytrees fall back to pickle transparently).
"""

import ctypes
import io
import os
import pickle
import struct
import subprocess
import tempfile
import threading
from typing import Any, Optional

import numpy as np

from dlrover_tpu.common.log import default_logger as logger

_SRC = os.path.join(os.path.dirname(__file__), "csrc", "shm_ring.cpp")
_LIB_LOCK = threading.Lock()
_LIB = None

_NUMPY_MAGIC = b"DLRN"
_PICKLE_MAGIC = b"DLRP"


def _build_library() -> str:
    """Compile shm_ring.cpp to a cached .so (g++ is in the image)."""
    cache_dir = os.environ.get(
        "DLROVER_TPU_CACHE",
        os.path.join(tempfile.gettempdir(), "dlrover_tpu_native"),
    )
    os.makedirs(cache_dir, exist_ok=True)
    so_path = os.path.join(cache_dir, "libshm_ring.so")
    if (
        os.path.exists(so_path)
        and os.path.getmtime(so_path) >= os.path.getmtime(_SRC)
    ):
        return so_path
    tmp = so_path + f".tmp.{os.getpid()}"
    cmd = [
        "g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SRC,
        "-o", tmp, "-lpthread", "-lrt",
    ]
    logger.info("Building native shm ring: %s", " ".join(cmd))
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, so_path)
    return so_path


def _load_library():
    global _LIB
    with _LIB_LOCK:
        if _LIB is None:
            lib = ctypes.CDLL(_build_library())
            lib.shm_ring_create.restype = ctypes.c_void_p
            lib.shm_ring_create.argtypes = [
                ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
            ]
            lib.shm_ring_attach.restype = ctypes.c_void_p
            lib.shm_ring_attach.argtypes = [ctypes.c_char_p]
            lib.shm_ring_push.restype = ctypes.c_int
            lib.shm_ring_push.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
                ctypes.c_long,
            ]
            lib.shm_ring_pop.restype = ctypes.c_int64
            lib.shm_ring_pop.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
                ctypes.c_long,
            ]
            lib.shm_ring_size.restype = ctypes.c_int
            lib.shm_ring_size.argtypes = [ctypes.c_void_p]
            lib.shm_ring_slot_size.restype = ctypes.c_uint64
            lib.shm_ring_slot_size.argtypes = [ctypes.c_void_p]
            lib.shm_ring_close.argtypes = [ctypes.c_void_p]
            lib.shm_ring_destroy.argtypes = [ctypes.c_void_p]
            _LIB = lib
    return _LIB


class RingClosed(Exception):
    """Producer closed the ring and all slots are drained."""


class ShmRing:
    """One shared-memory ring. Create in the owning process, attach from
    coworker processes by name."""

    def __init__(self, name: str, slot_bytes: int = 64 << 20,
                 num_slots: int = 8, create: bool = True):
        self._lib = _load_library()
        self.name = name
        self.slot_bytes = slot_bytes
        if create:
            self._handle = self._lib.shm_ring_create(
                name.encode(), slot_bytes, num_slots
            )
        else:
            self._handle = self._lib.shm_ring_attach(name.encode())
            if self._handle:
                # slot size is whatever the creator laid out — read it
                # from the control block so pop buffers always fit
                self.slot_bytes = int(
                    self._lib.shm_ring_slot_size(self._handle)
                )
        if not self._handle:
            raise OSError(f"shm ring {'create' if create else 'attach'} "
                          f"failed for {name!r}")
        self._buf = ctypes.create_string_buffer(
            self.slot_bytes
        )

    @classmethod
    def attach(cls, name: str) -> "ShmRing":
        """Attach to an existing ring; slot size comes from its control
        block, so there is no layout knob on this side."""
        return cls(name, create=False)

    def push_bytes(self, data: bytes, timeout_ms: int = 60_000):
        rc = self._lib.shm_ring_push(
            self._handle, data, len(data), timeout_ms
        )
        if rc == -1:
            raise TimeoutError("shm ring push timed out")
        if rc == -2:
            raise ValueError(
                f"payload {len(data)}B exceeds slot {self.slot_bytes}B"
            )
        if rc == -3:
            raise RingClosed()
        if rc != 0:
            raise OSError(f"shm ring push failed rc={rc}")

    def pop_bytes(self, timeout_ms: int = 60_000) -> bytes:
        rc = self._lib.shm_ring_pop(
            self._handle, self._buf, self.slot_bytes, timeout_ms
        )
        if rc == -1:
            raise TimeoutError("shm ring pop timed out")
        if rc == -3:
            raise RingClosed()
        if rc < 0:
            raise OSError(f"shm ring pop failed rc={rc}")
        return self._buf.raw[:rc]

    # -- batch framing ----------------------------------------------------

    def push(self, batch: Any, timeout_ms: int = 60_000,
             keep_waiting=None):
        """Push a numpy array / tuple of arrays / arbitrary pytree.
        A ring still full after ``timeout_ms`` raises ``TimeoutError``,
        unless ``keep_waiting()`` says to wait that long again: a
        consumer that compiles for minutes is busy, not gone."""
        data = _encode(batch)
        while True:
            try:
                return self.push_bytes(data, timeout_ms)
            except TimeoutError:
                if keep_waiting is None or not keep_waiting():
                    raise

    def pop(self, timeout_ms: int = 60_000) -> Any:
        return _decode(self.pop_bytes(timeout_ms))

    def __len__(self) -> int:
        return max(0, self._lib.shm_ring_size(self._handle))

    def close(self):
        """Signal EOF to consumers (drain then RingClosed). No-op after
        destroy: shm_ring_close(NULL) would be a native NULL deref."""
        if self._handle:
            self._lib.shm_ring_close(self._handle)

    def destroy(self):
        if self._handle:
            self._lib.shm_ring_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.destroy()
        except Exception:
            pass


def _encode(batch: Any) -> bytes:
    arrays = None
    if isinstance(batch, np.ndarray):
        arrays = [batch]
    elif isinstance(batch, (tuple, list)) and all(
        isinstance(a, np.ndarray) for a in batch
    ):
        arrays = list(batch)
    if arrays is not None:
        out = io.BytesIO()
        out.write(_NUMPY_MAGIC)
        out.write(struct.pack("<I", len(arrays)))
        for a in arrays:
            hdr = pickle.dumps((a.dtype.str, a.shape), protocol=4)
            out.write(struct.pack("<I", len(hdr)))
            out.write(hdr)
            out.write(np.ascontiguousarray(a).tobytes())
        return out.getvalue()
    return _PICKLE_MAGIC + pickle.dumps(batch, protocol=4)


def _decode(data: bytes) -> Any:
    magic, body = data[:4], memoryview(data)[4:]
    if magic == _PICKLE_MAGIC:
        return pickle.loads(body)
    if magic != _NUMPY_MAGIC:
        raise ValueError("unrecognized shm batch framing")
    (n,) = struct.unpack_from("<I", body, 0)
    off = 4
    arrays = []
    for _ in range(n):
        (hlen,) = struct.unpack_from("<I", body, off)
        off += 4
        dtype_str, shape = pickle.loads(body[off:off + hlen])
        off += hlen
        count = int(np.prod(shape)) if shape else 1
        a = np.frombuffer(
            body, dtype=np.dtype(dtype_str), count=count, offset=off,
        ).reshape(shape)
        off += a.nbytes
        arrays.append(a.copy())  # own the memory past the ring slot
    return arrays[0] if n == 1 else tuple(arrays)
