"""Native host runtime bindings (ctypes over paddle_native.cc).

Builds the shared library on first use with g++ (cached next to the
source, keyed on a hash of ``src/*.cc``); all entry points degrade to
numpy when the toolchain or library is unavailable, and ``status()``
says which of the two is in use and why.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(_DIR, "src")
_SO = os.path.join(_DIR, "libpaddle_native.so")
#: hash of the sources the library beside it was built from (mtimes do
#: not survive a copied or freshly checked-out tree)
_SO_HASH = _SO + ".srchash"


def _sources():
    return sorted(
        os.path.join(_SRC_DIR, f) for f in os.listdir(_SRC_DIR)
        if f.endswith(".cc"))


def _source_hash(srcs) -> str:
    h = hashlib.sha256()
    for path in srcs:
        h.update(os.path.basename(path).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


_lib = None
_lib_failed = False  # cache build/load failure: don't retry every call
_status = "not loaded"
_lock = threading.Lock()


def _build(srcs, src_hash):
    # build beside the target and rename: a concurrent process never
    # loads a half-written library
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
           *srcs, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, _SO)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    with open(_SO_HASH, "w") as f:
        f.write(src_hash)


def _built_hash():
    try:
        with open(_SO_HASH) as f:
            return f.read().strip()
    except OSError:
        return None


def status() -> str:
    """How the native library came to be in this process: ``built``
    (compiled now), ``loaded`` (an up-to-date build was found),
    ``unavailable: <why>`` (the numpy paths are in use) or ``not
    loaded`` (nothing asked for it yet)."""
    return _status


def get_lib():
    """Load (building if needed) the native library; None if
    unavailable — ``status()`` then carries the reason."""
    global _lib, _lib_failed, _status
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            srcs = _sources()
            src_hash = _source_hash(srcs)
            if os.path.exists(_SO) and _built_hash() == src_hash:
                how = "loaded"
            else:
                _build(srcs, src_hash)
                how = "built"
            lib = ctypes.CDLL(_SO)
        except (OSError, subprocess.CalledProcessError) as e:
            _lib_failed = True
            detail = e.stderr.decode(errors="replace")[-500:] \
                if getattr(e, "stderr", None) else str(e)
            _status = f"unavailable: {type(e).__name__}: {detail}"
            return None
        _status = how
        lib.pn_collate.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32]
        lib.pn_u8hwc_to_f32chw_batch.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_float,
            ctypes.c_int32]
        lib.pn_queue_create.restype = ctypes.c_void_p
        lib.pn_queue_create.argtypes = [ctypes.c_int64]
        lib.pn_queue_destroy.argtypes = [ctypes.c_void_p]
        lib.pn_queue_close.argtypes = [ctypes.c_void_p]
        lib.pn_queue_push.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_int64]
        lib.pn_queue_push.restype = ctypes.c_int32
        lib.pn_queue_next_size.argtypes = [ctypes.c_void_p]
        lib.pn_queue_next_size.restype = ctypes.c_int64
        lib.pn_queue_pop.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                     ctypes.c_int64]
        lib.pn_queue_pop.restype = ctypes.c_int64
        lib.pn_queue_size.argtypes = [ctypes.c_void_p]
        lib.pn_queue_size.restype = ctypes.c_int64
        # --- TCP store ---
        lib.pn_store_server_start.restype = ctypes.c_void_p
        lib.pn_store_server_start.argtypes = [ctypes.c_int32]
        lib.pn_store_server_stop.argtypes = [ctypes.c_void_p]
        lib.pn_store_connect.restype = ctypes.c_void_p
        lib.pn_store_connect.argtypes = [ctypes.c_char_p, ctypes.c_int32,
                                         ctypes.c_int32]
        lib.pn_store_client_close.argtypes = [ctypes.c_void_p]
        lib.pn_store_set.restype = ctypes.c_int32
        lib.pn_store_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_void_p, ctypes.c_int64]
        lib.pn_store_get.restype = ctypes.c_int64
        lib.pn_store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_void_p, ctypes.c_int64,
                                     ctypes.c_int64]
        lib.pn_store_add.restype = ctypes.c_int64
        lib.pn_store_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_int64]
        lib.pn_store_check.restype = ctypes.c_int32
        lib.pn_store_check.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pn_store_delete.restype = ctypes.c_int32
        lib.pn_store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pn_store_list.restype = ctypes.c_int64
        lib.pn_store_list.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int32)]
        # --- host tracer ---
        lib.pn_prof_enable.argtypes = [ctypes.c_int32]
        lib.pn_prof_enabled.restype = ctypes.c_int32
        lib.pn_prof_begin.argtypes = [ctypes.c_char_p]
        lib.pn_prof_record.argtypes = [ctypes.c_char_p, ctypes.c_double,
                                       ctypes.c_double]
        lib.pn_prof_count.restype = ctypes.c_int64
        lib.pn_prof_get.restype = ctypes.c_int64
        lib.pn_prof_get.argtypes = [
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_int64)]
        # --- stats registry ---
        lib.pn_stat_update.restype = ctypes.c_int64
        lib.pn_stat_update.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.pn_stat_current.restype = ctypes.c_int64
        lib.pn_stat_current.argtypes = [ctypes.c_char_p]
        lib.pn_stat_peak.restype = ctypes.c_int64
        lib.pn_stat_peak.argtypes = [ctypes.c_char_p]
        lib.pn_stat_reset_peak.argtypes = [ctypes.c_char_p]
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


# --------------------------------------------------------------------------
def collate(samples, nthreads=4):
    """Stack equally-shaped contiguous np arrays into a batch (parallel
    native memcpy; numpy fallback)."""
    arrs = [np.ascontiguousarray(s) for s in samples]
    lib = get_lib()
    first = arrs[0]
    if lib is None or any(a.shape != first.shape or a.dtype != first.dtype
                          for a in arrs):
        return np.stack(arrs)
    out = np.empty((len(arrs),) + first.shape, first.dtype)
    ptrs = (ctypes.c_void_p * len(arrs))(
        *[a.ctypes.data_as(ctypes.c_void_p) for a in arrs])
    lib.pn_collate(ptrs, len(arrs), out.ctypes.data_as(ctypes.c_void_p),
                   first.nbytes, nthreads)
    return out


def u8hwc_to_f32chw_batch(images, mean, std, scale=1.0 / 255.0,
                          nthreads=4):
    """Fused ToTensor+Normalize+Transpose over a batch of uint8 HWC
    images -> float32 [N, C, H, W]."""
    arrs = [np.ascontiguousarray(im, np.uint8) for im in images]
    h, w, c = arrs[0].shape
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    lib = get_lib()
    if lib is None:
        batch = np.stack(arrs).astype(np.float32) * scale
        batch = (batch - mean.reshape(1, 1, 1, -1)) / std.reshape(
            1, 1, 1, -1)
        return batch.transpose(0, 3, 1, 2).copy()
    out = np.empty((len(arrs), c, h, w), np.float32)
    ptrs = (ctypes.c_void_p * len(arrs))(
        *[a.ctypes.data_as(ctypes.c_void_p) for a in arrs])
    lib.pn_u8hwc_to_f32chw_batch(
        ptrs, out.ctypes.data_as(ctypes.c_void_p), len(arrs), h, w, c,
        mean.ctypes.data_as(ctypes.c_void_p),
        std.ctypes.data_as(ctypes.c_void_p), scale, nthreads)
    return out


class BlockingQueue:
    """Native condvar blocking queue for byte blobs
    (LoDTensorBlockingQueue analog). Fallback: queue.Queue."""

    def __init__(self, capacity=8):
        lib = get_lib()
        self._lib = lib
        if lib is None:
            import queue
            self._q = queue.Queue(maxsize=capacity)
            self._handle = None
        else:
            self._handle = ctypes.c_void_p(lib.pn_queue_create(capacity))

    def push(self, data: bytes) -> bool:
        if self._handle is None:
            self._q.put(data)
            return True
        buf = (ctypes.c_char * len(data)).from_buffer_copy(data)
        return bool(self._lib.pn_queue_push(self._handle, buf, len(data)))

    def pop(self):
        """bytes, or None at end-of-stream (closed + drained)."""
        if self._handle is None:
            item = self._q.get()
            return item
        size = self._lib.pn_queue_next_size(self._handle)
        if size < 0:
            return None
        out = ctypes.create_string_buffer(size)
        got = self._lib.pn_queue_pop(self._handle, out, size)
        if got < 0:
            return None
        return out.raw[:got]

    def close(self):
        if self._handle is not None:
            self._lib.pn_queue_close(self._handle)

    def __len__(self):
        if self._handle is None:
            return self._q.qsize()
        return self._lib.pn_queue_size(self._handle)

    def __del__(self):
        try:
            if self._handle is not None:
                self._lib.pn_queue_close(self._handle)
                self._lib.pn_queue_destroy(self._handle)
                self._handle = None
        except Exception:
            pass


# --------------------------------------------------------------------------
class TCPStore:
    """Native TCP rendezvous key-value store.

    Reference: phi/core/distributed/store/tcp_store.h:121 — the
    master/worker KV store used for bootstrap, endpoint exchange and
    host-level barriers. The master rank also runs the server thread.
    Values are bytes; `add` maintains int64 counters (mirrored into the
    KV space so `wait`/`get` can observe them).
    """

    def __init__(self, host: str, port: int, is_master: bool = False,
                 world_size: int = 1, timeout: float = 90.0):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable; TCPStore "
                               "requires the C++ runtime")
        self._lib = lib
        self.host = host
        self.port = port
        self.is_master = is_master
        self.world_size = world_size
        self.timeout = timeout
        self._server = None
        self._client = None
        self._barrier_seq = {}
        if is_master:
            self._server = lib.pn_store_server_start(port)
            if not self._server:
                raise RuntimeError(f"TCPStore: cannot bind port {port}")
        self._client = lib.pn_store_connect(
            host.encode(), port, int(timeout * 1000))
        if not self._client:
            if self._server:
                lib.pn_store_server_stop(self._server)
                self._server = None
            raise RuntimeError(f"TCPStore: cannot connect {host}:{port}")

    def set(self, key: str, value) -> None:
        if isinstance(value, str):
            value = value.encode()
        buf = (ctypes.c_char * len(value)).from_buffer_copy(value) \
            if value else None
        ok = self._lib.pn_store_set(self._client, key.encode(), buf,
                                    len(value))
        if not ok:
            raise RuntimeError("TCPStore.set failed")

    def get(self, key: str, timeout: float = None) -> bytes:
        """Blocking get: waits until `key` is set (reference wait+get)."""
        tmo = int((self.timeout if timeout is None else timeout) * 1000)
        cap = 1 << 16
        while True:
            out = ctypes.create_string_buffer(cap)
            n = self._lib.pn_store_get(self._client, key.encode(), out,
                                       cap, tmo)
            if n == -2:
                cap *= 16
                continue
            if n < 0:
                raise TimeoutError(f"TCPStore.get({key!r}) timed out")
            return out.raw[:n]

    def add(self, key: str, delta: int = 1) -> int:
        v = self._lib.pn_store_add(self._client, key.encode(), delta)
        if v == -(2 ** 63):
            raise RuntimeError("TCPStore.add failed")
        return v

    def check(self, key: str) -> bool:
        return self._lib.pn_store_check(self._client, key.encode()) == 1

    def delete_key(self, key: str) -> bool:
        return bool(self._lib.pn_store_delete(self._client, key.encode()))

    def list(self, prefix: str = "") -> dict:
        """All (key, value) pairs whose key starts with `prefix`."""
        cap = 1 << 16
        while True:
            out = ctypes.create_string_buffer(cap)
            count = ctypes.c_int32()
            n = self._lib.pn_store_list(self._client, prefix.encode(), out,
                                        cap, ctypes.byref(count))
            if n == -2:
                cap *= 16
                continue
            if n < 0:
                raise RuntimeError("TCPStore.list failed")
            buf, off, res = out.raw, 0, {}
            import struct
            for _ in range(count.value):
                klen = struct.unpack_from("<I", buf, off)[0]
                off += 4
                key = buf[off:off + klen].decode()
                off += klen
                vlen = struct.unpack_from("<Q", buf, off)[0]
                off += 8
                res[key] = buf[off:off + vlen]
                off += vlen
            return res

    def wait(self, keys, timeout: float = None) -> None:
        if isinstance(keys, str):
            keys = [keys]
        for k in keys:
            self.get(k, timeout=timeout)

    def barrier(self, tag: str = "default", timeout: float = None) -> None:
        """Host barrier over the store: arrive-count + release key.

        Reusable: each call advances a local per-tag sequence number (all
        ranks call barrier the same number of times, so sequences agree)
        and synchronizes on generation-specific keys.
        """
        seq = self._barrier_seq.get(tag, 0)
        self._barrier_seq[tag] = seq + 1
        n = self.add(f"__barrier/{tag}/{seq}/arrived", 1)
        if n == self.world_size:
            self.set(f"__barrier/{tag}/{seq}/release", b"1")
            if seq > 0:
                # last arriver garbage-collects the previous generation
                # (everyone passed it to get here), bounding store growth
                self.delete_key(f"__barrier/{tag}/{seq - 1}/arrived")
                self.delete_key(f"__barrier/{tag}/{seq - 1}/release")
        self.get(f"__barrier/{tag}/{seq}/release", timeout=timeout)

    def close(self):
        if getattr(self, "_client", None):
            self._lib.pn_store_client_close(self._client)
            self._client = None
        if getattr(self, "_server", None):
            self._lib.pn_store_server_stop(self._server)
            self._server = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# --------------------------------------------------------------------------
# Host tracer (native RecordEvent span buffer).

def tracer_enable(on: bool = True) -> bool:
    lib = get_lib()
    if lib is None:
        return False
    lib.pn_prof_enable(1 if on else 0)
    return True


def tracer_clear():
    lib = get_lib()
    if lib is not None:
        lib.pn_prof_clear()


def tracer_begin(name: str):
    lib = get_lib()
    if lib is not None:
        lib.pn_prof_begin(name.encode())


def tracer_end():
    lib = get_lib()
    if lib is not None:
        lib.pn_prof_end()


def tracer_record(name: str, start_us: float, dur_us: float):
    lib = get_lib()
    if lib is not None:
        lib.pn_prof_record(name.encode(), start_us, dur_us)


def tracer_spans():
    """Drain recorded spans -> list of (name, start_us, dur_us, tid)."""
    lib = get_lib()
    if lib is None:
        return []
    n = lib.pn_prof_count()
    out = []
    name = ctypes.create_string_buffer(512)
    start = ctypes.c_double()
    dur = ctypes.c_double()
    tid = ctypes.c_int64()
    for i in range(n):
        if lib.pn_prof_get(i, name, 512, ctypes.byref(start),
                           ctypes.byref(dur), ctypes.byref(tid)) >= 0:
            out.append((name.value.decode(errors="replace"), start.value,
                        dur.value, tid.value))
    return out


# --------------------------------------------------------------------------
# Stats registry (memory/stats.cc analog).

def stat_update(key: str, delta: int) -> int:
    lib = get_lib()
    if lib is None:
        return 0
    return lib.pn_stat_update(key.encode(), delta)


def stat_current(key: str) -> int:
    lib = get_lib()
    return 0 if lib is None else lib.pn_stat_current(key.encode())


def stat_peak(key: str) -> int:
    lib = get_lib()
    return 0 if lib is None else lib.pn_stat_peak(key.encode())


def stat_reset_peak(key: str):
    lib = get_lib()
    if lib is not None:
        lib.pn_stat_reset_peak(key.encode())


# --------------------------------------------------------------------------
# MultiSlot data feed (fluid/framework/data_feed.cc analog): parse the
# PS-training text format ("<count> v..." per slot per line) in C++
# threads, returning per-slot (values, offsets) ragged arrays.

def _feed_bind(lib):
    if getattr(lib, "_feed_bound", False):
        return
    lib.pn_feed_parse.restype = ctypes.c_void_p
    lib.pn_feed_parse.argtypes = [ctypes.c_char_p, ctypes.c_int32,
                                  ctypes.POINTER(ctypes.c_int32),
                                  ctypes.c_int32]
    lib.pn_feed_rows.restype = ctypes.c_int64
    lib.pn_feed_rows.argtypes = [ctypes.c_void_p]
    lib.pn_feed_slot_size.restype = ctypes.c_int64
    lib.pn_feed_slot_size.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.pn_feed_copy_slot.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64)]
    lib.pn_feed_free.argtypes = [ctypes.c_void_p]
    lib._feed_bound = True


def parse_multislot_file(path, slot_is_float, num_threads=4):
    """Parse one MultiSlot text file natively.

    Returns a list (per slot) of (values, offsets) numpy pairs, where
    offsets is int64[rows+1] and values is int64 or float32 per
    slot_is_float. None when the native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    _feed_bind(lib)
    n = len(slot_is_float)
    flags = (ctypes.c_int32 * n)(*[1 if f else 0
                                   for f in slot_is_float])
    h = lib.pn_feed_parse(str(path).encode(), n, flags, num_threads)
    if not h:
        raise FileNotFoundError(path)
    try:
        rows = lib.pn_feed_rows(h)
        out = []
        for s in range(n):
            total = lib.pn_feed_slot_size(h, s)
            vals = np.empty(total, np.float32 if slot_is_float[s]
                            else np.int64)
            offs = np.empty(rows + 1, np.int64)
            lib.pn_feed_copy_slot(
                h, s, vals.ctypes.data_as(ctypes.c_void_p),
                offs.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_int64)))
            out.append((vals, offs))
        return out
    finally:
        lib.pn_feed_free(h)
