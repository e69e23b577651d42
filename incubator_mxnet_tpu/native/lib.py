"""ctypes bindings for the native library (built from src/*.cc).

Build: on first use (``_load`` calls ``build()``, one g++ command, mtime-
gated), or by hand with ``python -c "from incubator_mxnet_tpu.native import
lib; lib.build()"``. The .so files are build outputs, not in git. All users
gate on ``available()`` and fall back to pure Python.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import sys

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libmxtpu.so")
_LIB = None


def build(force=False):
    """Compile src/*.cc into libmxtpu.so with g++ -O3 -pthread -ljpeg.

    c_predict_api.cc is excluded — it embeds CPython and builds into its
    own libmxtpu_predict.so (see build_predict)."""
    srcs = sorted(
        os.path.join(_DIR, "src", f) for f in os.listdir(os.path.join(_DIR, "src"))
        if f.endswith(".cc") and f != "c_predict_api.cc")
    if os.path.exists(_SO) and not force and \
            os.path.getmtime(_SO) >= max(os.path.getmtime(s) for s in srcs):
        return _SO
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           *srcs, "-o", _SO, "-ljpeg"]
    subprocess.run(cmd, check=True, capture_output=True)
    return _SO


_PREDICT_SO = os.path.join(_DIR, "libmxtpu_predict.so")


def build_predict(force=False):
    """Compile the C predict API (embedded CPython) into libmxtpu_predict.so.

    Include/link flags come from sysconfig of THIS interpreter, so the
    library embeds a matching libpython (ref c_predict_api deployment)."""
    import sysconfig
    src = os.path.join(_DIR, "src", "c_predict_api.cc")
    if os.path.exists(_PREDICT_SO) and not force and \
            os.path.getmtime(_PREDICT_SO) >= os.path.getmtime(src):
        return _PREDICT_SO
    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR") or "/usr/local/lib"
    ver = "python" + (sysconfig.get_config_var("LDVERSION")
                      or "%d.%d" % sys.version_info[:2])
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           src, "-I", inc, "-L", libdir, "-Wl,-rpath," + libdir,
           "-l" + ver, "-ldl", "-o", _PREDICT_SO]
    subprocess.run(cmd, check=True, capture_output=True)
    return _PREDICT_SO


def _load():
    global _LIB
    if _LIB is not None:
        return _LIB
    try:
        build()  # mtime-gated: rebuilds when src/*.cc is newer than the .so,
        #          so a stale binary can't skew the Python<->C++ contract
        lib = ctypes.CDLL(_SO)
    except (OSError, subprocess.CalledProcessError):
        _LIB = False
        return False
    c = ctypes
    lib.rio_writer_open.restype = c.c_void_p
    lib.rio_writer_open.argtypes = [c.c_char_p]
    lib.rio_writer_tell.restype = c.c_long
    lib.rio_writer_tell.argtypes = [c.c_void_p]
    lib.rio_write.restype = c.c_int
    lib.rio_write.argtypes = [c.c_void_p, c.c_char_p, c.c_uint32]
    lib.rio_writer_close.argtypes = [c.c_void_p]
    lib.rio_scan.restype = c.c_long
    lib.rio_scan.argtypes = [c.c_char_p, c.POINTER(c.c_int64),
                             c.POINTER(c.c_int64), c.c_long]
    lib.pool_create.restype = c.c_void_p
    lib.pool_alloc.restype = c.c_void_p
    lib.pool_alloc.argtypes = [c.c_void_p, c.c_size_t]
    lib.pool_free.argtypes = [c.c_void_p, c.c_void_p, c.c_size_t]
    lib.pool_used_bytes.restype = c.c_size_t
    lib.pool_used_bytes.argtypes = [c.c_void_p]
    lib.pool_destroy.argtypes = [c.c_void_p]
    lib.rio_reader_create.restype = c.c_void_p
    lib.rio_reader_create.argtypes = [c.c_char_p, c.c_long, c.c_int, c.c_int,
                                      c.c_int, c.c_long, c.c_long, c.c_long]
    lib.rio_reader_num_batches.restype = c.c_long
    lib.rio_reader_num_batches.argtypes = [c.c_void_p]
    lib.rio_reader_num_records.restype = c.c_long
    lib.rio_reader_num_records.argtypes = [c.c_void_p]
    lib.rio_reader_next.restype = c.c_long
    lib.rio_reader_next.argtypes = [c.c_void_p, c.c_char_p, c.c_long,
                                    c.POINTER(c.c_int64)]
    lib.rio_reader_reset.argtypes = [c.c_void_p, c.c_int]
    lib.rio_reader_destroy.argtypes = [c.c_void_p]
    # image pipeline (src/image.cc)
    lib.img_pipe_create.restype = c.c_void_p
    lib.img_pipe_create.argtypes = [
        c.c_char_p, c.c_long, c.c_int, c.c_int, c.c_int, c.c_int, c.c_int,
        c.c_int, c.POINTER(c.c_float), c.POINTER(c.c_float), c.c_float,
        c.c_int, c.c_int, c.c_int, c.c_long, c.c_long, c.c_long]
    lib.img_pipe_num_batches.restype = c.c_long
    lib.img_pipe_num_batches.argtypes = [c.c_void_p]
    lib.img_pipe_num_records.restype = c.c_long
    lib.img_pipe_num_records.argtypes = [c.c_void_p]
    lib.img_pipe_next.restype = c.c_long
    lib.img_pipe_next.argtypes = [c.c_void_p, c.POINTER(c.c_float),
                                  c.POINTER(c.c_float)]
    lib.img_pipe_reset.argtypes = [c.c_void_p, c.c_int]
    lib.img_pipe_destroy.argtypes = [c.c_void_p]
    _LIB = lib
    return lib


def available():
    from ..config import get_env
    if get_env("MXTPU_NO_NATIVE"):
        return False
    lib = _load()
    return bool(lib)


def get():
    lib = _load()
    if not lib:
        raise RuntimeError("native library unavailable (g++ build failed)")
    return lib


class NativeBatchReader:
    """Prefetching record-batch reader backed by C++ worker threads."""

    def __init__(self, path, batch_size, shuffle=False, seed=0, num_threads=2,
                 max_ready=4, part_index=0, num_parts=1):
        self._lib = get()
        self._h = self._lib.rio_reader_create(
            path.encode(), batch_size, int(shuffle), seed, num_threads,
            max_ready, part_index, num_parts)
        if not self._h:
            raise IOError("cannot open record file %s" % path)
        self.batch_size = batch_size
        self._sizes = (ctypes.c_int64 * batch_size)()
        self._cap = 1 << 22
        self._buf = ctypes.create_string_buffer(self._cap)

    @property
    def num_batches(self):
        return self._lib.rio_reader_num_batches(self._h)

    @property
    def num_records(self):
        return self._lib.rio_reader_num_records(self._h)

    def next(self):
        """Returns list[bytes] payloads of the next batch, or None at epoch end."""
        total = self._lib.rio_reader_next(self._h, self._buf, self._cap,
                                          self._sizes)
        if total < 0:
            return None
        while total > self._cap:
            # Oversized batch: the C++ side kept it queued (did not consume),
            # so growing the buffer and retrying fetches the SAME batch.
            self._cap = 1 << max(total.bit_length(), 22)
            self._buf = ctypes.create_string_buffer(self._cap)
            total = self._lib.rio_reader_next(self._h, self._buf, self._cap,
                                              self._sizes)
            if total < 0:
                return None
        raw = self._buf.raw  # ONE copy of the buffer, not one per record
        out, off = [], 0
        for i in range(self.batch_size):
            n = self._sizes[i]
            if n < 0:
                raise IOError("truncated record in batch (record %d): file "
                              "shorter than its index claims" % i)
            out.append(raw[off:off + n])
            off += n
        return out

    def reset(self, reshuffle=True):
        self._lib.rio_reader_reset(self._h, int(reshuffle))

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.rio_reader_destroy(self._h)
        except Exception:
            pass


class NativeImagePipeline:
    """C++ JPEG decode + augment + NCHW batch assembly (src/image.cc) — no
    Python in the decode loop (ref src/io/iter_image_recordio_2.cc:51)."""

    def __init__(self, path, batch_size, data_shape, label_width=1,
                 resize_short=0, rand_crop=False, rand_mirror=False,
                 mean_rgb=None, std_rgb=None, scale=1.0, shuffle=False,
                 seed=0, num_threads=4, part_index=0, num_parts=1):
        import numpy as onp
        self._lib = get()
        c, h, w = data_shape
        if c != 3:
            raise ValueError("native pipeline produces 3-channel RGB")
        mean = (ctypes.c_float * 3)(*(mean_rgb or (0., 0., 0.)))
        std = (ctypes.c_float * 3)(*(std_rgb or (1., 1., 1.)))
        self._h = self._lib.img_pipe_create(
            path.encode(), batch_size, h, w, label_width, resize_short,
            int(rand_crop), int(rand_mirror), mean, std, float(scale),
            int(shuffle), seed, num_threads, 4, part_index, num_parts)
        if not self._h:
            raise IOError("cannot open record file %s" % path)
        self.batch_size = batch_size
        self.data_shape = (batch_size, 3, h, w)
        self.label_shape = (batch_size, label_width)
        self._data = onp.empty(self.data_shape, onp.float32)
        self._labels = onp.empty(self.label_shape, onp.float32)

    @property
    def num_batches(self):
        return self._lib.img_pipe_num_batches(self._h)

    @property
    def num_records(self):
        return self._lib.img_pipe_num_records(self._h)

    def next(self):
        """Returns (data NCHW float32, labels, n_bad) or None at epoch end.
        The returned arrays are reused across calls — copy if you keep them."""
        bad = self._lib.img_pipe_next(
            self._h,
            self._data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        if bad < 0:
            return None
        return self._data, self._labels, int(bad)

    def reset(self, reshuffle=True):
        self._lib.img_pipe_reset(self._h, int(reshuffle))

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.img_pipe_destroy(self._h)
        except Exception:
            pass


class HostBufferPool:
    """Pooled host staging allocator (C++ size-bucketed free lists)."""

    def __init__(self):
        self._lib = get()
        self._h = self._lib.pool_create()

    def alloc(self, size):
        return self._lib.pool_alloc(self._h, size)

    def free(self, ptr, size):
        self._lib.pool_free(self._h, ptr, size)

    def used_bytes(self):
        return self._lib.pool_used_bytes(self._h)

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.pool_destroy(self._h)
        except Exception:
            pass


def scan_offsets(path):
    """Fast native scan: returns (offsets, lengths) numpy arrays."""
    import numpy as onp
    lib = get()
    n = lib.rio_scan(path.encode(), None, None, 0)
    if n < 0:
        raise IOError("scan failed for %s (code %d)" % (path, n))
    offs = (ctypes.c_int64 * n)()
    lens = (ctypes.c_int64 * n)()
    lib.rio_scan(path.encode(), offs, lens, n)
    return onp.frombuffer(offs, dtype=onp.int64).copy(), \
        onp.frombuffer(lens, dtype=onp.int64).copy()
