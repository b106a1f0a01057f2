"""ctypes binding for the native C++ prefetching token loader.

The native side (native/dataloader.cpp) mmaps a "BATD" token file and keeps
`queue_depth` ready [batch, seq_len+1] int32 buffers filled by worker
threads; this side hands out (inputs, targets) numpy views and optionally
`jax.device_put`s them.  The loader is deterministic and seekable, so
checkpoint resume (utils/checkpoint.py) just calls `seek(step)`.

Sharding for data parallelism is window-interleaved: rank r of R owns
windows w ≡ r (mod R) — disjoint across ranks, no coordination.  In a
multi-process run pass `shard_id=jax.process_index()`.

The shared library is compiled on first use with the system g++ (the image
has no pybind11; a plain C ABI + ctypes keeps the binding dependency-free)
and cached beside the source.
"""

import ctypes
import hashlib
import os
import subprocess
import threading
import time
from pathlib import Path
from typing import Optional, Tuple

import numpy as np

from ..obs.logs import get_logger, safe_warn
from ..obs.registry import default_registry

_logger = get_logger("burst_attn_tpu.data")

# a `dl_next` that blocked this long found no window ready: the worker
# threads are behind the consumer
STALL_S = 1e-3
_M_STALLS = default_registry().counter(
    "data.loader_stalls", "DataLoader.next() calls whose dl_next blocked "
                          "over 1 ms (no window was ready)")

_MAGIC = 0x44544142  # "BATD"
_HEADER = 16

_lib = None
_lib_lock = threading.Lock()


def _native_dir() -> Path:
    return Path(__file__).resolve().parents[2] / "native"


def _build_lib(src: Path, out: Path) -> None:
    out.parent.mkdir(parents=True, exist_ok=True)
    # pid-unique tmp + atomic rename: concurrent first-use builds (several
    # data-parallel processes starting at once) must not interleave writes
    tmp = out.with_suffix(f".so.tmp.{os.getpid()}")
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
           str(src), "-o", str(tmp)]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, out)
    except FileNotFoundError as e:
        raise RuntimeError(
            f"the data loader is native code and needs g++ to build {src}: "
            f"{e}") from e
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"building {src} failed ({' '.join(cmd)}):\n"
            f"{e.stderr.decode(errors='replace')}") from e
    finally:
        tmp.unlink(missing_ok=True)


def _load_lib() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        src = _native_dir() / "dataloader.cpp"
        out = _native_dir() / "build" / "libdataloader.so"
        # keyed on the source's hash, recorded beside the library: after a
        # copy or a checkout, mtimes say nothing about which is newer
        stamp = out.with_suffix(".so.sha256")
        digest = hashlib.sha256(src.read_bytes()).hexdigest()
        if not (out.exists() and stamp.exists()
                and stamp.read_text().strip() == digest):
            _build_lib(src, out)
            stamp.write_text(digest + "\n")
        lib = ctypes.CDLL(str(out))
        lib.dl_open.restype = ctypes.c_void_p
        lib.dl_open.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
            ctypes.c_int,
        ]
        lib.dl_next.restype = ctypes.c_int64
        lib.dl_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32)]
        lib.dl_seek.restype = None
        lib.dl_seek.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.dl_num_tokens.restype = ctypes.c_int64
        lib.dl_num_tokens.argtypes = [ctypes.c_void_p]
        lib.dl_windows_per_epoch.restype = ctypes.c_int64
        lib.dl_windows_per_epoch.argtypes = [ctypes.c_void_p]
        lib.dl_close.restype = None
        lib.dl_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


def write_token_file(path, tokens: np.ndarray) -> None:
    """Write a BATD token file (uint16 when vocab fits, else uint32)."""
    tokens = np.asarray(tokens)
    if tokens.ndim != 1:
        tokens = tokens.reshape(-1)
    if np.issubdtype(tokens.dtype, np.signedinteger) and tokens.min() < 0:
        raise ValueError("token ids must be non-negative")
    dtype = np.uint16 if tokens.max() < 2**16 else np.uint32
    header = np.array([_MAGIC, 1, dtype().itemsize, 0], np.uint32)
    with open(path, "wb") as f:
        f.write(header.tobytes())
        f.write(np.ascontiguousarray(tokens, dtype).tobytes())


def read_token_file(path) -> np.ndarray:
    """Read a whole BATD file back (for tests / inspection)."""
    raw = Path(path).read_bytes()
    header = np.frombuffer(raw[:_HEADER], np.uint32)
    if header[0] != _MAGIC or header[1] != 1:
        raise ValueError(f"{path}: not a BATD v1 file")
    dtype = np.uint16 if header[2] == 2 else np.uint32
    return np.frombuffer(raw[_HEADER:], dtype)


class DataLoader:
    """Iterator of (inputs [B,S] int32, targets [B,S] int32) batches.

    Targets are inputs shifted by one token (next-token LM objective); the
    native side delivers [B, S+1] windows so both returned arrays are views
    of ONE per-call buffer (no slice copies; the buffer is freshly allocated
    each call, so batches stay valid indefinitely and can be device_put
    asynchronously while the workers fill the next window).
    """

    def __init__(
        self,
        path,
        batch: int,
        seq_len: int,
        *,
        shard_id: int = 0,
        num_shards: int = 1,
        seed: int = 0,
        shuffle: bool = True,
        num_threads: int = 2,
        queue_depth: int = 4,
    ):
        self._lib = _load_lib()
        self._h = self._lib.dl_open(
            str(path).encode(), seq_len, batch, shard_id, num_shards,
            seed, num_threads, queue_depth, int(shuffle),
        )
        if not self._h:
            raise ValueError(
                f"dl_open failed for {path} (bad file/params: batch={batch}, "
                f"seq_len={seq_len}, shard {shard_id}/{num_shards}; the file "
                f"needs >= num_shards * (seq_len+1) tokens)")
        self.batch = batch
        self.seq_len = seq_len
        self.step = 0

    @property
    def num_tokens(self) -> int:
        return self._lib.dl_num_tokens(self._h)

    @property
    def windows_per_epoch(self) -> int:
        """Windows owned by THIS shard per epoch."""
        return self._lib.dl_windows_per_epoch(self._h)

    def seek(self, step: int) -> None:
        """Reposition so the next batch is `step` (checkpoint resume)."""
        self._lib.dl_seek(self._h, step)
        self.step = step

    def next(self) -> Tuple[np.ndarray, np.ndarray]:
        window = np.empty((self.batch, self.seq_len + 1), np.int32)
        ptr = window.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        t0 = time.perf_counter()
        got = self._lib.dl_next(self._h, ptr)
        if time.perf_counter() - t0 > STALL_S:
            _M_STALLS.inc()
        if got < 0:
            raise RuntimeError("dl_next failed")
        self.step = got + 1
        return window[:, :-1], window[:, 1:]

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.dl_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception as e:  # noqa: BLE001 — __del__ must not raise
            # interpreter teardown: even logging can fail here, so route
            # through obs.safe_warn (swallow-proof; failed emissions are
            # kept in obs.logs._DROPPED instead of vanishing)
            safe_warn(_logger, "DataLoader.__del__: close failed (%s: %s)",
                      type(e).__name__, e)
