"""Native (C++) runtime components, loaded via ctypes.

The compute path is JAX/XLA; the native layer covers the runtime role the
reference delegates to mpi4py's C library (rendezvous + cross-host tensor
transport, fedml_core/distributed/communication/mpi/) and its prototype gRPC
service (gRPC/grpc_comm_manager.py): a standalone star-topology message
broker (native/router.cpp) that silos dial out to, with frames addressed by
rank. Python talks to it through :class:`NativeRouter` and the
``RoutedCommManager`` backend in fedml_tpu/comm/routed.py.

The shared library is built lazily with g++ on first use and cached in
``fedml_tpu/native/_build`` keyed by source mtime; environments without a
toolchain raise :class:`NativeUnavailable` and the pure-Python TCP backend
remains the fallback.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

_REPO_ROOT = Path(__file__).resolve().parents[2]
_PKG_DIR = Path(__file__).resolve().parent


def _find_src(name: str) -> Path:
    """Native source lookup: repo checkout first, then the in-package copy
    setup.py's build hook ships into wheels (fedml_tpu/native/_src/)."""
    for base in (_REPO_ROOT / "native", _PKG_DIR / "_src"):
        if (base / name).exists():
            return base / name
    return _REPO_ROOT / "native" / name  # canonical path for the error msg


_SRC = _find_src("router.cpp")
_BUILD_DIR = _PKG_DIR / "_build"
_LIB = _BUILD_DIR / "libfedml_router.so"
_build_lock = threading.Lock()


class NativeUnavailable(RuntimeError):
    """The native library could not be built or loaded."""


def _fallback_build_dir() -> Path:
    """Writable cache for read-only installs (system site-packages)."""
    import tempfile

    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache")
    for cand in (Path(base) / "fedml_tpu" / "native",
                 Path(tempfile.gettempdir()) /
                 f"fedml_tpu_native_{os.getuid()}"):
        try:
            cand.mkdir(parents=True, exist_ok=True)
            return cand
        except OSError:
            continue
    raise NativeUnavailable("no writable build directory for native libs")


def _compile_into(src: Path, cand: Path) -> Path:
    """mkdir + writability-probe + g++ into ``cand``. Raises OSError for
    unwritable directories (caller may fall back) and NativeUnavailable
    for toolchain/compile failures (terminal)."""
    import tempfile

    cand.parent.mkdir(parents=True, exist_ok=True)
    # unique probe name: a fixed name races across processes
    fd, probe = tempfile.mkstemp(dir=cand.parent)
    os.close(fd)
    os.unlink(probe)
    cmd = ["g++", "-O2", "-std=c++17", "-fPIC", "-Wall", "-pthread",
           "-shared", "-o", str(cand), str(src)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=300)
    except subprocess.TimeoutExpired as exc:
        # a loaded host can time the build out transiently; mark it so
        # load_packer doesn't negative-cache for the whole process
        err = NativeUnavailable(f"g++ timed out: {exc}")
        err.transient = True
        raise err from exc
    except OSError as exc:
        raise NativeUnavailable(f"g++ unavailable: {exc}") from exc
    if proc.returncode != 0:
        raise NativeUnavailable(
            f"native build failed:\n{proc.stderr[-4000:]}")
    return cand


def _build(src: Path, lib: Path, force: bool = False) -> Path:
    """Compile one native source into a shared library (cached by mtime).

    Raises :class:`NativeUnavailable` for EVERY failure mode (missing
    toolchain, compile error, read-only install) so callers can always
    fall back to pure Python. A read-only package dir falls back to a
    per-user cache whose filename is keyed by the source hash, so two
    installs with different sources can never load each other's ABI."""
    with _build_lock:
        if not src.exists():
            if lib.exists():  # prebuilt library shipped without sources
                return lib
            raise NativeUnavailable(f"native source missing: {src}")
        if (not force and lib.exists()
                and lib.stat().st_mtime >= src.stat().st_mtime):
            return lib
        try:
            return _compile_into(src, lib)
        except OSError:
            # read-only install: content-addressed lib in the user cache
            import hashlib

            tag = hashlib.sha256(src.read_bytes()).hexdigest()[:12]
            fb = _fallback_build_dir() / f"{lib.stem}_{tag}{lib.suffix}"
            if not force and fb.exists():
                return fb
            try:
                return _compile_into(src, fb)
            except OSError as exc:
                raise NativeUnavailable(
                    f"no writable build directory for native libs: "
                    f"{exc}") from exc


def build_lib(force: bool = False) -> Path:
    """Compile native/router.cpp into a shared library (cached by mtime)."""
    return _build(_SRC, _LIB, force)


_lib_handle: Optional[ctypes.CDLL] = None

_PACKER_SRC = _find_src("packer.cpp")
_PACKER_LIB = _BUILD_DIR / "libfedml_packer.so"
# CDLL once loaded, NativeUnavailable after a failed build (negative cache)
_packer_handle = None
_packer_transient_fails = 0  # g++ timeouts seen (2nd one becomes terminal)


def load_packer() -> ctypes.CDLL:
    global _packer_handle
    if isinstance(_packer_handle, NativeUnavailable):
        raise _packer_handle  # negative cache: don't re-run g++ per round
    if _packer_handle is not None:
        return _packer_handle
    try:
        path = _build(_PACKER_SRC, _PACKER_LIB)
        lib = ctypes.CDLL(str(path))
        lib.fedml_pack_clients  # noqa: B018 — probe the symbol now
    except NativeUnavailable as exc:
        if getattr(exc, "transient", False):
            # transient (g++ timeout): allow ONE later retry, then treat as
            # terminal — unbounded retries would stall every large pack for
            # up to 300s on a host where the build reliably times out
            global _packer_transient_fails
            _packer_transient_fails += 1
            if _packer_transient_fails >= 2:
                _packer_handle = exc
        else:
            _packer_handle = exc  # terminal: missing toolchain/compile error
        raise
    except (OSError, AttributeError) as exc:
        # corrupt/truncated .so (e.g. a g++ killed mid-link whose output
        # the mtime cache would keep returning): rebuild once from
        # scratch, then negative-cache a persistent failure
        try:
            path = _build(_PACKER_SRC, _PACKER_LIB, force=True)
            lib = ctypes.CDLL(str(path))
            lib.fedml_pack_clients  # noqa: B018
        except Exception as exc2:  # noqa: BLE001
            err = NativeUnavailable(f"packer library unusable: {exc2!r}")
            _packer_handle = err
            raise err from exc
    lib.fedml_pack_clients.restype = ctypes.c_int
    lib.fedml_pack_clients.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),   # src_ptrs
        ctypes.POINTER(ctypes.c_int64),    # counts
        ctypes.c_int64, ctypes.c_int64,    # P, n_pad
        ctypes.c_int64,                    # row_bytes
        ctypes.c_void_p,                   # dst
        ctypes.c_void_p,                   # mask (nullable)
        ctypes.c_int,                      # n_threads
    ]
    _packer_handle = lib
    return lib


#: the most threads one pack call starts. Past 4-8 a pack into a buffer
#: that has its pages gets slower on the TPU hosts (13 and 30 cores: a
#: thread's start outweighs its share of the copy; PERF.md, PR 36)
PACK_THREADS = 8


def packer_status() -> str:
    """Which packer has served this process so far: ``"native"`` once the
    C++ library is loaded, ``"python"`` with the reason after a failed
    build, or — when no cohort was large enough to try — the numpy
    loop."""
    if isinstance(_packer_handle, NativeUnavailable):
        return f"python (native unavailable: {_packer_handle})"
    if _packer_handle is None:
        return "python (no cohort reached the native packer's size floor)"
    return "native"


def pack_arrays_native(srcs, dst, mask=None,
                       n_threads: Optional[int] = None) -> None:
    """Gather ragged per-client arrays into ``dst [P, n_pad, ...]`` with
    parallel memcpy (native/packer.cpp); zero-pads the tail and writes the
    validity ``mask [P, n_pad]`` when given. ``dst`` and ``mask`` are the
    caller's (fresh or recycled: every byte is written). ``n_threads``
    caps the threads (default: the cores, at most ``PACK_THREADS``); the
    library takes fewer for a small ``dst``.

    ``srcs``: list of P C-contiguous arrays shaped [n_i, ...] with the same
    trailing shape/dtype as ``dst``. Raises :class:`NativeUnavailable` if
    the toolchain is missing (callers fall back to the numpy loop)."""
    import numpy as np

    lib = load_packer()
    # copy the list: elements may be replaced by contiguous copies below,
    # and the caller's list must not see that mutation
    srcs = list(srcs)
    P, n_pad = dst.shape[0], dst.shape[1]
    if len(srcs) != P or not dst.flags.c_contiguous:
        raise ValueError("dst must be C-contiguous [P, n_pad, ...] with "
                         "one src per client")
    if mask is not None and (mask.dtype != np.float32
                             or mask.shape != (P, n_pad)
                             or not mask.flags.c_contiguous):
        # the C side writes P*n_pad float32s straight through the pointer
        raise ValueError(
            f"mask must be C-contiguous float32 [{P}, {n_pad}]; got "
            f"{mask.dtype}{mask.shape}")
    row_bytes = dst.nbytes // max(1, P * n_pad)
    ptrs = (ctypes.c_void_p * P)()
    counts = (ctypes.c_int64 * P)()
    for i, s in enumerate(srcs):
        s = np.ascontiguousarray(s)
        if s.dtype != dst.dtype or s.shape[1:] != dst.shape[2:]:
            # memcpy trusts row_bytes — a dtype/shape mismatch would read
            # out of bounds or silently corrupt rows
            raise ValueError(
                f"client {i}: {s.dtype}{s.shape[1:]} does not match dst "
                f"{dst.dtype}{dst.shape[2:]}")
        srcs[i] = s  # keep alive / contiguous for the call
        ptrs[i] = s.ctypes.data if len(s) else None
        counts[i] = len(s)
    rc = lib.fedml_pack_clients(
        ptrs, counts, P, n_pad, row_bytes,
        dst.ctypes.data_as(ctypes.c_void_p),
        mask.ctypes.data_as(ctypes.c_void_p) if mask is not None else None,
        n_threads or min(PACK_THREADS, os.cpu_count() or 1))
    if rc != 0:
        raise ValueError("a client has more samples than n_pad")


def load_lib() -> ctypes.CDLL:
    global _lib_handle
    if _lib_handle is not None:
        return _lib_handle
    path = build_lib()
    lib = ctypes.CDLL(str(path))
    lib.fedml_router_start.restype = ctypes.c_void_p
    # token is (pointer, length) so binary secrets with NUL bytes survive
    lib.fedml_router_start.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                       ctypes.c_char_p, ctypes.c_int,
                                       ctypes.POINTER(ctypes.c_int)]
    lib.fedml_router_stop.argtypes = [ctypes.c_void_p]
    lib.fedml_router_port.restype = ctypes.c_int
    lib.fedml_router_port.argtypes = [ctypes.c_void_p]
    lib.fedml_router_frames_routed.restype = ctypes.c_ulonglong
    lib.fedml_router_frames_routed.argtypes = [ctypes.c_void_p]
    lib.fedml_router_bytes_routed.restype = ctypes.c_ulonglong
    lib.fedml_router_bytes_routed.argtypes = [ctypes.c_void_p]
    lib.fedml_router_connected_ranks.restype = ctypes.c_int
    lib.fedml_router_connected_ranks.argtypes = [ctypes.c_void_p]
    _lib_handle = lib
    return lib


class NativeRouter:
    """Owns one broker instance inside this process.

    In production the broker runs wherever the federation coordinator lives
    (it is silo-agnostic — payloads are opaque bytes); in tests and
    single-host simulation it lives in-process.
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 token: Optional[bytes] = None):
        """``token``: shared secret every silo must present in its HELLO.
        None/empty = open router (trusted-network / test deployments only —
        see the security note in native/router.cpp)."""
        lib = load_lib()
        out_port = ctypes.c_int(-1)
        tok = bytes(token) if token else b""
        self._handle = lib.fedml_router_start(host.encode(), port,
                                              tok, len(tok),
                                              ctypes.byref(out_port))
        if not self._handle:
            raise NativeUnavailable(
                f"router failed to bind {host}:{port}")
        self._lib = lib
        self.host = host
        self.port = out_port.value

    @property
    def frames_routed(self) -> int:
        return int(self._lib.fedml_router_frames_routed(self._handle))

    @property
    def bytes_routed(self) -> int:
        return int(self._lib.fedml_router_bytes_routed(self._handle))

    @property
    def connected_ranks(self) -> int:
        return int(self._lib.fedml_router_connected_ranks(self._handle))

    def stop(self) -> None:
        if self._handle:
            self._lib.fedml_router_stop(self._handle)
            self._handle = None

    def __enter__(self) -> "NativeRouter":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def __del__(self) -> None:
        try:
            self.stop()
        except Exception:  # ft: allow[FT005] interpreter-teardown __del__:
            pass           # logging/raising here can itself crash
