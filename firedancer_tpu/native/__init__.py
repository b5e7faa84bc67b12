"""Native (C++) runtime components, built on demand.

The reference's performance-native layers (tango rings, util shmem) are C;
ours are C++ compiled here into a single shared library loaded via ctypes.
Build is lazy and cached: the library's file name carries a hash of the
sources and the compile command, so a source or flag change builds a new
library, and a library copied in from elsewhere is used only when it was
built from exactly these sources with exactly this command.
"""

import ctypes
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["tango.cpp", "pkteng.cpp", "txnparse.cpp", "hostpath.cpp",
            "packsched.cpp", "aescrypt.cpp"]
_FLAGS = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
          "-fvisibility=hidden"]

_lock = threading.Lock()
_lib = None


def _so_path() -> str:
    """Path of the library built from the current sources and flags."""
    h = hashlib.sha256("\0".join(_FLAGS).encode())
    for s in _SOURCES:
        with open(os.path.join(_DIR, s), "rb") as f:
            h.update(b"\0" + s.encode() + b"\0" + f.read())
    return os.path.join(_DIR, f"_fdtpu_native.{h.hexdigest()[:16]}.so")


def build() -> str:
    """Compile the native library if needed; returns the .so path."""
    so = _so_path()
    with _lock:
        if not os.path.exists(so):
            tmp = f"{so}.tmp{os.getpid()}"
            cmd = _FLAGS + ["-o", tmp] + [os.path.join(_DIR, s)
                                          for s in _SOURCES]
            subprocess.run(cmd, check=True, capture_output=True)
            os.replace(tmp, so)
    return so


def lib() -> ctypes.CDLL:
    """The loaded native library (builds on first use)."""
    global _lib
    if _lib is None:
        path = build()
        with _lock:
            if _lib is None:
                _lib = _bind(ctypes.CDLL(path))
    return _lib


def _bind(L: ctypes.CDLL) -> ctypes.CDLL:
    u64, u32, i32 = ctypes.c_uint64, ctypes.c_uint32, ctypes.c_int
    p = ctypes.c_void_p
    sig = {
        "fd_mcache_align": (u64, []),
        "fd_mcache_footprint": (u64, [u64]),
        "fd_mcache_new": (i32, [p, u64, u64]),
        "fd_mcache_depth": (u64, [p]),
        "fd_mcache_seq0": (u64, [p]),
        "fd_mcache_seq_query": (u64, [p]),
        "fd_mcache_publish": (u64, [p, u64, u32, u32, u32, u32, u32]),
        "fd_mcache_query": (i32, [p, u64, p]),
        "fd_mcache_consume_burst": (i32, [p, u64, u64, p, ctypes.POINTER(u64)]),
        "fd_fseq_footprint": (u64, []),
        "fd_fseq_new": (None, [p, u64]),
        "fd_fseq_update": (None, [p, u64]),
        "fd_fseq_query": (u64, [p]),
        "fd_fseq_diag_add": (None, [p, u64, u64]),
        "fd_fseq_diag_query": (u64, [p, u64]),
        "fd_cnc_footprint": (u64, []),
        "fd_cnc_new": (None, [p]),
        "fd_cnc_signal": (None, [p, u64]),
        "fd_cnc_signal_query": (u64, [p]),
        "fd_cnc_heartbeat": (None, [p, u64]),
        "fd_cnc_heartbeat_query": (u64, [p]),
        "fd_dcache_chunk_sz": (u64, []),
        "fd_dcache_req_data_sz": (u64, [u64, u64, u64]),
        "fd_dcache_compact_next": (u64, [u64, u64, u64, u64]),
        "fd_pkteng_open": (i32, [ctypes.c_char_p, i32, i32]),
        "fd_pkteng_port": (i32, [i32]),
        "fd_pkteng_rx_burst": (i32, [i32, p, i32, i32, p, p, p]),
        "fd_pkteng_tx_burst": (i32, [i32, p, i32, i32, p, p, p]),
        "fd_pkteng_close": (None, [i32]),
        "fd_xring_open": (ctypes.c_longlong,
                          [ctypes.c_char_p, i32, i32, i32]),
        "fd_xring_poll": (i32, [ctypes.c_longlong, i32]),
        "fd_xring_rx_burst": (i32, [ctypes.c_longlong, p, i32, i32,
                                    p, p, p, i32]),
        "fd_xring_close": (None, [ctypes.c_longlong]),
        "fd_ring_rx_burst": (i32, [p, p, u64, u64, u64, i32, i32,
                                   p, p, ctypes.c_int64, p, p, p, p]),
        "fd_ring_tx_burst": (u64, [p, p, u64, u64, u64, p, p, p, p,
                                   i32, u32, u32, p]),
        "fd_tcache_new": (p, [u64]),
        "fd_tcache_delete": (None, [p]),
        "fd_tcache_query": (i32, [p, u64]),
        "fd_tcache_insert": (None, [p, u64]),
        "fd_tcache_insert_batch": (None, [p, p, i32]),
        "fd_tcache_insert_batch_dedup": (None, [p, p, i32, p]),
        "fd_tcache_query_batch": (None, [p, p, i32, p]),
        "fd_hostpath_submit_rows": (ctypes.c_int64,
                                    [p, ctypes.c_int64, i32, i32, p, p, p,
                                     p]),
        "fd_hostpath_finish_rows": (ctypes.c_int64,
                                    [p, ctypes.c_int64, i32, i32, p, p, p,
                                     p, p, ctypes.c_int64, p, p, p]),
        "fd_txn_parse_batch": (i32, [p, p, i32, p, i32, i32, i32,
                                     p, p, p, p, p, p, p, p, p]),
        "fd_txn_parse_batch_packed": (i32, [p, p, i32, p, i32, i32, i32,
                                            p, ctypes.c_int64, p,
                                            p, p, p, p, p]),
        "fd_pack_new": (p, [i32, ctypes.c_longlong]),
        "fd_pack_delete": (None, [p]),
        "fd_pack_acct_key": (u64, [ctypes.c_char_p]),
        "fd_pack_insert": (ctypes.c_longlong,
                           [p, ctypes.c_char_p, ctypes.c_char_p]),
        "fd_pack_pending": (ctypes.c_longlong, [p]),
        "fd_pack_clear_pending": (None, [p]),
        "fd_pack_schedule": (ctypes.c_longlong,
                             [p, i32, i32, ctypes.POINTER(ctypes.c_longlong),
                              ctypes.POINTER(ctypes.c_longlong)]),
        "fd_pack_done": (None, [p, i32]),
        "fd_pack_end_block": (None, [p]),
        "fd_aescrypt_key_new": (ctypes.c_int64, [p, p, p]),
        "fd_aescrypt_key_free": (None, [ctypes.c_int64]),
        "fd_aescrypt_key_cnt": (ctypes.c_int64, []),
        "fd_aescrypt_decrypt_burst": (i32, [p, p, p, p, p, p, p, i32,
                                            p, p, p, p]),
        "fd_aescrypt_encrypt_burst": (i32, [p, p, p, p, p, i32, p]),
        "fd_xsk_fill": (i32, [p, ctypes.c_uint64, ctypes.c_uint64,
                              ctypes.c_uint64, ctypes.c_uint32, p, i32]),
        "fd_xsk_rx_burst": (i32, [p, ctypes.c_uint64, ctypes.c_uint64,
                                  ctypes.c_uint64, ctypes.c_uint32,
                                  p, ctypes.c_uint64, ctypes.c_uint64,
                                  ctypes.c_uint64, ctypes.c_uint32,
                                  p, ctypes.c_uint64, p, ctypes.c_int64,
                                  p, p, p, p, i32]),
    }
    for name, (res, args) in sig.items():
        fn = getattr(L, name)
        fn.restype = res
        fn.argtypes = args
    return L
