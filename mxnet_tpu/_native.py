"""Loader for the native libraries (``native/*.cc``): the runtime
(dependency engine + RecordIO codec) and the image data loader.  This
module owns the ctypes signatures.

``mxnet_tpu/lib/*.so`` are committed and are byte for byte what
``make -C native`` builds from the committed sources
(``tests/test_c_api.py`` holds them to that).  ``lib()`` and
``dataloader_lib()`` return None when a library is missing and cannot
be built or loaded; callers then take their pure-python path, a warning
says so, and :func:`loaded` reports which libraries this process has.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

FN_T = ctypes.CFUNCTYPE(None, ctypes.c_void_p)

_LOCK = threading.Lock()
_LIBS = {}       # file name -> CDLL, or None once loading it has failed


def _load(fname, declare):
    """The declared library ``mxnet_tpu/lib/<fname>``, built from
    ``native/`` when the file is missing, or None.  Tried once."""
    if fname in _LIBS:
        return _LIBS[fname]
    with _LOCK:
        if fname in _LIBS:
            return _LIBS[fname]
        here = os.path.dirname(os.path.abspath(__file__))
        path = os.path.join(here, "lib", fname)
        loaded = None
        try:
            if not os.path.exists(path):
                subprocess.run(
                    ["make", "-C", os.path.join(os.path.dirname(here),
                                                "native")],
                    check=True, capture_output=True)
            loaded = declare(ctypes.CDLL(path))
        except (OSError, subprocess.CalledProcessError) as e:
            logging.getLogger("mxtpu.native").warning(
                "native library %s unavailable (%s: %s); using the "
                "pure-python path", fname, type(e).__name__, e)
        _LIBS[fname] = loaded
        return loaded


def loaded():
    """{library file name: bool} for every library asked for so far."""
    return {name: lib is not None for name, lib in sorted(_LIBS.items())}


def _declare(lib):
    c = ctypes
    lib.MXTEngineCreate.restype = c.c_void_p
    lib.MXTEngineCreate.argtypes = [c.c_int, c.c_int]
    lib.MXTEngineNewVar.restype = c.c_void_p
    lib.MXTEngineNewVar.argtypes = [c.c_void_p]
    lib.MXTEnginePush.argtypes = [
        c.c_void_p, FN_T, c.c_void_p,
        c.POINTER(c.c_void_p), c.c_int,
        c.POINTER(c.c_void_p), c.c_int, c.c_int]
    lib.MXTEngineWaitAll.argtypes = [c.c_void_p]
    lib.MXTEngineWaitForVar.argtypes = [c.c_void_p, c.c_void_p]
    lib.MXTEngineVarVersion.restype = c.c_ulonglong
    lib.MXTEngineVarVersion.argtypes = [c.c_void_p, c.c_void_p]
    lib.MXTEnginePending.restype = c.c_long
    lib.MXTEnginePending.argtypes = [c.c_void_p]
    lib.MXTEngineFree.argtypes = [c.c_void_p]

    lib.MXTRecordWriterCreate.restype = c.c_void_p
    lib.MXTRecordWriterCreate.argtypes = [c.c_char_p]
    lib.MXTRecordWriterFree.argtypes = [c.c_void_p]
    lib.MXTRecordWriterWrite.restype = c.c_int
    lib.MXTRecordWriterWrite.argtypes = [c.c_void_p, c.c_char_p, c.c_size_t]
    lib.MXTRecordWriterTell.restype = c.c_long
    lib.MXTRecordWriterTell.argtypes = [c.c_void_p]
    lib.MXTRecordWriterFlush.argtypes = [c.c_void_p]
    lib.MXTRecordReaderCreate.restype = c.c_void_p
    lib.MXTRecordReaderCreate.argtypes = [c.c_char_p]
    lib.MXTRecordReaderFree.argtypes = [c.c_void_p]
    lib.MXTRecordReaderNext.restype = c.c_int
    lib.MXTRecordReaderNext.argtypes = [
        c.c_void_p, c.POINTER(c.c_char_p), c.POINTER(c.c_size_t)]
    lib.MXTRecordReaderTell.restype = c.c_long
    lib.MXTRecordReaderTell.argtypes = [c.c_void_p]
    lib.MXTRecordReaderSeek.argtypes = [c.c_void_p, c.c_long]
    return lib


def lib():
    """The loaded native runtime library, or None if unavailable."""
    return _load("libmxtpu_runtime.so", _declare)


def _dl_declare(lib):
    c = ctypes
    lib.mxt_loader_create.restype = c.c_void_p
    lib.mxt_loader_create.argtypes = [
        c.c_char_p, c.c_int, c.c_int, c.c_int, c.c_int, c.c_int,
        c.c_int, c.c_int, c.c_int, c.c_int, c.c_float,
        c.POINTER(c.c_float), c.POINTER(c.c_float),
        c.c_int, c.c_uint32, c.c_int, c.c_int]
    lib.mxt_loader_count.restype = c.c_int64
    lib.mxt_loader_count.argtypes = [c.c_void_p]
    lib.mxt_loader_failures.restype = c.c_int64
    lib.mxt_loader_failures.argtypes = [c.c_void_p]
    lib.mxt_loader_reset.argtypes = [c.c_void_p]
    lib.mxt_loader_next.restype = c.c_int
    lib.mxt_loader_next.argtypes = [c.c_void_p,
                                    c.POINTER(c.c_float),
                                    c.POINTER(c.c_float)]
    lib.mxt_loader_next_u8.restype = c.c_int
    lib.mxt_loader_next_u8.argtypes = [c.c_void_p,
                                       c.POINTER(c.c_uint8),
                                       c.POINTER(c.c_float)]
    lib.mxt_loader_free.argtypes = [c.c_void_p]
    lib.mxt_loader_set_layout.argtypes = [c.c_void_p, c.c_int]
    return lib


def dataloader_lib():
    """The native image loader library, or None if unavailable."""
    return _load("libmxtpu_dataloader.so", _dl_declare)
