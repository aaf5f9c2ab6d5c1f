"""Native (C++) runtime components, bound via ctypes.

The compute path is JAX/XLA; the host runtime around it uses native code
where the per-row work would otherwise be interpreted Python — here the
columnar ingest loader (``CsvLoader``): transport byte buffers parse in
one C++ pass into the typed column arrays ``InputHandler.send_columns``
consumes, with native dictionary encoding for string attributes (Python
syncs the app StringDictionary once per NEW unique string, never per
row).

The shared libraries build on first use with the image's g++ (no
pip/pybind11 dependency) into a file named after a hash of the source's
CONTENT, next to the source: a checkout copied with a stale build next
to a changed source never loads the stale one (mtimes do not survive a
copy), and the build outputs are ignored by git.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from siddhi_tpu.query_api.definitions import AttrType

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "csv_loader.cpp")
_LOCK = threading.Lock()
_LIB = None
_STRDICT_SRC = os.path.join(_HERE, "strdict.cpp")
_STRDICT_LIB = None
_STRDICT_FAILED = False


def _built(src: str, *flags: str) -> str:
    """Path of the shared library for ``src`` as it reads NOW, building
    it if that exact content (and flag set) was never built here."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read() + " ".join(flags).encode())
    stem = os.path.splitext(os.path.basename(src))[0]
    so = os.path.join(_HERE, f"_{stem}-{digest.hexdigest()[:16]}.so")
    if not os.path.exists(so):
        # build beside, then rename: another process (xdist worker,
        # cluster worker) may be building or loading the same file
        tmp = f"{so}.{os.getpid()}.tmp"
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", *flags,
             src, "-o", tmp],
            check=True, capture_output=True)
        os.replace(tmp, so)
    return so

_TYPE_CODES = {
    AttrType.INT: 0, AttrType.LONG: 0,
    AttrType.FLOAT: 1, AttrType.DOUBLE: 1,
    AttrType.STRING: 2,
    AttrType.BOOL: 3,
}


def _lib():
    global _LIB
    with _LOCK:
        if _LIB is not None:
            return _LIB
        lib = ctypes.CDLL(_built(_SRC))
        lib.loader_new.restype = ctypes.c_void_p
        lib.loader_free.argtypes = [ctypes.c_void_p]
        lib.loader_dict_size.restype = ctypes.c_int64
        lib.loader_dict_size.argtypes = [ctypes.c_void_p]
        lib.loader_dict_get.restype = ctypes.c_int64
        lib.loader_dict_get.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64]
        lib.loader_parse_csv.restype = ctypes.c_int64
        lib.loader_parse_csv.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_int64,
        ]
        lib.loader_parse_jsonl.restype = ctypes.c_int64
        lib.loader_parse_jsonl.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_int64,
        ]
        _LIB = lib
        return lib


def strdict_lib():
    """The native string-dictionary encoder (strdict.cpp), or None when it
    can't build — callers fall back to the pure-Python path. Loaded with
    PyDLL: strdict_encode walks PyObject* arrays and must hold the GIL."""
    global _STRDICT_LIB, _STRDICT_FAILED
    with _LOCK:
        if _STRDICT_LIB is not None or _STRDICT_FAILED:
            return _STRDICT_LIB
        try:
            import sysconfig

            lib = ctypes.PyDLL(_built(
                _STRDICT_SRC, "-I", sysconfig.get_paths()["include"]))
            lib.strdict_new.restype = ctypes.c_void_p
            lib.strdict_free.argtypes = [ctypes.c_void_p]
            lib.strdict_clear.argtypes = [ctypes.c_void_p]
            lib.strdict_count.restype = ctypes.c_int64
            lib.strdict_count.argtypes = [ctypes.c_void_p]
            lib.strdict_insert.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
                ctypes.c_int64]
            lib.strdict_encode.restype = ctypes.c_int64
            lib.strdict_encode.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
                ctypes.c_int64]
            _STRDICT_LIB = lib
        except Exception:
            _STRDICT_FAILED = True
        return _STRDICT_LIB


class CsvLoader:
    """Parse CSV byte buffers into send_columns-ready column dicts.

    String columns come back dictionary-encoded; ids are remapped into the
    app's StringDictionary (one Python round trip per new unique)."""

    def __init__(self, definition, dictionary):
        self.definition = definition
        self.dictionary = dictionary
        self._lib = _lib()
        self._loader = ctypes.c_void_p(self._lib.loader_new())
        self._codes = np.array(
            [_TYPE_CODES[a.type] for a in definition.attributes], np.int32)
        # native-dict id -> app StringDictionary id
        self._remap = np.zeros(0, np.int64)

    def __del__(self):
        try:
            if self._loader:
                self._lib.loader_free(self._loader)
        except Exception:
            pass

    def _sync_dictionary(self):
        n = int(self._lib.loader_dict_size(self._loader))
        if n <= len(self._remap):
            return
        grown = np.zeros(n, np.int64)
        grown[: len(self._remap)] = self._remap
        buf = ctypes.create_string_buffer(1 << 16)
        for i in range(len(self._remap), n):
            ln = self._lib.loader_dict_get(self._loader, i, buf, len(buf))
            grown[i] = self.dictionary.encode(buf.raw[:ln].decode("utf-8"))
        self._remap = grown

    def _native_parse(self, data, out_cols, out_masks, max_rows) -> int:
        return int(self._lib.loader_parse_csv(
            self._loader, data, len(data),
            self._codes.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            len(self.definition.attributes), out_cols, out_masks, max_rows))

    def parse(self, data: bytes, max_rows: Optional[int] = None
              ) -> Tuple[Dict[str, np.ndarray], int]:
        """-> (columns dict incl. null masks, n_rows)."""
        attrs = self.definition.attributes
        ncols = len(attrs)
        if max_rows is None:
            max_rows = data.count(b"\n") + 1
        from siddhi_tpu.ops.types import dtype_of

        natives: List[np.ndarray] = []
        out_cols = (ctypes.c_void_p * ncols)()
        out_masks = (ctypes.POINTER(ctypes.c_uint8) * ncols)()
        masks: List[np.ndarray] = []
        for c, a in enumerate(attrs):
            code = self._codes[c]
            arr = np.zeros(max_rows,
                           {0: np.int64, 1: np.float64, 2: np.int64,
                            3: np.uint8}[int(code)])
            natives.append(arr)
            out_cols[c] = arr.ctypes.data_as(ctypes.c_void_p)
            mk = np.zeros(max_rows, np.uint8)
            masks.append(mk)
            out_masks[c] = mk.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        n = self._native_parse(data, out_cols, out_masks, max_rows)
        if n < 0:
            raise ValueError(f"{type(self).__name__}: parse failed")
        self._sync_dictionary()
        cols: Dict[str, np.ndarray] = {}
        for c, a in enumerate(attrs):
            v = natives[c][:n]
            if a.type == AttrType.STRING:
                v = self._remap[v]
            elif a.type == AttrType.BOOL:
                v = v.astype(bool)
            else:
                v = v.astype(dtype_of(a.type))
            cols[a.name] = v
            cols[a.name + "?"] = masks[c][:n].astype(bool)
        return cols, n


class JsonlLoader(CsvLoader):
    """Parse JSON-lines byte buffers (one flat object per line) into
    send_columns-ready column dicts — the native analog of the json
    SourceMapper for bulk ingest. Fields resolve by attribute name;
    missing keys / JSON null become null-masked."""

    def __init__(self, definition, dictionary):
        super().__init__(definition, dictionary)
        names = "".join(a.name for a in definition.attributes).encode("utf-8")
        self._names = names
        self._name_lens = np.array(
            [len(a.name.encode("utf-8")) for a in definition.attributes],
            np.int32)

    def _native_parse(self, data, out_cols, out_masks, max_rows) -> int:
        return int(self._lib.loader_parse_jsonl(
            self._loader, data, len(data), self._names,
            self._name_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            self._codes.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            len(self.definition.attributes), out_cols, out_masks, max_rows))
