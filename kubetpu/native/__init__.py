"""Native runtime components — compile-on-first-use C++ extensions.

The reference's runtime is compiled code end to end (Go binaries + native
etcd); kubetpu's device path is XLA-compiled, and THIS package supplies the
native host-runtime pieces: currently the store core
(``memstore_core.cpp`` — the versioned object map + watch ring behind
``kubetpu.store.MemStore``).

Build model: ``g++ -O2 -shared -fPIC`` against the running CPython's
headers, cached under ``.native_cache/`` next to this package (keyed by a
hash of the source + python version, so a copied or checked-out tree can
never load an artifact built from other source). No pip, no pybind11 — the
CPython C API only (environment contract). A missing compiler or
``KUBETPU_NO_NATIVE=1`` falls back to the pure-Python implementation with
identical semantics; the store test suite exercises the same contract
against both backends. ``build_status`` says which of the two happened.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig

_CACHE: dict[str, object] = {}
#: name -> "cached" | "built" | "disabled" | "failed: <reason>" — how the
#: last ``load_extension(name)`` in this process ended
_STATUS: dict[str, str] = {}


def _build_dir() -> str:
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".native_cache")
    os.makedirs(d, exist_ok=True)
    return d


def _so_path(name: str, src: str) -> str:
    tag = f"{sys.version_info.major}{sys.version_info.minor}"
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_build_dir(), f"{name}.py{tag}.{digest}.so")


def load_extension(name: str, source_file: str):
    """Compile (if needed) and import the named CPython extension; returns
    the module or None when native is disabled/unbuildable. EVERY failure
    mode (read-only package dir, missing compiler, concurrent build, torn
    artifact) degrades to the Python fallback — never a startup crash —
    but is reported once on stderr and kept in ``build_status``."""
    if os.environ.get("KUBETPU_NO_NATIVE"):
        _STATUS[name] = "disabled"
        return None
    if name in _CACHE:
        return _CACHE[name]
    try:
        mod, _STATUS[name] = _load_extension(name, source_file)
    except Exception as e:
        _STATUS[name] = f"failed: {type(e).__name__}: {e}"
        print(f"kubetpu.native: {name} unavailable "
              f"({type(e).__name__}: {e}); using the Python fallback",
              file=sys.stderr)
        mod = None
    _CACHE[name] = mod
    return mod


def _load_extension(name: str, source_file: str):
    """(module, "cached" | "built"); raises with the reason otherwise."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       source_file)
    so = _so_path(name, src)
    status = "cached"
    if not os.path.exists(so):
        include = sysconfig.get_paths()["include"]
        # build to a per-process temp name, then atomically rename: two
        # processes racing the first build can never leave (or load) a
        # torn .so under the cached name
        tmp = f"{so}.tmp.{os.getpid()}"
        cmd = [
            "g++", "-O2", "-std=c++17", "-shared", "-fPIC",
            f"-I{include}", src, "-o", tmp,
        ]
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"g++ exited {proc.returncode}:\n{proc.stderr[-2000:]}"
            )
        os.replace(tmp, so)
        status = "built"
    spec = importlib.util.spec_from_file_location(name, so)
    if spec is None or spec.loader is None:
        raise ImportError(f"no loader for {so}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, status


def build_status(name: str = "_kubetpu_store") -> str:
    """How this process came by extension ``name``: "cached" (artifact for
    this exact source found), "built" (compiled now), "disabled"
    (``KUBETPU_NO_NATIVE``), "failed: <reason>" (build or import was
    attempted and failed — the Python fallback is serving), or "" when
    nothing has asked for it yet."""
    return _STATUS.get(name, "")


def store_core():
    """The native StoreCore class, or None (fallback to pure Python)."""
    mod = load_extension("_kubetpu_store", "memstore_core.cpp")
    return getattr(mod, "StoreCore", None) if mod is not None else None
