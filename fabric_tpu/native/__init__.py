"""Native (C) components of the framework, built on demand.

The reference leans on C-backed machinery for its hot paths (protobuf,
LevelDB, cgo PKCS#11 — SURVEY.md §2.1); this package holds the
TPU-native framework's equivalents.  Extensions are compiled lazily on
first import with the system compiler and cached next to their sources;
set FABRIC_TPU_NO_NATIVE=1 to force the pure-Python fallbacks.

Current extensions:
  _ftlv        — the canonical serde codec (fabric_tpu/utils/serde.py)
  _fastcollect — txvalidator pass-1 block walker + SHA-256 (SHA-NI)
  _fastparse   — zero-copy wire ingest: block/envelope span parser
  _fastmvcc    — the commit's MVCC walk over a lane table's arrays, and
                 the state store's key hash (ledger/mvcc.py, statedb.py)
"""

from __future__ import annotations

import importlib
import logging
import os
import subprocess
import sysconfig

logger = logging.getLogger("fabric_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))


def _build(name: str):
    src = os.path.join(_DIR, f"{name[1:]}.c")
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    so = os.path.join(_DIR, name + suffix)
    if (not os.path.exists(so)
            or os.path.getmtime(so) < os.path.getmtime(src)):
        cc = os.environ.get("CC", "cc")
        inc = sysconfig.get_path("include")
        tmp = so + f".tmp{os.getpid()}"
        # warnings are errors: a diagnostic in accelerator-adjacent C is
        # a bug report, and silent ones rot (tests/smoke.sh also runs an
        # ASan/UBSan build of the parser over the fuzz corpus)
        cmd = [cc, "-O3", "-shared", "-fPIC",
               "-Wall", "-Wextra", "-Werror",
               f"-I{inc}", src, "-o", tmp]
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, so)    # atomic: concurrent builders race benignly
    return importlib.import_module(f"fabric_tpu.native.{name}")


def load(name: str):
    """Import a native extension, (re)building it if the source is newer
    than the cached .so.  Returns the module or None (unavailable /
    disabled)."""
    if os.environ.get("FABRIC_TPU_NO_NATIVE") == "1":
        return None
    try:
        # always go through _build: it checks source-vs-.so mtimes, so a
        # source edit invalidates the cache (importing first would pin a
        # stale build for every new process)
        return _build(name)
    except Exception as exc:
        logger.warning("native extension %s unavailable (%s); using "
                       "pure-Python fallback", name, exc)
        return None
