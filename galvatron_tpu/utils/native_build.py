"""Build-on-first-use for the C-ABI helpers under ``csrc/``.

The shared objects are build outputs, never tracked: ``build/`` is in
``.gitignore`` and a fresh copy of the tree compiles them from ``csrc/`` the
first time they are asked for.  Staleness is decided by the CONTENT of the
``.cpp`` (its hash is part of the library's file name), not by mtime — a
copy or a checkout resets mtimes.  Callers fall back to their NumPy twins
when this returns None; a failed build says why, once, on stderr.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import tempfile
from pathlib import Path
from typing import Optional

_REPO_ROOT = Path(__file__).resolve().parents[2]
_BUILD_DIR = _REPO_ROOT / "build"


def load_native(name: str) -> Optional[ctypes.CDLL]:
    """``csrc/<name>.cpp`` → ``build/libgalvatron_<name>.<hash>.so``, built
    if that exact file is not there yet, then loaded.  None (after one
    message on stderr) when it cannot be built or loaded."""
    src = _REPO_ROOT / "csrc" / f"{name}.cpp"
    try:
        digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
        so = _BUILD_DIR / f"libgalvatron_{name}.{digest}.so"
        if not so.exists():
            _BUILD_DIR.mkdir(exist_ok=True)
            # compile beside the target and rename: several processes (xdist
            # workers, elastic children) may get here at once
            fd, tmp = tempfile.mkstemp(dir=_BUILD_DIR, suffix=".so.tmp")
            os.close(fd)
            try:
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", str(src), "-o", tmp],
                    check=True, capture_output=True, text=True, timeout=120,
                )
                os.replace(tmp, so)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        return ctypes.CDLL(str(so))
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", None) or e
        print(
            f"galvatron_tpu: native {name} unavailable, using the NumPy path: "
            f"{str(detail).strip()[:400]}",
            file=sys.stderr,
        )
        return None
