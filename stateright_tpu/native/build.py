"""Build the native components: `python -m stateright_tpu.native.build`.

Compiles each .cpp target into a .so next to this file with g++ (no
pybind11 — the binding layers are ctypes in runtime.py / vset.py). A .so
counts as built only when its ``.sha256`` stamp names the current source's
content, so a .so built from other source is rebuilt, never loaded.
"""

from __future__ import annotations

import hashlib
import os
import shutil
import subprocess
import sys

from ..obs.log import get_logger

_log = get_logger("native.build")

_DIR = os.path.dirname(os.path.abspath(__file__))

# (source, output) pairs; each is an independent shared object.
TARGETS = {
    "core": (os.path.join(_DIR, "core.cpp"), os.path.join(_DIR, "_core.so")),
    "checker": (
        os.path.join(_DIR, "checker.cpp"),
        os.path.join(_DIR, "_checker.so"),
    ),
}

# Backwards-compatible aliases (round 1-3 callers import these).
SOURCE, OUTPUT = TARGETS["core"]


def build_one(source: str, output: str, quiet: bool = False) -> bool:
    gxx = shutil.which("g++") or shutil.which("c++")
    if gxx is None:
        if not quiet:
            _log.warning("native build: no C++ compiler found")
        return False
    # Build beside the target and rename into place, so a concurrent
    # loader never sees a half-written .so. The stamp names the source as
    # it was read for this build.
    digest = _source_digest(source)
    tmp = f"{output}.{os.getpid()}.tmp"
    cmd = [
        gxx,
        "-O2",
        "-std=c++17",
        "-shared",
        "-fPIC",
        "-o",
        tmp,
        source,
        "-lpthread",
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except Exception as e:
        if not quiet:
            _log.warning("native build failed to run", error=repr(e))
        return False
    if proc.returncode != 0:
        if not quiet:
            _log.warning(
                "native build failed", compiler=gxx, stderr=proc.stderr
            )
        return False
    os.replace(tmp, output)
    with open(_stamp(output), "w", encoding="utf-8") as fh:
        fh.write(digest)
    return True


def _stamp(output: str) -> str:
    return output + ".sha256"


def _source_digest(source: str) -> str:
    with open(source, "rb") as fh:
        return hashlib.sha256(fh.read()).hexdigest()


def build(quiet: bool = False, target: str = "core") -> bool:
    """Compile one target; returns True on success."""
    source, output = TARGETS[target]
    return build_one(source, output, quiet)


def build_all(quiet: bool = False) -> dict:
    """Build every target; returns {name: succeeded}."""
    return {name: build(quiet, name) for name in TARGETS}


def is_built(target: str = "core") -> bool:
    """True when the target's .so was built from its current source."""
    source, output = TARGETS[target]
    try:
        with open(_stamp(output), encoding="utf-8") as fh:
            stamp = fh.read().strip()
    except OSError:
        return False
    return os.path.exists(output) and stamp == _source_digest(source)


if __name__ == "__main__":
    results = build_all()
    for name, (_src, out) in TARGETS.items():
        status = "built " + out if results[name] else "BUILD FAILED"
        print(f"native {name}: {status}")
    sys.exit(0 if all(results.values()) else 1)
