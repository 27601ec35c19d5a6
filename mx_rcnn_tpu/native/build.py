"""Build the native shared library with g++ (no setuptools, no pybind11).

Usage: ``python -m mx_rcnn_tpu.native.build``; the test suite and package
import both tolerate an un-built tree (numpy fallbacks take over).

The library is NAMED by the hash of its source (``_native.<hash>.so``), so
the one that gets loaded is provably the build of the ``src/native.cc``
next to it: a library left behind by another checkout state (the file is
git-ignored, and a tool that copies the tree copies it too) has another
name, is never opened, and is removed by the next build.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys

PKG_DIR = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(PKG_DIR, "src", "native.cc")


def so_path() -> str:
    """Where the build of the CURRENT source lives (it may not exist)."""
    with open(SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(PKG_DIR, f"_native.{digest}.so")


def build(verbose: bool = True) -> str:
    # Portable ISA (no -march=native): the .so may be built once and used
    # from a shared filesystem on heterogeneous hosts; a SIGILL in the data
    # loader is worse than a few percent of scalar-loop speed.
    # Compile to a temp path + atomic rename so concurrent builders
    # (multi-process loaders, parallel test workers) never dlopen a
    # half-written file.
    out = so_path()
    tmp = f"{out}.tmp.{os.getpid()}"
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17", SRC, "-o", tmp,
    ]
    if verbose:
        print(" ".join(cmd), file=sys.stderr)
    try:
        subprocess.run(cmd, check=True)
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    # Builds of other sources are dead weight that travels with the tree.
    for name in os.listdir(PKG_DIR):
        path = os.path.join(PKG_DIR, name)
        if name.startswith("_native.") and name.endswith(".so") and path != out:
            try:
                os.unlink(path)
            except OSError:
                pass
    return out


if __name__ == "__main__":
    build()
