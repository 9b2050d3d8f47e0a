"""Build the native engine shared library with g++ (no pip deps).

Usage: ``python -m text_crdt_rust_tpu.native.build`` or just import
``text_crdt_rust_tpu.models.native`` (builds on demand).

The library is compiled with ``-march=native``, so it is keyed on the
source, the compiler flags AND the host CPU: a copy of the checkout
that lands on another machine (the chip host) finds no library under
its own key and rebuilds, instead of loading one built for this CPU.
"""
from __future__ import annotations

import hashlib
import os
import platform
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "tcr_engine.cpp")
BUILD_DIR = os.path.join(HERE, "_build")
FLAGS = ["-O3", "-std=c++17", "-fPIC", "-shared", "-march=native",
         "-fno-exceptions", "-fno-rtti"]


def _host_cpu() -> str:
    """What ``-march=native`` compiles for: the CPU model and its
    feature flags (``/proc/cpuinfo``), or the platform's own name."""
    try:
        with open("/proc/cpuinfo") as f:
            lines = [ln for ln in f if ln.startswith(("model name", "flags"))]
        return "".join(sorted(set(lines)))
    except OSError:
        return f"{platform.machine()} {platform.processor()}"


def _key() -> str:
    h = hashlib.sha256()
    with open(SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(FLAGS).encode())
    h.update(_host_cpu().encode())
    return h.hexdigest()[:16]


def lib_path() -> str:
    return os.path.join(BUILD_DIR, f"libtcr_{_key()}.so")


def build(verbose: bool = False) -> str:
    """Compile (if no library exists under this host's key) and return
    the shared-library path."""
    out = lib_path()
    if os.path.exists(out):
        return out
    os.makedirs(BUILD_DIR, exist_ok=True)
    # Compile to a temp path and rename into place so a concurrent builder
    # can never dlopen a partially written library.
    tmp = f"{out}.tmp.{os.getpid()}"
    cmd = ["g++", *FLAGS, SRC, "-o", tmp]
    if verbose:
        print(" ".join(cmd), file=sys.stderr)
    subprocess.run(cmd, check=True)
    os.rename(tmp, out)
    return out


if __name__ == "__main__":
    print(build(verbose=True))
