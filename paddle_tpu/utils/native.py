"""Build and locate the repo's native libraries (csrc/*.cc).

No binary is tracked: `native_lib(name)` runs `make` for the one target,
which (re)builds `csrc/build/lib<name>.so` from its tracked source when
the source is newer or the library is missing, and returns its path.
"""
from __future__ import annotations

import os
import subprocess

__all__ = ["CSRC_DIR", "BUILD_DIR", "native_lib"]

CSRC_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "csrc")
BUILD_DIR = os.path.join(CSRC_DIR, "build")


def native_lib(name: str) -> str:
    """Path of `csrc/build/lib<name>.so`, built first if out of date.
    Raises (with the compiler's output) when the build fails."""
    target = f"build/lib{name}.so"
    r = subprocess.run(["make", "-C", CSRC_DIR, target],
                       capture_output=True, text=True)
    if r.returncode != 0:
        raise RuntimeError(f"building csrc/{target} failed:\n{r.stderr}")
    return os.path.join(CSRC_DIR, target)
