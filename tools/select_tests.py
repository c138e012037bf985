"""CI test pruning (reference `tools/get_pr_ut.py` + `parallel_UT_rule.py`:
map changed files to the unit tests that must run).

Usage:
    python tools/select_tests.py [--base REF]      # print test files
    python tools/select_tests.py --run [--base REF]

Heuristics (mirroring the reference's file→UT mapping):
  * a changed test file selects itself
  * a changed `paddle_tpu/<pkg>/...` module selects every test whose
    source mentions the package or any changed module's basename
  * csrc/ or build files select the native-backed tests
  * anything unmapped (docs touching nothing) selects nothing;
    `--fallback-all` selects the whole suite instead
"""
from __future__ import annotations

import argparse
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.join(REPO, "tests")

NATIVE_TESTS = {"test_capi.py", "test_ps.py", "test_host_embedding.py"}


def changed_files(base: str):
    out = subprocess.run(["git", "diff", "--name-only", base, "--"],
                         cwd=REPO, capture_output=True, text=True,
                         check=True).stdout
    return [l.strip() for l in out.splitlines() if l.strip()]


def select(changed):
    tests = sorted(f for f in os.listdir(TESTS)
                   if f.startswith("test_") and f.endswith(".py"))
    picked = set()
    tokens = set()
    for path in changed:
        name = os.path.basename(path)
        if path.startswith("tests/") and name in tests:
            picked.add(name)
        elif path.startswith("csrc/") or name in ("Makefile", "setup.py"):
            picked |= NATIVE_TESTS
        elif path == "paddle_tpu/__init__.py":
            # the package root wires the whole public surface — no token
            # heuristic is safe, run everything
            return sorted(tests)
        elif path.startswith("paddle_tpu/") and path.endswith(".py"):
            parts = path.split("/")
            if len(parts) > 2:
                tokens.add(parts[1])                  # package dir
            tokens.add(os.path.splitext(name)[0])     # module basename
    if tokens:
        pat = re.compile("|".join(re.escape(t) for t in tokens if t
                                  not in ("__init__",)))
        for t in tests:
            with open(os.path.join(TESTS, t)) as f:
                if pat.search(f.read()):
                    picked.add(t)
    return sorted(picked)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--base", default="HEAD~1")
    ap.add_argument("--run", action="store_true")
    ap.add_argument("--fallback-all", action="store_true")
    args = ap.parse_args(argv)

    picked = select(changed_files(args.base))
    if not picked and args.fallback_all:
        picked = ["tests"]
    else:
        picked = [os.path.join("tests", t) for t in picked]
    if not picked:
        print("no tests selected")
        return 0
    try:
        print("\n".join(picked))
    except BrokenPipeError:
        pass
    if args.run:
        return subprocess.call([sys.executable, "-m", "pytest", "-q",
                                *picked], cwd=REPO)
    return 0


if __name__ == "__main__":
    sys.exit(main())
