"""The one warm start: JAX's persistent compile cache, placed by
`paddle_tpu/device/__init__.py`.

Shown across real processes, for both decode families: a second process
over the directory a first one filled compiles nothing anew (it adds no
entry to the cache), serves the same tokens, holds its pools in the layout
its programs were compiled for (PR 28: an executable handed back from a
cache returns its pools in the default layout whatever it was compiled
for, so `_check_pool_layout("warmed")` is what a warm process must pass),
and a supervised restart there moves neither the compile ledger nor the
cache.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "warm_start_worker.py")
FAMILIES = ("gpt", "latent")


def _start(family, cache):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    return subprocess.Popen([sys.executable, WORKER, family], env=env,
                            cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _report(proc):
    try:
        out, err = proc.communicate(timeout=150)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise
    assert proc.returncode == 0, err[-4000:]
    return json.loads(out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """{family: (cold report, warm report)}: the families side by side,
    each family's second process after its first has exited."""
    caches = {f: tmp_path_factory.mktemp(f"jax_cache_{f}") for f in FAMILIES}

    def one_process_each():
        procs = {f: _start(f, caches[f]) for f in FAMILIES}
        return {f: _report(p) for f, p in procs.items()}

    cold, warm = one_process_each(), one_process_each()
    return {f: (cold[f], warm[f]) for f in FAMILIES}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_warm_process_compiles_nothing_anew_and_serves_the_same_tokens(
        runs, family):
    cold, warm = runs[family]
    assert cold["cache_before"] == [] and cold["cache_added"]
    assert warm["cache_before"] == cold["cache_added"]
    assert warm["cache_added"] == []
    assert warm["tokens"] == cold["tokens"]
    # the ledger counts traces, one a program in either process
    assert warm["compiles"] == cold["compiles"] == {
        "prefill[b=8]": 1, "decode[m=2]": 1}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_warm_process_holds_its_pools_as_compiled_for(runs, family):
    for run in runs[family]:
        assert len(run["pools"]) == {"gpt": 2, "latent": 1}[family]
        for pool in run["pools"] + run["restart"]["pools"]:
            assert pool["layout"] == pool["compiled_for"]


@pytest.mark.parametrize("family", FAMILIES)
def test_a_supervised_restart_in_a_warm_process_moves_no_ledger(
        runs, family):
    for run in runs[family]:
        restart = run["restart"]
        assert restart["restarts"] == 1
        assert restart["compiles"] == run["compiles"]
        assert restart["cache_added"] == []
        assert restart["tokens"] == run["tokens"]
