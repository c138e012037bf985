"""CPU tests of the two readers of the engine's own device timeline,
`decode_device_ms.serve` and `device_idle_share.serve`: hand-made records
against hand-worked numbers, None where the window ran no decode step, and
NO_RECORD for records from a program that keeps no such timeline (the
parent's, under these benchmark files), whose traced line must still pass
`check_line`. Nothing here measures anything."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check_line, run  # noqa: E402
from benchmark.program_records import NO_RECORD  # noqa: E402

NAMES = ("decode_device_ms.serve", "device_idle_share.serve")
SERVE = ("gpt2-xl.batch-saturated", "glm-4.7-flash.reasoning-saturated",
         "falcon-h1-34b.chat-saturated")


def reader(name):
    return run.load_by_name("metrics", name)


def rec(decode, dev, wall, idle=0.0, prefill_dev=0.0):
    return {"decode_ms": decode, "decode_dev_ms": dev,
            "prefill_dev_ms": prefill_dev, "dev_idle_ms": idle,
            "dev_idle_by": {"generation::step": idle} if idle else {},
            "attr_wall_ms": wall}


def older(r):
    return {k: v for k, v in r.items() if not k.startswith(("decode_dev",
                                                            "prefill_dev",
                                                            "dev_idle"))}


STEPS = [rec(21.0, 20.7, 21.0),
         rec(24.0, 20.8, 40.0, idle=2.5, prefill_dev=16.7),
         rec(0.0, 0.0, 18.0, prefill_dev=16.8),     # admitted, no step read
         rec(21.1, 20.9, 21.1),
         rec(22.0, 20.6, 22.0, idle=1.4)]


def test_decode_device_ms_is_the_median_over_decode_iterations():
    # 20.7, 20.8, 20.9, 20.6 -> 20.75; the prefill-only record is left out
    assert reader(NAMES[0]).read({"steps": STEPS}) == pytest.approx(20.75)


def test_device_idle_share_is_a_ratio_of_sums_over_every_record():
    assert reader(NAMES[1]).read({"steps": STEPS}) == pytest.approx(
        100.0 * 3.9 / 122.1)


@pytest.mark.parametrize("name", NAMES)
def test_a_window_without_a_decode_step_reads_none(name):
    assert reader(name).read({"steps": [STEPS[2]]}) is None
    assert reader(name).read({"steps": []}) is None


@pytest.mark.parametrize("name", NAMES)
def test_records_from_before_the_timeline_read_no_record(name):
    assert reader(name).read({"steps": [older(r) for r in STEPS]}) \
        == NO_RECORD


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_two_metrics_are_entries_for_the_three_serve_cells():
    """Looked up by name: a later PR may append entries after them."""
    m = manifest()
    two = [p for p in m["per_layer"] if p["name"] in NAMES]
    assert [p["name"] for p in two] == list(NAMES)
    for p, unit, layer in zip(two, ("ms", "%"),
                              ("engine programs",
                               "engine programs (host)")):
        assert (p["unit"], p["layer"], p["better"], p["source"],
                p["moves"]) == (unit, layer, "lower", "program_span",
                                "serve_tokens_per_s")
        assert p["workloads"] == list(SERVE)
    check_line.check_manifest(m, ROOT)


@pytest.mark.parametrize("cell", SERVE)
def test_a_parents_traced_line_passes_with_no_record(cell):
    """The parent's records lack the fields: both readers give NO_RECORD,
    which is finite, so the line the parent prints under these files
    passes `check_line`."""
    m = manifest()
    metrics = {p["name"]: {"value": 1.0, "unit": p["unit"]}
               for p in check_line.metrics_of(m, cell, "per_layer")}
    for name in NAMES:
        metrics[name]["value"] = reader(name).read(
            {"steps": [older(r) for r in STEPS]})
    line = {"correct": True, "attempted": 1, "failed": 0,
            "metrics": metrics,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1, "busy_s": 1.0,
                       "window_s": 2.0}}
    check_line.check_line(m, cell, 1, line)
