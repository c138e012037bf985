"""tools/trace_report.py on hand-made planes: the grouping by program, by
name stack and by program span, and the clock offset taken from the trace
itself. The planes are in the shape benchmark/trace_reduce.load gives."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import trace_report as tr  # noqa: E402

MS = 1e6    # the trace's times are nanoseconds


@pytest.mark.parametrize("stack, depth, scope", [
    ("jit(train_step)/jit(main)/jvp(forward)/ErnieForPretraining/ernie/"
     "encoder/layers/3/self_attn/q_proj/dot_general", 6,
     "jvp(forward)/ErnieForPretraining/ernie/encoder/layers/*"),
    ("jit(train_step)/transpose(jvp(forward))/ErnieForPretraining/ernie/"
     "encoder/layers/11/linear1/dot_general", 7,
     "transpose(jvp(forward))/ErnieForPretraining/ernie/encoder/layers/*/"
     "linear*"),
    ("jit(gen_decode)/layer_17/attn/kv_gather/jit(_take)/gather", 6,
     "layer_*/attn/kv_gather/_take"),
    ("jit(gen_decode)/layer_17/attn/kv_gather/gather", 2, "layer_*/attn"),
    # the latent family's scopes (PR 27): one row a mechanism, whatever
    # the layer
    ("jit(gen_decode)/layer_5/mla/latent_attend/dot_general", 7,
     "layer_*/mla/latent_attend"),
    ("jit(gen_decode)/layer_5/mla/latent_write/scatter", 7,
     "layer_*/mla/latent_write"),
    ("jit(gen_decode)/layer_3/moe/experts/ragged_dot", 7,
     "layer_*/moe/experts"),
    ("jit(gen_prefill)/layer_6/moe/dispatch/jit(argsort)/sort", 7,
     "layer_*/moe/dispatch/argsort"),
    ("jit(gen_decode)/layer_0/mlp/dot_general", 7, "layer_*/mlp"),
    ("jit(train_step)/optimizer/add", 6, "optimizer"),
    ("jit(train_step)/jit(main)/add", 6, ""),
    ("", 6, ""),
])
def test_a_scope_is_the_leading_levels_of_the_name_stack(
        monkeypatch, stack, depth, scope):
    monkeypatch.setattr(tr, "DEPTH", depth)
    assert tr.scope_of(stack) == scope


def test_the_name_stack_is_found_among_an_events_statistics():
    assert tr.stack_of({"tf_op": "jit(f)/layer_0/attn/dot_general:"}) \
        == "jit(f)/layer_0/attn/dot_general"
    assert tr.stack_of({"long_name": '%fusion.3 = f32[8]{0} fusion(%p), '
                        'metadata={op_name="jit(f)/lm_head/dot_general"}'}) \
        == "jit(f)/lm_head/dot_general"
    assert tr.stack_of({"hlo_category": "convolution", "flops": 12}) == ""
    assert tr.program_of("jit_gen_decode(123456789)") == "gen_decode"


def planes(offset_ms=1.0, runs=4):
    """A decode loop: each `generation::step` span launches one
    `jit_gen_decode` run of 8 ms (two operations) 0.3 ms after the span
    starts and reads its tokens back; the host then works 2 ms under
    `generation::record` before the next step. The device's clock reads
    `offset_ms` early. Returns (planes, metadata, runs, host events with a
    run_id): each run is enqueued 0.05 ms before it starts and its
    completion is seen 0.1 ms after it ends."""
    host, feeder, modules, ops, stacks = [], [], [], [], {}
    ids, stamped = {}, []
    t = 10 * MS
    for i in range(runs):
        launch = t
        run0 = launch + 0.3 * MS - offset_ms * MS
        host.append((f"generation::step[m=16]", launch, launch + 8.5 * MS))
        host.append(("PjitFunction(gen_decode)", launch, launch + 0.2 * MS))
        modules.append((f"jit_gen_decode({7})", run0, run0 + 8 * MS))
        ids[str(i)] = (run0, run0 + 8 * MS)
        stamped.append((tr.ENQUEUE, str(i), launch + 0.25 * MS,
                        launch + 0.29 * MS))
        stamped.append((tr.COMPLETE, str(i), launch + 8.4 * MS,
                        launch + 8.45 * MS))
        ops.append(("%fusion.1 = f32[8] fusion(f32[8] %p)", run0,
                    run0 + 6 * MS))
        ops.append(("%copy.14 = f32[8] copy(f32[8] %rest_0_.1)",
                    run0 + 6 * MS, run0 + 8 * MS))
        host.append(("generation::record", launch + 8.5 * MS,
                     launch + 10.5 * MS))
        # another thread's span over the whole iteration: second choice
        feeder.append(("feeder::fetch", launch, launch + 10.5 * MS))
        t = launch + 10.5 * MS
    stacks = {
        ops[0][0]: ("jit(gen_decode)/layer_3/attn/kv_attend/dot_general",
                    "loop fusion"),
        ops[1][0]: ("", "data formatting")}
    return [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host},
            {"name": "python", "events": feeder}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": tr.MODULE_LINE, "events": modules},
            {"name": tr.OP_LINE, "events": ops}]},
    ], stacks, ids, stamped


def test_device_time_goes_to_programs_scopes_and_the_unscoped_row(
        monkeypatch):
    monkeypatch.setattr(tr, "DEPTH", 3)
    pl, stacks, ids, stamped = planes()
    r = tr.reduce(pl, stacks, ids, stamped)
    assert r["programs"] == {"gen_decode": {"runs": 4,
                                            "ms": pytest.approx(32.0)}}
    assert r["scopes_ms"] == {
        "layer_*/attn/kv_attend": pytest.approx(24.0),
        "unscoped: data formatting": pytest.approx(8.0)}
    assert r["unscoped_share"] == pytest.approx(0.25)
    assert r["busy_ms"] == pytest.approx(32.0)


def test_a_gap_is_laid_to_the_span_that_covers_it_after_the_shift():
    pl, stacks, ids, stamped = planes(offset_ms=1.0)
    r = tr.reduce(pl, stacks, ids, stamped)
    g = r["gaps"]
    # three gaps of 2.5 ms between four runs: 0.2 ms of read-back after the
    # run, 2 ms of `generation::record`, 0.3 ms of launch
    assert g["count"] == 3 and g["idle_ms"] == pytest.approx(7.5)
    assert g["by_span_ms"] == {"generation::record": pytest.approx(7.5)}
    assert g["named_share"] == 1.0
    # unshifted, the same midpoints would fall a millisecond earlier
    text = tr.render(r)
    assert "jit_gen_decode" in text and "generation::record" in text
    assert "device reads 0.950 ms early" in text


def test_run_ids_pair_a_run_with_its_own_enqueue_and_completion():
    pl, stacks, ids, stamped = planes(offset_ms=1.0, runs=3)
    stamped.append((tr.ENQUEUE, "no such run", 0.0, 1.0))
    r = tr.reduce(pl, stacks, ids, stamped)
    # enqueued 0.05 ms before the start, seen 0.1 ms after the end: that
    # much of the 1 ms cannot be told from the trace, on either side
    assert r["offset_pairs"] == 3
    assert r["clock_offset_ms"] == pytest.approx(0.95)
    assert r["clock_offset_max_ms"] == pytest.approx(1.1)
    assert "paired by run_id; at most 1.100 ms" in tr.render(r)
    r = tr.reduce(pl, stacks, {}, [])   # a trace that stamps no run
    assert (r["clock_offset_ms"], r["offset_pairs"]) == (0.0, 0)
    assert "0 taken" in tr.render(r)


def test_an_operation_the_compiler_added_takes_its_producers_scope(
        monkeypatch):
    monkeypatch.setattr(tr, "DEPTH", 3)
    pl, stacks, ids, stamped = planes(runs=3)
    ops = pl[1]["lines"][1]["events"]
    for i, (n, s, e) in enumerate(ops):
        if n.startswith("%copy.14"):    # now a copy of the fusion's output
            ops[i] = ("%copy.16 = f32[8] copy(f32[8] %fusion.1)", s, e)
    stacks["%copy.16 = f32[8] copy(f32[8] %fusion.1)"] = (
        "", "data formatting")
    r = tr.reduce(pl, stacks, ids, stamped)
    assert r["scopes_ms"] == {"layer_*/attn/kv_attend": pytest.approx(24.0)}
    assert r["unscoped_share"] == 0.0


def test_a_gap_goes_to_the_launching_threads_span_before_any_others():
    pl, stacks, ids, stamped = planes(offset_ms=1.0)
    # the launching thread's record span is gone: the other thread's counts
    pl[0]["lines"][0]["events"] = [
        ev for ev in pl[0]["lines"][0]["events"]
        if ev[0] != "generation::record"]
    g = tr.reduce(pl, stacks, ids, stamped)["gaps"]
    assert set(g["by_span_ms"]) == {"feeder::fetch"}
    assert g["named_share"] == 1.0


def test_custom_calls_are_listed_by_their_own_names():
    pl, stacks, ids, stamped = planes(runs=3)
    ops = pl[1]["lines"][1]["events"]
    kernel = ("%flash_bwd_dkv.16 = (bf16[384,512,64]{2,1,0}) custom-call("
              "bf16[384,512,64]{2,1,0} %a), custom_call_target="
              "\"tpu_custom_call\"")
    fusion = ops[0][0]
    for i, (n, s, e) in enumerate(ops):
        if n == fusion:
            ops[i] = (kernel, s, e)
    r = tr.reduce(pl, stacks, ids, stamped)
    assert r["kernels_ms"] == {"flash_bwd_dkv": pytest.approx(18.0)}
    # an operation that only READS a custom call's output is no kernel
    assert not tr._CUSTOM_CALL.search(
        "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %custom-call.44)")


def test_metadata_and_run_ids_come_from_one_pass_over_the_wire_format(
        tmp_path):
    from paddle_tpu.onnx.wire import (field_bytes, field_string,
                                      field_varint)

    def entry(key, value):
        return field_varint(1, key) + field_bytes(2, value)

    def names(*pairs):
        return b"".join(field_bytes(5, entry(k, field_varint(1, k)
                                             + field_string(2, n)))
                        for k, n in pairs)

    def event(metadata_id, offset_ps, duration_ps, *stats):
        return field_bytes(4, field_varint(1, metadata_id)
                           + field_varint(2, offset_ps)
                           + field_varint(3, duration_ps)
                           + b"".join(field_bytes(4, st) for st in stats))
    fusion = (field_varint(1, 7) + field_string(2, "%fusion.1 = f32[8]")
              + field_bytes(5, field_varint(1, 1)
                            + field_string(5, "jit(f)/layer_0/mlp/add:"))
              + field_bytes(5, field_varint(1, 2) + field_varint(7, 3)))
    module = field_varint(1, 8) + field_string(2, "jit_f(99)")
    chip = (field_string(2, "/device:TPU:0")
            # the run: 2 ms in, 8 ms long, run_id 41 as a uint64 stat; the
            # operations' line is `load`'s to read, not this pass's
            + field_bytes(3, field_string(2, tr.MODULE_LINE) + event(
                8, 2_000_000_000, 8_000_000_000,
                field_varint(1, 4) + field_varint(3, 41)))
            + field_bytes(3, field_string(2, tr.OP_LINE) + event(
                7, 2_000_000_000, 8_000_000_000,
                field_varint(1, 4) + field_varint(3, 41)))
            + field_bytes(4, entry(7, fusion)) + field_bytes(4, entry(8, module))
            + names((1, "tf_op"), (2, "hlo_category"), (3, "loop fusion"),
                    (4, "run_id")))
    # the host's line starts at 1 ms; run_id as an int64 stat there
    host = (field_string(2, "/host:CPU")
            + field_bytes(3, field_varint(3, 1_000_000)
                          + event(1, 500_000_000, 100_000_000,
                                  field_varint(1, 9) + field_varint(4, 41))
                          + event(2, 600_000_000, 100_000_000,
                                  field_varint(1, 9) + field_varint(4, 41))
                          + event(3, 9_500_000_000, 50_000_000,
                                  field_varint(1, 9) + field_varint(4, 41)))
            + field_bytes(4, entry(1, field_string(2, tr.ENQUEUE)))
            + field_bytes(4, entry(2, field_string(2, "SomethingElse")))
            + field_bytes(4, entry(3, field_string(2, tr.COMPLETE)))
            + names((9, "run_id")))
    other = field_string(2, "/device:TPU:1") + field_bytes(4, entry(7, fusion))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field_bytes(1, host) + field_bytes(1, other)
                     + field_bytes(1, chip))
    metadata, runs, launches = tr.read_wire(str(path), "/device:TPU:0")
    assert metadata == {
        "%fusion.1 = f32[8]": ("jit(f)/layer_0/mlp/add", "loop fusion"),
        "jit_f(99)": ("", "")}
    assert runs == {41: (2 * MS, 10 * MS)}
    assert launches == [(tr.ENQUEUE, 41, 1.5 * MS, 1.6 * MS),
                        (tr.COMPLETE, 41, 10.5 * MS, 10.55 * MS)]
    # the run starts 0.5 ms after its enqueue and ends 0.5 ms before its
    # completion is seen: the device's clock is not early here
    assert tr.clock_offset(runs, launches) == (0.0, 1, 0.5 * MS)
    assert tr.read_wire(str(path), "/device:TPU:9") == ({}, {}, launches)


def test_the_await_lag_pairs_each_timed_run_with_the_wait_for_it():
    """The engine's watcher ends `generation::await` 0.05-2 ms after each
    run's true end (launch + 8.3 ms on the host's clock); the trace's offset
    is 0.95 ms where the true one is 1 ms, so each lag reads 0.05 ms long,
    and the completion callbacks, seen 0.1 ms after the end, read 0.1 ms
    short. A wait for a run before the trace and an untimed program are
    paired with nothing."""
    pl, stacks, ids, stamped = planes(offset_ms=1.0, runs=4)
    modules = pl[1]["lines"][0]["events"]
    lags = (0.05, 0.1, 0.15, 2.0)
    awaits = [(tr.AWAIT, 0.0, 9 * MS)]      # a wait for a run before
    for (n, s, e), lag in zip(list(modules), lags):
        awaits.append((tr.AWAIT, s, e + 1 * MS + lag * MS))
        modules.append(("jit_gen_zero_pages(3)", e, e + 0.1 * MS))
    pl[0]["lines"].append({"name": "python", "events": awaits})
    r = tr.reduce(pl, stacks, ids, stamped)
    a = r["await_lag_ms"]
    assert set(a) == {"gen_decode"} and a["gen_decode"]["runs"] == 4
    assert a["gen_decode"]["median"] == pytest.approx(0.175)
    assert a["gen_decode"]["p95"] == pytest.approx(0.2 + 0.85 * 1.85)
    assert a["gen_decode"]["complete_median"] == pytest.approx(0.025)
    text = tr.render(r)
    assert "jit_gen_decode x4: device median 0.175" in text
    # the first run's wait began before the trace did: that run is left
    # out, and the others keep their own waits
    del awaits[1]
    a = tr.reduce(pl, stacks, ids, stamped)["await_lag_ms"]["gen_decode"]
    assert a["runs"] == 3 and a["median"] == pytest.approx(0.2)
    # a trace without the engine's watcher prints no such table
    assert tr.reduce(*planes())["await_lag_ms"] == {}


def test_a_trace_without_a_chip_is_refused_by_name(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(SystemExit, match="no plane named /device:TPU:"):
        tr.main([str(tmp_path)])
    with pytest.raises(SystemExit, match="no .xplane.pb under"):
        tr.main([str(tmp_path / "nothing")])
