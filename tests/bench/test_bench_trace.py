"""The trace reduction (``bench/trace.py``) and the metric readers that
use it: on a synthetic trace whose every interval is known, and on a
trace recorded on a TPU v5e (``data/``: ``jacobi2d-16k`` run as chained
calls of 100 steps, seed 11, a 5-second window)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, trace, work  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "jacobi2d-16k.k100.xplane.pb.gz")

KERNEL_OP = ('%k.1 = f32[8,128]{1,0:T(8,128)} custom-call(f32[8,128]{1,0} '
             '%x), custom_call_target=\\"tpu_custom_call\\"')
COPY_OP = "%copy.2 = f32[8,128]{1,0:T(8,128)} copy(f32[8,128]{1,0} %y)"
PERMUTE_OP = ("%collective-permute-start.3 = (f32[4,128]{1,0}, "
              "f32[4,128]{1,0}) collective-permute-start(f32[4,128]{1,0} "
              "%z), source_target_pairs={{0,1}}")
WHILE_OP = ("%while = (s32[]{:T(128)}, f32[8,128]{1,0:T(8,128)}) "
            "while((s32[]{:T(128)}, f32[8,128]{1,0}) %t), condition=%c")


def _xspace(planes) -> str:
    """Text proto of an XSpace: ``planes`` maps a plane name to
    ``[(line name, [(event name, start_us, duration_us), ...]), ...]``."""
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names = sorted({ev[0] for _, evs in lines for ev in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        body = [f'planes {{ id: {pid} name: "{pname}"']
        for lid, (lname, evs) in enumerate(lines, 1):
            body.append(f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
            for name, start, dur in evs:
                body.append(f"    events {{ metadata_id: {ids[name]} "
                            f"offset_ps: {start * 10**6} "
                            f"duration_ps: {dur * 10**6} }}")
            body.append("  }")
        for n, i in ids.items():
            body.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}')
        body.append("}")
        out.extend(body)
    return "\n".join(out)


@pytest.fixture(scope="module")
def synthetic():
    """Window 0-100 us.  Device 0: kernel 10-40, copy 35-50 (overlaps
    the kernel by 5), permute 60-70 alone, a while op around all of it.
    Device 1: kernel 10-30 only.  Host: dispatch 0-8, block 8-95."""
    from jax.profiler import ProfileData
    planes = {
        "/device:TPU:0": [("XLA Ops", [(WHILE_OP, 5, 90),
                                       (KERNEL_OP, 10, 30),
                                       (COPY_OP, 35, 15),
                                       (PERMUTE_OP, 60, 10)]),
                          ("XLA Modules", [("jit_run(1)", 5, 90)])],
        "/device:TPU:1": [("XLA Ops", [(KERNEL_OP, 10, 20)])],
        "/host:CPU": [("python3", [("bench.window", 0, 100),
                                   ("bench.dispatch", 0, 8),
                                   ("bench.block", 8, 87),
                                   ("PjitFunction(run)", 1, 2)])],
    }
    return trace.reduce(ProfileData.from_text_proto(_xspace(planes)).planes)


def test_parse_op_reads_name_and_opcode_from_hlo_text():
    assert trace.parse_op(KERNEL_OP.replace('\\"', '"')) == (
        "k.1 (custom-call)", "custom-call")
    assert trace.parse_op(COPY_OP) == ("copy.2", "copy")
    assert trace.parse_op(PERMUTE_OP)[1] == "collective-permute-start"
    assert trace.parse_op(WHILE_OP) == ("while", "while")
    assert trace.parse_op("fusion.7") == ("fusion.7", "fusion")


def test_op_kinds_come_from_the_op_not_its_name():
    kernel = KERNEL_OP.replace('\\"', '"')
    assert trace.op_kind(kernel, "custom-call") == trace.KERNEL
    assert trace.op_kind("%x = f32[] custom-call(), custom_call_target="
                         '"Sharding"', "custom-call") == trace.GLUE
    assert trace.op_kind(PERMUTE_OP, "collective-permute-start") == (
        trace.COLLECTIVE)
    assert trace.op_kind(COPY_OP, "copy") == trace.GLUE
    assert trace.op_kind(WHILE_OP, "while") is None


def test_busy_union_idle_and_split(synthetic):
    s = synthetic
    us = 1000
    assert s.window == (0, 100 * us)
    assert sorted(s.devices) == [0, 1]
    # the while op encloses its body and is left out
    assert [o.name for o in s.devices[0]] == [
        "k.1 (custom-call)", "copy.2", "collective-permute-start.3"]
    assert s.busy_ns(0) == 50 * us              # 10-50 and 60-70
    assert s.busy_ns(1) == 20 * us
    assert s.summed_ns(0, trace.KERNEL) == 30 * us
    assert s.busy_ns(0, (trace.GLUE,)) == 15 * us
    assert s.busy_ns(0, (trace.COLLECTIVE,)) == 10 * us
    assert s.gaps(0) == [(0, 10 * us), (50 * us, 60 * us),
                         (70 * us, 100 * us)]


def test_gap_attribution_names_the_host_span(synthetic):
    s = synthetic
    assert s.span_at(5 * 1000) == "dispatch"
    assert s.span_at(55 * 1000) == "block"
    assert s.span_at(97 * 1000) == "host"
    assert s.top_gaps(0) == [["block", 30e-6], ["dispatch", 10e-6],
                             ["block", 10e-6]]
    assert s.top_ops(2) == [["k.1 (custom-call)", 25e-6], ["copy.2", 7.5e-6]]


def _run(summary, calls, cell="jacobi2d-16k.solve1000", peaks=None,
         traffic=None):
    bm = harness.benchmark()
    entry = harness.cell_entry(bm, cell)
    config = harness.load_config(entry["config"])
    traffic = traffic or harness.load_traffic(entry["traffic"])
    peaks = peaks or harness.peak_row("TPU v5 lite")
    return harness.Run(
        cell=cell, config=config, traffic=traffic, peaks=peaks,
        n_devices=len(summary.devices), setup_s=1.0, compile_s=0.5,
        calls=calls,
        point_updates_per_call=work.point_updates_per_call(config, traffic),
        bytes_per_call=work.algorithmic_bytes_per_call(config, traffic),
        trace=summary)


def _reader(name):
    return harness.load_module("metrics", name)


def test_trace_readers_on_the_synthetic_trace(synthetic):
    run = _run(synthetic, [(0.0, 0.001, 1.0)])
    assert _reader("glue_busy_share").read(run) == pytest.approx(
        100 * 15 / 70)
    assert _reader("device_idle_share").read(run) == pytest.approx(80.0)


@pytest.fixture(scope="module")
def recorded():
    return trace.load(RECORDED)


def test_recorded_trace_reduces_to_pinned_numbers(recorded):
    s = recorded
    assert sorted(s.devices) == [0]
    assert s.window_ns == 5572816968
    assert s.busy_ns(0) == 5564618031
    assert s.summed_ns(0, trace.KERNEL) == 5058160351
    assert s.busy_ns(0, (trace.GLUE,)) == 506457680
    assert s.busy_ns(0, (trace.COLLECTIVE,)) == 0
    # 6 calls of 25 fused blocks, one Mosaic kernel each
    assert sum(o.kind == trace.KERNEL for o in s.devices[0]) == 150
    assert [name for name, _ in s.top_ops(2)] == [
        "closed_call.4 (custom-call)", "copy.9"]
    gaps = s.top_gaps(0)
    assert gaps[0] == ["block", 0.002304545]
    assert {name for name, _ in gaps} <= {"block", "dispatch", "host"}


def test_recorded_trace_metrics(recorded):
    # the trace was recorded with calls of 100 steps
    run = _run(recorded, [(0.0, 0.0001, 0.9)] * 6,
               traffic={"driver": "timestep", "steps_per_call": 100})
    share = _reader("fused_stencil_roofline").read(run)
    # 6 calls x 25 blocks x (read + write of 1 GiB) over 5.058 s of
    # kernel time at 819 GB/s
    assert share == pytest.approx(100 * 150 * 2 * 2**30 / 5.058160351 / 819e9)
    assert 0 < share < 100
    assert _reader("glue_busy_share").read(run) == pytest.approx(
        100 * 506457680 / 5564618031)
    assert _reader("device_idle_share").read(run) == pytest.approx(
        100 * (1 - 5564618031 / 5572816968))


def test_roofline_reader_is_silent_without_kernels_or_peaks(synthetic):
    run = _run(synthetic, [(0.0, 0.001, 1.0)], peaks={"source": "none"})
    assert _reader("fused_stencil_roofline").read(run) is None
    assert _reader("solve_hbm_mfu").read(run) is None
    run.trace = None
    assert _reader("glue_busy_share").read(run) is None
