"""The reduction from a profiler trace to device intervals, program and
kernel time, idle gaps and their host labels.

``test_hand_*`` use a few events whose names are copied from a TPU
v5e's trace, with times chosen so that every number can be worked by
hand.  ``test_recorded_*`` read ``fixtures/small.xplane.pb``, recorded
on one v5e by ``record_fixture.py``."""

import os

import pytest

from bench import trace_reduce as tr
from bench.metrics import fxp_matmul_roofline as fxp
from bench.metrics import kmeans_assign_roofline as km

FXP_FWD = ('%vmap__.28 = s32[2048,3,8192]{2,1,0:T(4,128)} custom-call('
           's8[3,32]{1,0:T(4,128)(4,1)S(1)} %add_bitcast_fusion.2, '
           's8[2048,32,8192]{2,1,0:T(8,128)(4,1)} %bitcast.78), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints'
           '={s8[3,32]{1,0}, s8[2048,32,8192]{2,1,0}}, frontend_attributes='
           '{kernel_metadata={}}')
LUT = ('%vmap__.29 = f32[2048,1,8192]{2,1,0:T(1,128)} custom-call('
       'f32[2048,1,8192]{2,1,0:T(1,128)} %get-tuple-element.179, '
       'f32[1024]{0:T(1024)S(1)} %copy-done.8), custom_call_target='
       '"tpu_custom_call", operand_layout_constraints={f32[2048,1,8192]'
       '{2,1,0}, f32[1024]{0}}, frontend_attributes={kernel_metadata={}}')
FXP_GRAD = ('%vmap__.30 = s32[2048,32,3]{2,1,0:T(8,128)} custom-call('
            's8[2048,32,4096]{2,1,0:T(8,128)(4,1)} %get-tuple-element.170, '
            's8[2048,4096,3]{2,1,0:T(8,128)(4,1)} %copy.45), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints'
            '={s8[2048,32,4096]{2,1,0}, s8[2048,4096,3]{2,1,0}}, '
            'frontend_attributes={kernel_metadata={}}')
KMEANS = ('%vmap__.3 = (f32[2048,8,16]{2,1,0:T(8,128)S(1)}, f32[2048,1,8]'
          '{2,1,0:T(1,128)S(1)}, f32[2048,1,1]{2,1,0:T(1,128)S(1)}) '
          'custom-call(f32[2048,4096,16]{2,1,0:T(8,128)} '
          '%get-tuple-element.100, f32[8,16]{1,0:T(8,128)S(1)} %copy-done.1, '
          'f32[2048,4096,1]{2,1,0:T(8,128)} %copy.4), custom_call_target='
          '"tpu_custom_call"')
LOOP = ('%while.3 = (s32[]{:T(128)}, f32[32]{0:T(128)}) while((s32[]'
        '{:T(128)}, f32[32]{0:T(128)}) %tuple.51), condition=%wide.region_6'
        '.14, body=%wide.region_0.13')
FUSION_A = ('%fusion.47 = (f32[2048,1,8192]{2,1,0:T(1,128)}) fusion(f32[]'
            '{:T(128)S(6)} %multiply.30), kind=kLoop, calls=%fused_computation'
            '.58')
FUSION_B = ('%fusion.48 = (f32[]{:T(128)}, f32[2048]{0:T(1024)S(1)}) fusion('
            'f32[2048,8192]{1,0:T(8,128)} %get-tuple-element.218), kind=kLoop,'
            ' calls=%fused_computation.21')

DEV = tr.Device(index=0, ops=sorted([
    (100, 900, LOOP), (120, 400, FXP_FWD), (400, 800, LUT),
    (800, 880, FXP_GRAD), (1000, 1100, FUSION_A), (1300, 1350, FUSION_B),
    (1400, 1500, KMEANS)]), modules=[
    (100, 900, "jit_runner(14501184970720192881)"),
    (1000, 1100, "jit_round(3398144308914875799)"),
    (1300, 1350, "jit_runner(12514065506589298521)")])
WINDOW = (50, 1600)
HOST = sorted([(880, 1020, "CommonPjRtLoadedExecutable::Execute", "main"),
               (1090, 1310, "bench.fit", "main"),
               (1120, 1290, "jax.trace:runner", "jax"),
               (1150, 1200, "jax.lower:runner", "jax")])


def test_hand_busy_is_the_union_of_operations():
    # [100, 900] (the loop covers the kernels) + [1000, 1100]
    # + [1300, 1350] + [1400, 1500]
    assert tr.busy_ns(DEV, WINDOW) == 800 + 100 + 50 + 100
    # a window that cuts operations counts only their inside
    assert tr.busy_ns(DEV, (500, 1050)) == 400 + 50


def test_hand_gaps_longest_first():
    assert tr.gaps(DEV, WINDOW) == [(1100, 1300), (900, 1000), (1500, 1600),
                                    (50, 100), (1350, 1400)]


def test_hand_gap_labels():
    ignore = ("bench.fit",)
    assert tr.label((1100, 1300), HOST, ignore) == "jax.trace:runner"
    assert tr.label((900, 1000), HOST, ignore) == (
        "CommonPjRtLoadedExecutable::Execute")
    assert tr.label((1500, 1600), HOST, ignore) == "none"
    # the fit annotation covers the gap whole when it is not ignored
    assert tr.label((1100, 1300), HOST) == "bench.fit"


def test_hand_kernel_time():
    assert tr.kernel_ns(DEV, WINDOW, fxp.is_kernel) == (280 + 80, 2)
    assert tr.kernel_ns(DEV, WINDOW, km.is_kernel) == (100, 1)
    # the LUT kernel is neither
    assert tr.hlo_types(LUT) == ([("f32", (2048, 1, 8192))],
                                 [("f32", (2048, 1, 8192)), ("f32", (1024,))])


def test_hand_program_time():
    assert tr.module_ns(DEV, "jit_runner", WINDOW) == (800 + 50, 2)
    assert tr.module_ns(DEV, "jit_round", WINDOW) == (100, 1)


def test_hand_top_ops_leave_loops_out():
    assert tr.top_ops(DEV, WINDOW) == [("tpu_custom_call:vmap__", 860),
                                       ("fusion", 150)]


ALL_REDUCE = ('%all-reduce.4 = f32[33]{0:T(128)} all-reduce(f32[33]{0:T(128)}'
              ' %fusion.12), channel_id=5, replica_groups={{0,1,2,3}}, '
              'to_apply=%add.clone')
AR_START = ('%all-reduce-start.1 = f32[33]{0:T(128)} all-reduce-start('
            'f32[33]{0:T(128)} %fusion.13), channel_id=6')
AR_DONE = ('%all-reduce-done.1 = f32[33]{0:T(128)} all-reduce-done('
           'f32[33]{0:T(128)} %all-reduce-start.1)')
# the fusion that feeds the all-reduce is not one itself
REDUCE_FUSION = ('%all-reduce-fusion = f32[33]{0:T(128)} fusion('
                 'f32[2048,33]{1,0} %p), kind=kLoop')
MESH_DEV = tr.Device(index=1, ops=sorted([
    (0, 1000, LOOP), (100, 300, FUSION_A), (300, 400, ALL_REDUCE),
    (350, 500, FUSION_B), (600, 650, AR_START), (640, 700, REDUCE_FUSION),
    (700, 760, AR_DONE)]), modules=[])


def test_hand_collectives_by_name():
    assert tr.is_collective(ALL_REDUCE) and tr.is_collective(AR_START)
    assert tr.is_collective(AR_DONE)
    assert not tr.is_collective(REDUCE_FUSION)
    assert not tr.is_collective(FUSION_A) and not tr.is_collective(LOOP)


def test_hand_exposed_collective_time():
    # [300, 400] less [350, 500] of FUSION_B: 50; [600, 650] less
    # [640, 700]: 40; [700, 760]: 60 (the loop spans them and is left out)
    assert tr.exposed_collective_ns(MESH_DEV, (0, 1000)) == (50 + 40 + 60, 3)
    # a window that cuts them counts only their inside
    assert tr.exposed_collective_ns(MESH_DEV, (320, 620)) == (30 + 20, 2)
    # a chip with no collective reads none
    assert tr.exposed_collective_ns(DEV, WINDOW) == (0, 0)


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "small.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(FIXTURE):
        pytest.fail(f"missing {FIXTURE}: run record_fixture.py on a TPU")
    return tr.load(FIXTURE, window_name="bench.window")


def test_recorded_window_and_chip(recorded):
    assert len(recorded.devices) == 1
    assert recorded.window_ns > 0
    busy = tr.busy_ns(recorded.devices[0], recorded.window)
    assert 0 < busy < recorded.window_ns


def test_recorded_kernels_by_their_types(recorded):
    dev, win = recorded.devices[0], recorded.window
    # int8 logreg, 3 steps: a forward and a gradient product a step (512
    # rows a vDPU fit one K-chunk); k-means, 2 iterations: one call each
    assert tr.kernel_ns(dev, win, fxp.is_kernel)[1] == 3 * 2
    assert tr.kernel_ns(dev, win, km.is_kernel)[1] == 2
    lut = [c for c in tr.custom_calls(dev, win)
           if not fxp.is_kernel(*c[2:]) and not km.is_kernel(*c[2:])]
    assert len(lut) == 3
    assert all(ops[-1] == ("f32", (1024,)) for _, _, _, ops in lut)


def test_recorded_programs_and_gaps(recorded):
    dev, win = recorded.devices[0], recorded.window
    # one scan runner per fit (3 steps and 2 iterations, one chunk each)
    assert tr.module_ns(dev, "jit_runner", win)[1] == 2
    # the longest idle stretch is the int8 fit's re-trace of its runner
    longest = tr.gaps(dev, win)[0]
    assert tr.label(longest, recorded.host,
                    ("bench.window", "bench.fit")) == "PjitFunction(runner)"
