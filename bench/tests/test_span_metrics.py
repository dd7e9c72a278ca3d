"""The readers of the program's own spans and kernel names:
``prepare_ms_per_fit``, ``runner_builds_per_fit``,
``dispatch_idle_ms_per_fit``, ``history_idle_ms_per_fit``,
``unattributed_idle_share`` and ``lut_activation_roofline``.

``test_hand_*`` build a reduced trace by hand, with times chosen so that
every number can be worked by hand.  ``test_cpu_*`` run small cells on
the CPU, whose trace holds the host spans and no chip.
``test_recorded_*`` read ``fixtures/spans.xplane.pb``, recorded on one
v5e by ``record_spans_fixture.py``."""

import os
import types

import pytest

from bench import harness, peaks, spans
from bench import trace_reduce as tr
from bench.metrics import (dispatch_idle_ms_per_fit,
                           history_idle_ms_per_fit, lut_activation_roofline,
                           prepare_ms_per_fit, runner_builds_per_fit,
                           unattributed_idle_share)

SPAN_READERS = {"prepare_ms_per_fit": prepare_ms_per_fit,
                "runner_builds_per_fit": runner_builds_per_fit}
DEVICE_READERS = {"dispatch_idle_ms_per_fit": dispatch_idle_ms_per_fit,
                  "history_idle_ms_per_fit": history_idle_ms_per_fit,
                  "unattributed_idle_share": unattributed_idle_share,
                  "lut_activation_roofline": lut_activation_roofline}
READERS = {**SPAN_READERS, **DEVICE_READERS}

LUT = ('%lut_activation.3 = f32[8,1,256]{2,1,0:T(1,128)} custom-call('
       'f32[8,1,256]{2,1,0:T(1,128)} %get-tuple-element.17, '
       'f32[1024]{0:T(1024)S(1)} %copy-done.2), custom_call_target='
       '"tpu_custom_call", operand_layout_constraints={f32[8,1,256]'
       '{2,1,0}, f32[1024]{0}}, frontend_attributes={kernel_metadata={\n'
       '"kernel":"lut_activation"\n}}')
FXP = ('%fxp_matmul.2 = s32[8,3,256]{2,1,0:T(4,128)} custom-call('
       's8[3,32]{1,0:T(4,128)(4,1)S(1)} %add_bitcast_fusion.2, '
       's8[8,32,256]{2,1,0:T(8,128)(4,1)} %bitcast.78), '
       'custom_call_target="tpu_custom_call", frontend_attributes='
       '{kernel_metadata={\n"kernel":"fxp_matmul"\n}}')

WINDOW = (120, 800)
# two fits; the first starts before the window, the second ends after
# it.  A pim.fit nested in the first counts once.
HOST = sorted([
    (90, 850, "bench.window", "main"),
    (100, 500, "pim.fit", "main"), (200, 250, "pim.fit", "main"),
    (110, 150, "pim.prepare", "main"),
    (155, 158, "pim.runner_build", "main"),
    (160, 300, "pim.dispatch", "main"), (300, 340, "pim.history", "main"),
    (340, 400, "pim.dispatch", "main"), (400, 450, "pim.history", "main"),
    (550, 900, "pim.fit", "main"),
    (560, 590, "pim.prepare", "main"),
    (592, 596, "pim.runner_build", "main"),
    (600, 850, "pim.dispatch", "main"), (850, 880, "pim.history", "main"),
    (860, 862, "pim.runner_build", "main"),
    (600, 700, "jax.trace:runner", "jax")])
OPS = sorted([(130, 140, "%fusion.1 = f32[8]{0} fusion()"),
              (170, 290, LUT), (345, 395, FXP), (460, 470, LUT),
              (620, 780, LUT), (790, 900, LUT)])
# idle in the window: [120,130] [140,170] [290,345] [395,460] [470,620]
# [780,790]; inside a fit and no named part: [150,160] [450,460]
# [470,500] [550,560] [590,600]
UNATTRIBUTED_NS = 10 + 10 + 30 + 10 + 10
# inside pim.dispatch: [160,170] [290,300] [340,345] [395,400] [600,620]
# [780,790]; inside pim.history: [300,340] [400,450]
DISPATCH_IDLE_NS = 10 + 10 + 5 + 5 + 20 + 10
HISTORY_IDLE_NS = 40 + 50
SMALL_CFG = {"chips": 1, "n_vdpus": 8, "data": {"rows": 1948}}


def hand_ctx(devices=None, host=HOST, completed=2, traffic=None):
    devices = ([tr.Device(0, OPS, [])] if devices is None else devices)
    return types.SimpleNamespace(
        trace=tr.Trace(devices=devices, host=host, window=WINDOW),
        out={"completed": completed, "steps_per_fit": 3},
        cfg=SMALL_CFG, traffic=traffic or {"steps": 3},
        peaks=peaks.PEAKS["TPU v5 lite"])


def test_hand_span_time_is_the_union_clipped_to_the_window():
    ctx = hand_ctx()
    # [120,150] (cut by the window's start) + [560,590], over 2 fits
    assert prepare_ms_per_fit.read(ctx) == pytest.approx(60 / 2 / 1e6)
    # [160,300] + [340,400] + [600,800] (cut by the window's end)
    assert spans.ms_per_fit(ctx, spans.DISPATCH) == pytest.approx(
        400 / 2 / 1e6)
    # [300,340] + [400,450]; [850,880] lies after the window
    assert spans.ms_per_fit(ctx, spans.HISTORY) == pytest.approx(
        90 / 2 / 1e6)


def test_hand_idle_inside_spans_per_fit():
    """The chip's idle inside a span, not the span's length: the host
    waits inside ``pim.history`` while the chip runs the chunk."""
    ctx = hand_ctx()
    assert dispatch_idle_ms_per_fit.read(ctx) == pytest.approx(
        DISPATCH_IDLE_NS / 2 / 1e6)
    assert history_idle_ms_per_fit.read(ctx) == pytest.approx(
        HISTORY_IDLE_NS / 2 / 1e6)
    # a chip busy through the whole window: the mean over two halves it
    busy = tr.Device(1, [(0, 1000, "%fusion.2 = f32[8]{0} fusion()")], [])
    two = hand_ctx(devices=[tr.Device(0, OPS, []), busy])
    assert dispatch_idle_ms_per_fit.read(two) == pytest.approx(
        DISPATCH_IDLE_NS / 2 / 2 / 1e6)
    assert history_idle_ms_per_fit.read(two) == pytest.approx(
        HISTORY_IDLE_NS / 2 / 2 / 1e6)


def test_hand_runner_builds_start_in_the_window():
    # 155 and 592; the build at 860 starts after the window closed
    assert runner_builds_per_fit.read(hand_ctx()) == 1.0
    assert runner_builds_per_fit.read(hand_ctx(completed=4)) == 0.5
    no_builds = [h for h in HOST if h[2] != "pim.runner_build"]
    assert runner_builds_per_fit.read(hand_ctx(host=no_builds)) == 0.0


def test_hand_nested_spans_count_once():
    fits = spans.spans(tr.Trace([], HOST, WINDOW), "pim.fit")
    assert len(fits) == 3
    assert tr.union_ns(fits) == (500 - 120) + (800 - 550)


def test_hand_unattributed_idle():
    ctx = hand_ctx()
    w = WINDOW[1] - WINDOW[0]
    assert unattributed_idle_share.read(ctx) == pytest.approx(
        100.0 * UNATTRIBUTED_NS / w)
    # a second chip busy through the whole window halves the mean
    busy = tr.Device(1, [(0, 1000, "%fusion.2 = f32[8]{0} fusion()")], [])
    two = hand_ctx(devices=[tr.Device(0, OPS, []), busy])
    assert unattributed_idle_share.read(two) == pytest.approx(
        50.0 * UNATTRIBUTED_NS / w)


def test_hand_idle_split_adds_up():
    """Idle inside the named parts, inside a fit but no part, and
    outside every fit make up the whole idle of the window."""
    t = tr.Trace([tr.Device(0, OPS, [])], HOST, WINDOW)
    split = spans.idle_split(t)
    assert split == {spans.PREPARE: 10 + 10 + 30,
                     spans.DISPATCH: DISPATCH_IDLE_NS,
                     spans.HISTORY: HISTORY_IDLE_NS,
                     "unattributed": UNATTRIBUTED_NS, "outside": 50}
    w = WINDOW[1] - WINDOW[0]
    assert sum(split.values()) == w - tr.busy_ns(t.devices[0], WINDOW)


def test_hand_lut_roofline_by_kernel_name():
    # [170,290] + [460,470] + [620,780] + [790,800] (cut): 300 ns over
    # 2 fits of 3 steps; the fxp_matmul kernel is not counted
    assert lut_activation_roofline.kernel_ns(tr.Device(0, OPS, []),
                                             WINDOW) == (300, 4)
    per_step_s = 300 / 6 / 1e9
    # 1,948 rows (8 vDPUs of 244, less the padding rows), 8 bytes a row
    least = 8 * 1948 / 819e9
    assert lut_activation_roofline.read(hand_ctx()) == pytest.approx(
        100.0 * least / per_step_s)
    # a minibatch step looks up 16 rows on each of the 8 vDPUs
    least = 8 * 8 * 16 / 819e9
    assert lut_activation_roofline.read(hand_ctx(
        traffic={"steps": 3, "batch_size": 16})) == pytest.approx(
        100.0 * least / per_step_s)


def test_hand_no_fits_and_no_program_spans_read_nothing():
    # the readers per fit or per step; the idle share is one of the window
    for name, reader in READERS.items():
        value = reader.read(hand_ctx(completed=0))
        assert (value is None) == (name != "unattributed_idle_share")
    # a program that leaves no spans (an older checkout)
    bare = [h for h in HOST if not h[2].startswith("pim.")]
    for name, reader in READERS.items():
        value = reader.read(hand_ctx(host=bare))
        assert (value is None) == (name != "lut_activation_roofline")


def test_hand_no_device_reads_spans_alone():
    ctx = hand_ctx(devices=[])
    assert all(r.read(ctx) is None for r in DEVICE_READERS.values())
    assert all(r.read(ctx) is not None for r in SPAN_READERS.values())


@pytest.mark.parametrize("cell,builds", [("logreg-int8.gd", None),
                                         ("kmeans.lloyd", 0.0)])
def test_cpu_run_reports_the_span_metrics(small_root, cell, builds):
    traced, _ = harness.run_cell(cell, 2147483999, 0.2, True,
                                 root=small_root, require_accelerator=False)
    assert traced["correct"]
    m = traced["metrics"]
    assert set(SPAN_READERS) <= set(m)
    # no chip in a CPU trace: the device readers read nothing
    assert not set(DEVICE_READERS) & set(m)
    assert m["prepare_ms_per_fit"]["value"] > 0
    if builds is not None:
        assert m["runner_builds_per_fit"]["value"] == builds


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "spans.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(FIXTURE):
        pytest.fail(f"missing {FIXTURE}: run record_spans_fixture.py on a "
                    f"TPU")
    return tr.load(FIXTURE, window_name="bench.window")


def test_recorded_spans_inside_the_window(recorded):
    t0, t1 = recorded.window
    names = {n for s, e, n, _ in recorded.host
             if n.startswith("pim.") and t0 <= s and e <= t1}
    assert names == {spans.FIT, spans.PREPARE, spans.DISPATCH,
                     spans.HISTORY, spans.RUNNER_BUILD}
    # one logreg fit and one k-means fit, one chunk each; the logreg fit
    # builds a new runner, the k-means fit finds its own
    assert len(spans.spans(recorded, spans.FIT)) == 2
    assert len(spans.spans(recorded, spans.DISPATCH)) == 2
    assert len(spans.spans(recorded, spans.RUNNER_BUILD)) == 1


def is_lut_by_type(results, operands) -> bool:
    """The LUT sigmoid by its types: an ``f32`` block and the
    ``f32[1024]`` table to an ``f32`` block of the same shape."""
    return (len(results) == 1 and len(operands) == 2
            and results[0][0] == "f32" and operands[0] == results[0]
            and operands[1] == ("f32", (1024,)))


def test_recorded_lut_by_name_is_lut_by_type(recorded):
    dev, win = recorded.devices[0], recorded.window
    by_name = [(s, e) for s, e, n in tr.clip(dev.ops, *win)
               if lut_activation_roofline.KERNEL in n]
    by_type = [(s, e) for s, e, r, o in tr.custom_calls(dev, win)
               if is_lut_by_type(r, o)]
    assert len(by_name) == 3          # one a step of the 3-step fit
    assert by_name == by_type


def test_recorded_idle_split_adds_up(recorded):
    """On the chip's own trace the five parts make up the window's idle,
    and the idle readers read the named parts."""
    split = spans.idle_split(recorded)
    dev, win = recorded.devices[0], recorded.window
    idle = (win[1] - win[0]) - tr.busy_ns(dev, win)
    assert sum(split.values()) == pytest.approx(idle)
    ctx = types.SimpleNamespace(trace=recorded, out={"completed": 2})
    assert dispatch_idle_ms_per_fit.read(ctx) == pytest.approx(
        split[spans.DISPATCH] / 2 / 1e6)
    assert history_idle_ms_per_fit.read(ctx) == pytest.approx(
        split[spans.HISTORY] / 2 / 1e6)
    assert split[spans.DISPATCH] > 0
