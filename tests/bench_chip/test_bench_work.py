"""The benchmark's yardstick: work counts from the configuration, the peak
table, the MSDA roofline share's independence of the backend, and the
harness's own arithmetic of rates and dispatch."""
from pathlib import Path

import pytest

from benchmarks.chip import model, work, xplane
from benchmarks.chip.harness import (AHEAD_S, HERE, WARM_BATCHES, Rec, Run,
                                    ahead_batches, metric_reader,
                                    service_gaps, warm_batch_s)

# configurations in a cell, and those kept for a later cell
PLACES = (HERE / "configs", Path(__file__).resolve().parent / "data")


def load(name):
    path = next(p / f"{name}.json" for p in PLACES
                if (p / f"{name}.json").exists())
    return model.load(path)[0]


def test_detr_512_counts_are_the_hand_arithmetic():
    """PERF.md, "Work counts": N_in = 128^2 + 64^2 + 32^2 + 16^2 = 21,760;
    an encoder MSDA call samples 21,760 x 8 heads x 16 points, 32
    channels, 10 FLOPs a channel, and moves the bf16 table (21,760 x 256
    x 2 B), 10 B of operands a point (two f32 coordinates and a bf16
    probability) and the bf16 output (21,760 x 256 x 2 B)."""
    m = load("deformable-detr")
    assert m.n_in == 21760
    enc = work.encoder_call(m, 0)
    assert enc.flops == 21760 * 8 * 16 * 32 * 10 == 891_289_600
    assert enc.bytes == (21760 * 256 * 2 + 21760 * 8 * 16 * 10
                         + 21760 * 256 * 2) == 50_135_040
    dec = work.decoder_call(m)
    assert dec.flops == 300 * 8 * 16 * 32 * 10
    assert dec.bytes == 21760 * 256 * 2 + 300 * 8 * 16 * 10 + 300 * 256 * 2
    block = (2 * 21760 * 256 * 256 * 2          # value and output
             + 2 * 21760 * 256 * 128            # attention logits
             + 2 * 21760 * 256 * 256            # offsets (16 points x 2)
             + 891_289_600                      # sampling
             + 2 * 2 * 21760 * 256 * 1024)      # FFN
    assert work.encoder_block_flops(m, 0) == block == 33_690_746_880
    backbone = 2 * 9 * 32 * (3 * 256 ** 2 + 32 * (128 ** 2 + 64 ** 2
                                                   + 32 ** 2 + 16 ** 2))
    assert m.arch.backbone_flops(m) == backbone
    assert work.flops_per_image(m) == 209_932_013_568


def test_defa_counts_keep_only_what_pruning_keeps():
    m = load("deformable-detr-defa")
    assert m.level_caps == (9830, 2458, 614, 154)
    first, later = work.encoder_call(m, 0), work.encoder_call(m, 1)
    assert first.flops == later.flops == 21760 * 8 * 4 * 32 * 10
    assert first.bytes == 21760 * 256 * 2 + 21760 * 8 * 4 * 10 \
        + 21760 * 256 * 2
    assert later.bytes == 13056 * 256 * 2 + 21760 * 4 \
        + 21760 * 8 * 4 * 10 + 21760 * 256 * 2
    assert work.flops_per_image(m) < work.flops_per_image(
        load("deformable-detr"))


def test_unknown_device_kind_is_an_error():
    assert work.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peak_for("TPU v4")


def _run_with_kernels(name, m, durations):
    """A run whose trace holds one forward of MSDA kernels called ``name``."""
    ops, t = [], 0.0
    for d in durations:
        ops.append((t, t + d * 1e9, name))
        t += d * 1e9 + 1e3
    trace = xplane.Summary(0.0, t + 1e3, {0: ops}, [],
                           {0: [(0.0, t, "jit_fwd")]})
    cell = type("Cell", (), {"model": m, "traffic": {"max_batch": 1}})
    return Run(cell=cell, seconds=1.0, setup_s=0.0, t0=0.0, t1=1.0,
               recs=[], memory_peak_bytes=0, platform="tpu",
               device_kind="TPU v5 lite", device_count=1, trace=trace)


@pytest.mark.parametrize("config", ["deformable-detr", "deformable-detr-defa"])
@pytest.mark.parametrize("kernel", ["msgs_fused", "msgs_fused_packed",
                                    "msgs_fused_packed_remap",
                                    "msgs_windowed_msp",
                                    "msgs_decode_persistent"])
def test_msda_share_is_the_same_for_every_backend(config, kernel):
    """The share counts the configuration's work, whatever kernel the plan
    picked: the same device times give the same share under every
    backend's kernel name."""
    m = load(config)
    durations = [0.05] * m.enc_layers + [0.01] * m.dec_layers
    share = metric_reader("msda_roofline_share")(
        _run_with_kernels(kernel, m, durations))
    peak = work.peak_for("TPU v5 lite")
    least = sum(w.least_seconds(peak)[0] for w in work.msda_calls(m))
    assert share == pytest.approx(100 * least / sum(durations), rel=1e-12)
    assert 0 < share < 100


def test_images_per_s_counts_the_batch_in_flight_by_its_share():
    """Batches of 4 finish at 1, 2, 3, ... s, the results of one batch a
    microsecond apart; a 2.5 s window holds two batches and half of the
    third."""
    recs = []
    for b in range(4):
        for i in range(4):
            r = Rec(index=4 * b + i, image=None, batch=b)
            r.req = type("Req", (), {"done": True,
                                     "t_done": 1.0 + b + i * 1e-6})
            recs.append(r)
    run = Run(cell=None, seconds=2.5, setup_s=0.0, t0=0.0, t1=2.5,
              recs=recs, memory_peak_bytes=0, platform="tpu",
              device_kind="TPU v5 lite", device_count=1)
    assert run.images_per_s() == pytest.approx((8 + 4 * 0.5) / 2.5)
    assert metric_reader("images_per_s")(run) == run.images_per_s()


def _warm(t_submit, t_done_first, t_done_second):
    """Two warm-up batches of 4, submitted at ``t_submit``."""
    return [type("Req", (), {"t_submit": t_submit, "t_done": t})
            for t in [t_done_first + i * 1e-6 for i in range(4)]
            + [t_done_second + i * 1e-6 for i in range(4)]]


def test_dispatch_ahead_is_six_seconds_of_warm_up_batches():
    """The warm-up's two batches of 4, submitted at 0.51 s, end 0.5 s
    apart: the window keeps 12 batches dispatched; batches of 2.26 s keep
    the floor of 2."""
    warm = _warm(0.51, 1.0, 1.5)
    assert warm_batch_s(warm, 4) == pytest.approx(0.5)
    assert ahead_batches(0.5) == 12
    assert ahead_batches(2.26) == WARM_BATCHES == 2


def test_a_misread_warm_up_dispatches_no_more_than_ahead_s():
    """Batches of 0.5 s on the device, submitted at 0; the first one's
    results held up behind a host stall until 2 ms before the second's:
    the gap reads 2 ms, yet the window keeps no more than AHEAD_S of the
    device's work dispatched."""
    batch_s = warm_batch_s(_warm(0.0, 0.998, 1.0), 4)
    assert batch_s >= 0.5
    assert ahead_batches(batch_s) * 0.5 <= AHEAD_S


def test_service_gaps_are_waits_between_back_to_back_batches():
    """Batch 2 waited 0.3 s past its predecessor on the device; batch 3 was
    dispatched only after batch 2 ended, so its wait is the host's."""
    recs = []
    for b, (disp, done) in enumerate([(0.0, 1.0), (0.1, 2.0), (0.2, 3.3),
                                      (4.0, 5.0)]):
        r = Rec(index=b, image=None, batch=b, t_dispatch=disp)
        r.req = type("Req", (), {"done": True, "t_done": done})
        recs.append(r)
    gaps = service_gaps(recs, 1.0)
    assert [i for i, _ in gaps] == [2, 1]
    assert gaps[0][1] == pytest.approx(0.3) and gaps[1][1] == pytest.approx(0)
