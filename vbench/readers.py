"""Readers that several per-layer metrics share; each metric's own file
under ``metrics/`` names the one it uses."""

from __future__ import annotations

from vbench import stats

KERNEL = "vita_layer"


def dispatch_ms(run):
    """Median host time of one ``VisionServer.dispatch`` in the window:
    stack, pad, place and enqueue one micro-batch (harness span)."""
    d = run.durations("vbench.dispatch")
    return stats.percentile(d, 50) * 1e3 if d else None


def step_mfu(run):
    """Model operations of the images answered in the window, over the
    window, the chips and the peak rate of the configuration's arithmetic
    (bfloat16 for float, int8 for int8), in percent."""
    done = sum(1 for r in run.requests if run.in_window(r.t_done))
    if not done:
        return None
    ops = done * run.work.model_ops_per_image(run.geometry)
    peak = run.peaks[run.arithmetic] * run.chips
    return 100.0 * ops / (run.seconds * peak)


def vita_layer_roofline(run):
    """The least time the chip could take for the encoder-layer kernel
    calls of the traced window, max(operations / peak, bytes / HBM
    bandwidth) per call and chip, over their summed device time in the
    trace, in percent.  The peak is the highest that the kernel's
    operands allow."""
    if run.trace is None or not run.trace.kernel_s.get(KERNEL):
        return None
    weight_bytes = 1 if run.arithmetic == "int8" else 4
    peak = run.peaks[run.arithmetic]
    layers = int(run.geometry["layers"])
    least = 0.0
    for _, _, bucket, _ in run.traced_dispatches:
        ops, nbytes = run.work.layer_call(run.geometry, bucket // run.chips,
                                          weight_bytes)
        least += max(ops / peak, nbytes / run.peaks["hbm_bytes_s"]) \
            * layers * run.chips
    return 100.0 * least / run.trace.kernel_s[KERNEL]


def vita_layer_busy_share(run):
    """Device time of the encoder-layer kernel over the device's busy
    time in the traced window, in percent."""
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.kernel_s.get(KERNEL, 0.0) / (t.busy_s * t.chips)


def idle_share(run):
    """The share of the traced window in which no operation ran on the
    device, averaged over the chips used, in percent."""
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
