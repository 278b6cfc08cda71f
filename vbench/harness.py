"""Run one cell once: set up, drive its traffic for a window, measure,
then check the answers against the plain reference.

Every cell drives the serving path users call: requests go in through
``AdmissionController.submit`` / ``step``, which call
``VisionServer.dispatch`` / ``complete``, which run one jitted forward
per batch bucket.  The harness records spans around those calls from its
own code (wrapping the methods on the instances it built) and, in a
traced run, writes the same spans into the profiler's trace.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import pathlib
import shutil
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from vbench import peaks as peaks_lib
from vbench import spec as spec_lib
from vbench import stats
from vbench import traffic as traffic_lib

CODE_ROOT = pathlib.Path(__file__).resolve().parents[1]
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")
# answers compared against the reference at most, drawn from the seed
MAX_COMPARED = 20000
# seconds an answer due in the window may still take after it closes
ANSWER_WAIT_S = 60.0
# the longest window a traced run measures: a trace of the whole window
# of a busy cell holds millions of device operations, and reading it
# would outlast the run's time limit
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    """The run needs chips that JAX does not have."""


@dataclasses.dataclass
class Run:
    """What the metric readers read: the stamps, spans and counts of one
    run, and the trace reduction of a traced one."""
    cell: spec_lib.Cell
    seconds: float
    window: Tuple[float, float]          # host clock (perf_counter)
    setup_s: float
    requests: List[Any]                  # VisionRequest, with vb_* stamps
    dispatches: List[Tuple[float, float, int, int]]  # t0, t1, bucket, real
    spans: List[Tuple[str, float, float]]
    lateness_s: List[float]
    compiles_in_window: int
    padded: int
    geometry: Dict[str, Any]
    peaks: Dict[str, float]
    arithmetic: str
    work: Any                            # vbench.work.<family>
    trace: Any = None                    # vbench.trace.Reduction
    traced_dispatches: List[Tuple[float, float, int, int]] = \
        dataclasses.field(default_factory=list)

    @property
    def chips(self) -> int:
        return self.cell.chips

    @property
    def sla_ms(self) -> Optional[float]:
        return self.cell.traffic.get("sla_ms")

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and stats.in_window(t, self.window)

    def durations(self, name: str) -> List[float]:
        return [t1 - t0 for n, t0, t1 in self.spans
                if n == name and self.in_window(t0)]


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def program_path() -> None:
    """Make the system under test importable from the checkout."""
    src = str(CODE_ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def check_device(chips: int):
    """JAX's first device must be a TPU, with ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is on {devs[0].platform!r}, "
                     f"not a TPU; the benchmark measures the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX has {len(devs)}")
    return devs[0]


def seed_key(seed: int, stream: int):
    """A PRNG key for one use (``stream``) of a seed of any size."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def make_bank(seed: int, n: int, image: int) -> np.ndarray:
    """``n`` float32 images (H, W, 3) from the seed, made on the device."""
    import jax
    return np.asarray(jax.random.normal(seed_key(seed, 1),
                                        (n, image, image, 3)))


def check_geometry(cfg, geometry: Dict[str, Any]) -> None:
    """The program's model must have the configuration's sizes."""
    have = {"image": cfg.image, "patch": cfg.patch, "dim": cfg.dim,
            "heads": cfg.heads, "layers": cfg.layers,
            "mlp_hidden": cfg.mlp_hidden, "n_classes": cfg.n_classes}
    for k, v in have.items():
        if int(geometry[k]) != int(v):
            raise spec_lib.SpecError(
                f"configuration says {k}={geometry[k]}, the program's "
                f"model has {v}")


def build_server(cell: spec_lib.Cell, params, bank: np.ndarray, seed: int):
    """The served model, through the program's one construction path."""
    from repro.launch.vision_serve import ServeConfig, make_server
    c = cell.config
    buckets = traffic_lib.served_buckets(cell.traffic, c["buckets"])
    sc = ServeConfig(mode=c["mode"], buckets=buckets,
                     full=bool(c["full"]), backend=c.get("backend", "pallas"),
                     seed=int(seed) & 0x7FFFFFFF,
                     data_parallel=cell.chips if cell.chips > 1 else None,
                     calib_images=int(c.get("calib_images", 8)))
    calib = bank[:sc.calib_images] if c["mode"] == "int8" else None
    server = make_server(c["registry"], sc, params=params, calib_bank=calib)
    check_geometry(server.cfg, c["geometry"])
    return server


class _Recorder:
    """Host spans around the serving calls, and in a traced run the same
    spans as profiler annotations."""

    def __init__(self):
        self.spans: List[Tuple[str, float, float]] = []
        self.dispatches: List[Tuple[float, float, int, int]] = []
        self.annotate = False

    def span(self, name: str, fn, *args, **kw):
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                t0 = time.perf_counter()
                out = fn(*args, **kw)
                t1 = time.perf_counter()
        else:
            t0 = time.perf_counter()
            out = fn(*args, **kw)
            t1 = time.perf_counter()
        self.spans.append((name, t0, t1))
        return out, t0, t1

    def wrap(self, server, controller) -> None:
        dispatch, complete = server.dispatch, server.complete
        step, submit = controller.step, controller.submit

        def w_dispatch(requests=None, bucket=None):
            out, t0, t1 = self.span("vbench.dispatch", dispatch, requests,
                                    bucket)
            if out is not None:
                self.dispatches.append((t0, t1, out.bucket,
                                        len(out.requests)))
            return out

        server.dispatch = w_dispatch
        server.complete = lambda inflight: self.span(
            "vbench.complete", complete, inflight)[0]
        controller.step = lambda now=None: self.span(
            "vbench.step", step, now)[0]
        controller.submit = lambda *a, **k: self.span(
            "vbench.submit", submit, *a, **k)[0]

    def sleep(self, seconds: float) -> None:
        self.span("vbench.sleep", time.sleep, seconds)


class _CompileCounter:
    def __init__(self):
        import jax
        self.on = False
        self.count = 0

        def listener(event, duration, **kw):
            if self.on and event in COMPILE_EVENTS:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


class _Tracer:
    """The profiler over a traced run, started before the traffic's
    warm-up so that starting it stalls nothing in the window, with the
    window itself as the host span ``vbench.window``."""

    def __init__(self, directory: Optional[pathlib.Path]):
        self.dir = directory
        self.window = None
        self.started = False

    def start(self) -> None:
        if self.dir is None:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        # the harness's spans are host annotations; Python function
        # tracing would slow the host path under test several times over
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=options)
        self.started = True

    def open_window(self) -> None:
        if self.started:
            import jax
            self.window = jax.profiler.TraceAnnotation("vbench.window")
            self.window.__enter__()

    def close_window(self) -> None:
        if self.window is not None:
            self.window.__exit__(None, None, None)
            self.window = None

    def stop(self) -> Optional[str]:
        if not self.started:
            return None
        import jax
        self.close_window()
        jax.profiler.stop_trace()
        self.started = False
        found = sorted(self.dir.rglob("*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return str(found[-1])


def _send(ctl, model: str, plan, bank, seq: int, t_due=None):
    req = ctl.submit(model, bank[plan.image_of(seq)], sla_ms=plan.sla_ms,
                     t_submit=t_due)
    req.vb_seq = seq
    req.vb_img = plan.image_of(seq)
    return req


def drive_closed(ctl, model, plan, bank, rec, tracer, counter):
    """Clients that each keep one request outstanding.  Returns the
    window and the requests sent."""
    sent = []
    tracer.start()
    w0 = time.perf_counter() + plan.warm_s
    seq = 0
    for _ in range(plan.clients):
        sent.append(_send(ctl, model, plan, bank, seq))
        seq += 1
    seen = 0
    window = None
    while True:
        now = time.perf_counter()
        if window is None and now >= w0:
            if tracer.started:
                # a traced window opens and closes with nothing in flight,
                # so that every kernel in it is of a dispatch made in it
                ctl.drain()
                tracer.open_window()
                rec.annotate = True
                now = time.perf_counter()
            window = (now, now + plan.seconds)
            counter.on = True
        if window is not None and now >= window[1]:
            break
        ctl.step()
        done = ctl.completed
        for _ in range(len(done) - seen):
            sent.append(_send(ctl, model, plan, bank, seq))
            seq += 1
        seen = len(done)
    counter.on = False
    ctl.drain()
    tracer.close_window()
    rec.annotate = False
    return window, sent


def drive_open(ctl, model, plan, bank, rec, tracer, counter):
    """Requests due on the plan's schedule, sent when due or, when the
    server holds the loop, as soon as it lets go (their latency counts
    from the due time).  Returns the window, the requests, and how late
    each was sent."""
    sent, late = [], []
    tracer.start()
    base = time.perf_counter() + plan.warm_s
    due = base + plan.due
    window = (base, base + plan.seconds)
    i, n = 0, len(due)
    opened = closed = False
    while i < n or ctl.pending or ctl.ring:
        now = time.perf_counter()
        if not opened and now >= window[0]:
            opened = True
            counter.on = True
            tracer.open_window()
            rec.annotate = tracer.started
        if opened and not closed and now >= window[1]:
            closed = True
            counter.on = False
            tracer.close_window()
            rec.annotate = False
        while i < n and due[i] <= now:
            sent.append(_send(ctl, model, plan, bank, i,
                              t_due=float(due[i])))
            late.append(now - float(due[i]))
            i += 1
        if ctl.pending or ctl.ring:
            ctl.step()
        elif i < n:
            rec.sleep(min(max(due[i] - now, 0.0), 0.005))
    counter.on = False
    tracer.close_window()
    rec.annotate = False
    return window, sent, late


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def compared_requests(requests, seed: int) -> List[Any]:
    """The answers compared: all of them, or a sample drawn from the
    seed where there are more than ``MAX_COMPARED``."""
    if len(requests) <= MAX_COMPARED:
        return list(requests)
    rng = np.random.default_rng([int(seed), 0xC0DE])
    pick = np.sort(rng.choice(len(requests), MAX_COMPARED, replace=False))
    return [requests[i] for i in pick]


def logit_gap(answers: np.ndarray, images: np.ndarray,
              reference: np.ndarray) -> float:
    """The widest gap between an answer's logits and the reference's for
    its image, as a share of the reference's logit scale (its largest
    magnitude over the bank)."""
    scale = float(np.abs(reference).max())
    worst = 0.0
    for i in range(0, len(answers), 1024):
        diff = np.abs(answers[i:i + 1024] - reference[images[i:i + 1024]])
        worst = max(worst, float(diff.max()))
    return worst / scale


# the numbers a configuration's ``correct.limits`` may name
GAPS = {"logit_err": logit_gap}


def reference_logits(cell, params, bank: np.ndarray,
                     precision: Optional[str] = None,
                     calib: Optional[np.ndarray] = None) -> np.ndarray:
    ref = spec_lib.family_module("reference", cell.config)
    corr = cell.config["correct"]
    precision = precision or corr["reference"]
    g = cell.config["geometry"]
    scales = ref.calibrate(params, calib, g, precision) \
        if precision in ref.QUANT_MAX else None
    return ref.forward(params, bank, g, precision=precision,
                       act_scales=scales)


def gaps(cell, answers: np.ndarray, images: np.ndarray,
         reference: np.ndarray) -> Dict[str, float]:
    """Each number the configuration's limits name, of ``answers``."""
    return {name: GAPS[name](answers, images, reference)
            if len(answers) else math.inf
            for name in cell.config["correct"]["limits"]}


def checks(cell, answers: np.ndarray, images: np.ndarray,
           unanswered: int, reference: np.ndarray) -> Dict[str, Dict]:
    limits = cell.config["correct"]["limits"]
    out = {name: {"value": v, "limit": float(limits[name])}
           for name, v in gaps(cell, answers, images, reference).items()}
    out["unanswered"] = {"value": int(unanswered), "limit": 0}
    return out


def passed(result_checks: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in result_checks.values())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Outcome:
    run: Run
    checks: Dict[str, Dict]
    device: Dict[str, Any]
    metrics: Dict[str, Dict[str, Any]]
    breakdown: Optional[Dict[str, Any]]
    attempted: int
    failed: int
    notes: List[str]
    answers: np.ndarray = None           # the logits compared
    images: np.ndarray = None            # the bank image of each
    params: Any = None
    bank: np.ndarray = None


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


@dataclasses.dataclass
class Session:
    """A cell set up for driving: weights, image bank, the server and the
    admission controller in front of it, with the harness's spans."""
    cell: spec_lib.Cell
    seed: int
    device: Any
    devices: List[Any]
    params: Any
    bank: np.ndarray
    server: Any
    ctl: Any
    rec: _Recorder
    counter: _CompileCounter
    phases: List[Tuple[str, float]]      # set-up phase, seconds


def prepare(cell: spec_lib.Cell, seed: int, bank_size: int) -> Session:
    program_path()
    phases = []
    t = time.perf_counter()

    def phase(name):
        nonlocal t
        now = time.perf_counter()
        phases.append((name, now - t))
        t = now

    import jax
    from repro.launch.admission import AdmissionController

    first = check_device(cell.chips)
    phase("jax and program import, devices")
    c = cell.config
    g = c["geometry"]
    params = spec_lib.family_module("reference", c).init_params(
        seed_key(seed, 0), g)
    jax.block_until_ready(params)
    phase("weights")
    bank = make_bank(seed, bank_size, int(g["image"]))
    phase("image bank")
    server = build_server(cell, params, bank, seed)
    phase("server build (int8: quantize, calibrate)")
    ctl = AdmissionController({c["registry"]: server}, max_inflight=2)
    phase("controller's probe of every bucket")
    rec = _Recorder()
    rec.wrap(server, ctl)
    return Session(cell, seed, first, jax.devices()[:cell.chips], params,
                   bank, server, ctl, rec, _CompileCounter(), phases)


def drive(s: Session, plan, tracer: "_Tracer"):
    """One window of ``plan``'s traffic; returns the window, the requests
    sent and how late each open-loop request was sent."""
    model = s.cell.config["registry"]
    if plan.loop == "closed":
        window, sent = drive_closed(s.ctl, model, plan, s.bank, s.rec,
                                    tracer, s.counter)
        late = []
    else:
        window, sent, late = drive_open(s.ctl, model, plan, s.bank, s.rec,
                                        tracer, s.counter)
    t_wait = time.perf_counter() + ANSWER_WAIT_S
    while (s.ctl.pending or s.ctl.ring) and time.perf_counter() < t_wait:
        s.ctl.step()
    return window, sent, late


def answers_of(sent, seed: int, n_classes: int):
    """(logits, image index) of the answers compared, and how many
    requests were never answered."""
    answered = [r for r in sent if r.t_done is not None]
    compared = compared_requests(answered, seed)
    answers = np.stack([r.logits for r in compared]) if compared else \
        np.zeros((0, n_classes), np.float32)
    images = np.array([r.vb_img for r in compared], np.int64)
    return answers, images, len(sent) - len(answered)


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool,
             t_process: Optional[float] = None) -> Outcome:
    """Set up, drive and check one run of ``workload``."""
    t_process = time.perf_counter() if t_process is None else t_process
    if trace:
        seconds = min(float(seconds), TRACE_SECONDS)
    cell = spec_lib.load_cell(root, workload)
    c = cell.config
    g = c["geometry"]
    plan = traffic_lib.plan(cell.traffic, seed, seconds, c["buckets"])
    s = prepare(cell, seed, plan.bank)
    padded0 = s.server.n_padded
    trace_dir = pathlib.Path(root) / ".vbench_trace" if trace else None
    tracer = _Tracer(trace_dir)
    window, sent, late = drive(s, plan, tracer)
    padded = s.server.n_padded - padded0
    mem = memory_peak(s.devices)
    xplane = tracer.stop()

    run = Run(cell=cell, seconds=float(seconds), window=window,
              setup_s=window[0] - t_process, requests=sent,
              dispatches=s.rec.dispatches, spans=s.rec.spans,
              lateness_s=late, compiles_in_window=s.counter.count,
              padded=padded, geometry=g,
              peaks=peaks_lib.peaks(s.device.device_kind),
              arithmetic=c["arithmetic"],
              work=spec_lib.family_module("work", c))
    if xplane is not None:
        from vbench import trace as trace_lib
        run.trace = trace_lib.reduce_file(xplane, c["kernels"],
                                          chips=cell.chips)
        run.traced_dispatches = [d for d in s.rec.dispatches
                                 if d[0] >= window[0]]
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}

    answers, images, unanswered = answers_of(sent, seed, int(g["n_classes"]))
    n_sent = len(sent)
    # the program's state goes before the reference runs
    params, bank, s_phases = s.params, s.bank, s.phases
    run.requests = []
    del s, sent
    gc.collect()
    import jax
    jax.clear_caches()
    reference = reference_logits(cell, params, bank)
    result_checks = checks(cell, answers, images, unanswered, reference)

    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "device_kind": first.device_kind, "count": jax.device_count(),
              "memory_peak_bytes": mem}
    breakdown = None
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": [list(x) for x in run.trace.device_ops],
                     "idle_gaps": [list(x) for x in run.trace.idle_gaps]}
    notes = ["set-up: " + ", ".join(f"{n} {v:.3f} s"
                                    for n, v in s_phases)]
    if late:
        notes.append(f"generator lateness p95 "
                     f"{stats.percentile(late, 95) * 1e3:.4f} ms, max "
                     f"{max(late) * 1e3:.4f} ms over {len(late)} requests")
    notes.append(f"compilations in the window: {run.compiles_in_window}")
    notes.append(f"n_padded: {padded} padding images over "
                 f"{len(run.dispatches)} dispatches")
    return Outcome(run=run, checks=result_checks, device=device,
                   metrics=metrics, breakdown=breakdown, attempted=n_sent,
                   failed=unanswered, notes=notes, answers=answers,
                   images=images, params=params, bank=bank)
