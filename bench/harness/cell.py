"""One run of one cell: build, warm up, measure one window, check what was
served against the reference, and reduce everything to the result line."""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import jax
import numpy as np

from . import check, devtrace, serving, spec, timeline, traffic, weights


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def peaks_for(kind: str, root: Path = spec.ROOT) -> dict:
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["devices"][kind]


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read (host times in seconds)."""
    cell: dict
    config: dict                 # the configuration as run
    reference: object            # its module: the model and its work count
    mix: dict
    chips: int
    stats: dict                  # timeline.window_stats of the window
    steps: timeline.Steps
    times: dict                  # rid -> token host times
    prompts: dict                # rid -> prompt tokens
    dispatch: dict               # rid -> prefill dispatch host time
    w0: float
    w1: float
    peaks: Optional[dict]        # bench/peaks.json row of this device
    trace: Optional[dict]        # ops and host spans on the trace clock


def _device_info(devices, chips):
    peak = 0
    for dev in devices[:chips]:
        st = dev.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return dict(platform=devices[0].platform, kind=devices[0].device_kind,
                count=chips, memory_peak_bytes=peak)


class Tracer:
    """The profiler over the window, with a marker that puts host times on
    the trace's clock."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.mark_host = None

    def start(self):
        jax.profiler.start_trace(self.dir)
        with jax.profiler.TraceAnnotation("bench_window_open"):
            self.mark_host = time.perf_counter()

    def stop(self):
        jax.profiler.stop_trace()

    def reduce(self, w0: float, w1: float, events: list, offset: float) -> dict:
        ops, host = devtrace.read(devtrace.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        marks = host.get("bench_window_open", [])
        if not marks:
            raise RuntimeError("the trace lacks its bench_window_open marker")
        shift = marks[0][1] - self.mark_host        # trace clock - host clock
        spans = [(ev["name"], offset + ev["ts"] / 1e6 + shift,
                  offset + (ev["ts"] + ev["dur"]) / 1e6 + shift)
                 for ev in events if ev["ph"] == "X"]
        return dict(ops=ops, spans=spans, t0=w0 + shift, t1=w1 + shift,
                    shift=shift)


def _log_stalls(src, store_dir: str) -> None:
    """The longest intervals between decode steps reaching the host, and
    the audit events (each appended with an fsync) the program wrote in
    the window, on one clock: seconds from the window's open."""
    ts = np.asarray(sorted(t for t in src.step_done.values()
                           if src.w0 <= t <= src.w1))
    if ts.size < 2:
        return
    d = np.diff(ts)
    top = np.argsort(d)[::-1][:3]
    log(f"decode steps reaching the host in the window: {ts.size}, median "
        f"interval {np.median(d) * 1e3:.2f} ms, longest "
        + ", ".join(f"{d[i] * 1e3:.1f} ms at +{ts[i] - src.w0:.2f} s"
                    for i in top))
    unix = time.time() - time.perf_counter()
    path = Path(store_dir) / "audit.jsonl"
    events = []
    for line in path.read_text().splitlines() if path.is_file() else []:
        try:
            events.append(json.loads(line))
        except ValueError:
            continue                  # a line torn by a crash mid-append
    inside = [(e["unix_time"] - unix - src.w0, e.get("kind"))
              for e in events if "unix_time" in e
              and src.w0 <= e["unix_time"] - unix <= src.w1]
    log(f"audit events in the window: {len(inside)}"
        + "".join(f"; {k} at +{t:.2f} s" for t, k in inside[:12]))


def _e2e(names, stats) -> dict:
    """The end-to-end metrics the cell reports, from the window."""
    vals = dict(
        tokens_per_s=stats["tokens"] / stats["seconds"],
        ttft_p90_ms=_ms(timeline.percentile(stats["ttft"], 90)),
        itl_p95_ms=_ms(timeline.percentile(stats["itl"], 95)))
    return {k: v for k, v in vals.items() if k in names}


def _within(value, limit) -> bool:
    return value is not None and limit is not None and value <= limit


def _ms(x):
    return None if x is None else x * 1e3


def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        shrink: Optional[Callable] = None, allow_cpu: bool = False,
        root: Path = spec.ROOT, fault: Optional[Callable] = None,
        control: bool = False) -> dict:
    """One run of cell ``name``; returns the result line's object.
    ``shrink`` and ``allow_cpu`` let tests run it at a reduced size on the
    CPU; ``fault`` (tests) is called with the built stack to break the
    timed path underneath; ``control`` (``bench/control.py``) also reads
    the fp8 control's gaps at the same rows and judges them by the same
    statistic and limit, under the key ``control``."""
    sp = spec.resolve(name, root)
    chips = int(sp["cell"]["chips"])
    devices = jax.devices()
    if not allow_cpu:
        if devices[0].platform != "tpu":
            raise NoDevice(f"needs a TPU, JAX found {devices[0].platform}")
        if len(devices) < chips:
            raise NoDevice(f"cell {name} needs {chips} chips, JAX found "
                           f"{len(devices)}")
    peaks = (peaks_for(devices[0].device_kind, root)
             if devices[0].platform == "tpu" else None)
    compiles = serving.CompileCounter()
    mix = sp["traffic"]
    model = sp["reference"]
    cfg = serving.program_config(sp["config"], model, shrink)
    config = serving.as_run(sp["config"], cfg, model)
    plan = traffic.Plan(mix, seed, cfg.vocab)
    stack = serving.build(cfg, mix, chips, seed)
    if fault is not None:
        fault(stack)
    serving.warm_up(stack)
    tracer = Tracer() if trace else None
    setup = {}

    def opened():
        setup["s"] = time.perf_counter() - t_start
        if tracer is not None:
            tracer.start()

    def closed():
        if tracer is not None:
            tracer.stop()

    depth = mix["arrivals"].get("depth_per_chip", 0) * chips
    win = serving.drive(stack, plan, mix["warm_s"], seconds, depth, compiles,
                        opened, closed)
    src = win.source
    device = _device_info(devices, chips)
    steps, splice, dispatch, retire = timeline.parse(
        win.events, win.clock_offset, src.step_done)
    times = timeline.token_times(steps, splice, retire)
    stats = timeline.window_stats(times, src.due, dispatch, src.w0, src.w1)
    finished = list(stack.batcher.finished)
    log(f"window {stats['seconds']:.3f} s: {stats['tokens']} tokens, "
        f"{stats['due']} requests due, {len(finished)} finished in the "
        f"drain; setup {setup['s']:.2f} s (compile {compiles.seconds:.2f} s "
        f"over {compiles.count} programs)")
    lag = np.asarray([src.submitted[r] - src.due[r] for r in src.due])
    log(f"generator lateness (due -> submit): p50 "
        f"{np.percentile(lag, 50) * 1e3:.3f} ms, p99 "
        f"{np.percentile(lag, 99) * 1e3:.3f} ms")
    queued = sum(1 for r in src.due if r not in splice)
    in_use = max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                 for d in devices[:chips])
    log(f"requests in flight at the close: "
        f"{sum(1 for r in splice if r not in retire)}, queued {queued}; peak "
        f"device memory {device['memory_peak_bytes'] / 1e9:.3f} GB, in use at "
        f"the close {in_use / 1e9:.3f} GB")
    log("controller: " + " | ".join(stack.log[-4:]) if stack.log
        else "controller: no re-tune")
    _log_stalls(src, stack.store_dir)

    trace_red = None
    if tracer is not None:
        trace_red = tracer.reduce(src.w0, src.w1, win.events, win.clock_offset)
        busy = devtrace.busy(trace_red["ops"], trace_red["t0"], trace_red["t1"])
        device["busy_s"] = float(np.mean(list(busy.values()))) if busy else 0.0
        device["window_s"] = trace_red["t1"] - trace_red["t0"]

    # what the check needs, then the program's state goes
    inside = {r for r, t in retire.items() if src.w0 < t <= src.w1}
    picked = check.sample(finished, seed, inside)
    first = {c.rid: check.first_step(steps.start, splice[c.rid])
             for c in picked}
    by_step = dict(stack.policy_log.by_step)
    layout, placement = stack.layout, stack.placement
    in_window = dict(compiles=win.compiles, retraces=win.retraces)
    shutil.rmtree(stack.store_dir, ignore_errors=True)
    src.bat = None                    # the source held the batcher
    del stack, win
    gc.collect()
    log(f"device arrays alive after the stack was freed: "
        f"{sum(a.nbytes for a in jax.live_arrays()) / 1e9:.3f} GB")

    # the reference reads the weights made anew from the seed: the batcher
    # held only their prepared form
    t_ref = time.perf_counter()
    ref = model.Reference(weights.make(layout, seed, placement), config)
    replayed = check.replay(ref, picked, src.prompt, first, by_step,
                            config["approx"]["swap"],
                            config["approx"]["targets"],
                            check.padded_length(mix, model.ROWS),
                            control=control)
    log(f"reference replay of {len(picked)} requests: "
        f"{time.perf_counter() - t_ref:.1f} s")
    stat = config["correct"]["statistic"]
    limit = config["correct"]["limit"]
    value = (check.STATISTICS[stat](replayed) if replayed["gap"].size
             else None)
    miscounted = [c.rid for c in finished
                  if len(c.tokens) != src.max_new[c.rid]
                  or len(times.get(c.rid, ())) != len(c.tokens)]
    oov = sum(int(((np.asarray(c.tokens) < 0)
                   | (np.asarray(c.tokens) >= cfg.vocab)).sum())
              for c in finished)
    checks = {
        f"logit_{stat}": (value, limit),
        "served_tokens_checked": (int(replayed["gap"].size), ">= 1"),
        "requests_miscounted": (len(miscounted), 0),
        "tokens_outside_vocab": (oov, 0),
        "compiles_in_window": (in_window["compiles"], 0),
        "retraces_in_window": (in_window["retraces"], 0),
    }
    sound = (not miscounted and oov == 0 and in_window["compiles"] == 0
             and in_window["retraces"] == 0)
    correct = sound and _within(value, limit)
    log(f"checked {len(picked)} requests: "
        + json.dumps({k: round(v, 5) for k, v in
                      check.summary(replayed).items()}))

    out = dict(correct=bool(correct), attempted=int(stats["due"]), failed=0)
    units = {m["name"]: m["unit"] for m in sp["end_to_end"]}
    if trace:
        ctx = Context(sp["cell"], config, model, mix, chips, stats, steps,
                      times, src.prompt, dispatch, src.w0, src.w1, peaks,
                      trace_red)
        metrics = {}
        for entry, reader in sp["per_layer"]:
            v = reader.read(ctx)
            if v is not None:
                metrics[entry["name"]] = dict(value=v, unit=entry["unit"])
        out["metrics"] = metrics
        out["device"] = device
        d0 = sorted({o.device for o in trace_red["ops"]})
        out["breakdown"] = dict(
            device_ops=devtrace.top_ops(trace_red["ops"], trace_red["t0"],
                                        trace_red["t1"]),
            idle_gaps=devtrace.idle_gaps(
                trace_red["ops"], trace_red["t0"], trace_red["t1"],
                trace_red["spans"], d0[0]) if d0 else [])
    else:
        metrics = {k: dict(value=v, unit=units[k])
                   for k, v in _e2e(units, stats).items() if v is not None}
        metrics["setup_s"] = dict(value=setup["s"], unit=units["setup_s"])
        out["metrics"] = metrics
        out["device"] = device
    out["load"] = dict(queued_at_close=queued, due=int(stats["due"]),
                       finished_in_window=len(inside),
                       tokens_per_s=stats["tokens"] / stats["seconds"])
    if control:
        # the control in the program's place, judged as the program is
        low = (check.STATISTICS[stat](dict(gap=replayed["control_gap"],
                                           rank=replayed["control_rank"]))
               if replayed["control_gap"].size else None)
        out["control"] = dict(correct=bool(sound and _within(low, limit)),
                              value=low, limit=limit,
                              program=check.summary(replayed),
                              control=check.summary(replayed, "control_"))
        log(f"control logit_{stat}: {low} (limit {limit})")
    out["checks"] = {k: dict(value=v, limit=lim) for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} (limit {lim})")
    return out
