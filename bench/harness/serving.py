"""The system under test, built as ``launch/serve._run_fleet`` builds it,
and driven through one measured window.

Construction mirrors ``_run_fleet``: the ``make_fleet_mesh(n)`` mesh, a
``PolicyStore``, the ``AdaptiveController`` with ``_run_fleet``'s
``AdaptiveConfig`` (canary on), the SLO engine, replicated weights and a
token-granular ``ContinuousBatcher`` on the mesh.  The weights are the
benchmark's (``weights.py``).  ``_run_fleet`` serves traffic of its own,
so it is mirrored here rather than called.

The window is driven through ``ContinuousBatcher.run_arrivals`` with the
benchmark's :class:`WindowSource`: it submits the plan's requests as they
come due, notes when each decode step's tokens reached the host, opens the
window once the warm-up time has passed, and ends the drain by raising
:class:`WindowClosed` from ``poll`` when the window closes, so no run waits
for requests still in flight.
"""
from __future__ import annotations

import dataclasses
import tempfile
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from repro import obs
from repro.configs import ARCHS
from repro.configs.base import AxPolicy, ModelConfig
from repro.fleet import (ArrivalSource, BatcherConfig, ContinuousBatcher,
                         PolicyStore, Request)
from repro.launch.mesh import make_fleet_mesh
from repro.models import init_params
from repro.runtime import (AdaptiveConfig, AdaptiveController, SwapPolicy,
                           triple_of)

from . import traffic, weights

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class WindowClosed(Exception):
    """Raised from the arrival source when the measured window ends."""


# keys of every configuration file that the harness reads itself (depth,
# dtype, approximation policy, the program's architecture and the check)
# or that describe the file; every other key belongs to the reference
# module, which maps it to the program (``PROGRAM_KEYS``)
COMMON_KEYS = frozenset({
    "name", "source", "paper", "reference", "reduced", "published",
    "assumed", "departures", "deployment", "num_hidden_layers",
    "torch_dtype", "approx", "program", "correct"})


def _program_value(cfg: ModelConfig, field):
    return field(cfg) if callable(field) else getattr(cfg, field)


def program_config(config: dict, model, shrink: Optional[Callable] = None
                   ) -> ModelConfig:
    """The program's ModelConfig for a configuration file: the repo's
    architecture with the file's depth, dtype and approximation policy.
    Every other key of the file is one the reference module ``model`` maps
    to the program, and the program's value equals the file's; the
    program also holds what ``model.PROGRAM_FIXED`` says its block leaves
    out."""
    unmapped = sorted(set(config) - COMMON_KEYS - set(model.PROGRAM_KEYS))
    if unmapped:
        raise ValueError(f"{config['name']}: keys that neither the harness "
                         f"nor reference {model.NAME} maps to the program: "
                         f"{unmapped}")
    absent = sorted(set(model.PROGRAM_KEYS) - set(config))
    if absent:
        raise ValueError(f"{config['name']}: reference {model.NAME} reads "
                         f"keys the file lacks: {absent}")
    prog = config["program"]
    base = ARCHS[prog["arch"]]
    ax = config["approx"]
    op_a, bit, value = ax["swap"]
    cfg = dataclasses.replace(
        base, n_layers=config["num_hidden_layers"],
        param_dtype=config["torch_dtype"],
        ax=AxPolicy(mult_name=ax["multiplier"], backend=ax["backend"],
                    targets=tuple(ax["targets"]),
                    swap_operand="A" if op_a == 1 else "B", swap_bit=bit,
                    swap_value=value if value in (0, 1) else 0,
                    swap_enabled=value in (0, 1)))
    differ = {k: (_program_value(cfg, f), config[k])
              for k, f in model.PROGRAM_KEYS.items()
              if _program_value(cfg, f) != config[k]}
    differ.update({k: (getattr(cfg, k), v)
                   for k, v in model.PROGRAM_FIXED.items()
                   if getattr(cfg, k) != v})
    if differ:
        raise ValueError(f"{config['name']}: the program's {prog['arch']} "
                         f"differs from the file and reference "
                         f"{model.NAME} (program, file): {differ}")
    return shrink(cfg) if shrink is not None else cfg


def as_run(config: dict, cfg: ModelConfig, model) -> dict:
    """The configuration file with every key ``model`` maps to the program
    read from ``cfg`` (a shrunken copy in tests; at full size the file's
    own values)."""
    return dict(config, num_hidden_layers=cfg.n_layers,
                **{k: _program_value(cfg, f)
                   for k, f in model.PROGRAM_KEYS.items()})


class CompileCounter:
    """Counts XLA backend compiles (a persistent-cache hit is not one).
    Listeners cannot be removed: make one per process."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == _COMPILE_EVENT:
            self.count += 1
            self.seconds += secs


class PolicyLog:
    """Stands in for the controller in the batcher and forwards everything
    to it, noting the swap triple of each target that every decode step
    runs under (the step reads ``dyn_tree`` once).  ``stats`` is the
    batcher's stats dict, set once the batcher exists."""

    def __init__(self, controller):
        self._ctl = controller
        self._tree = None
        self._now = None
        self.stats: dict = {}
        self.by_step: Dict[int, dict] = {}

    def dyn_tree(self):
        tree = self._ctl.dyn_tree()
        if tree is not self._tree:
            self._tree = tree
            self._now = {t: tuple(int(v) for v in triple_of(
                self._ctl.policy.lookup(t))) for t in self._ctl.targets}
        self.by_step[self.stats["decode_steps"]] = self._now
        return tree

    def __getattr__(self, name):
        return getattr(self._ctl, name)


class RecordingBatcher(ContinuousBatcher):
    """The batcher, keeping each completion as it retires: a drain that
    ends by an exception returns nothing, and the check needs the tokens."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.finished: List = []

    def _retire(self, *a, **kw):
        out = super()._retire(*a, **kw)
        self.finished.extend(out)
        return out


class WindowSource(ArrivalSource):
    """Open-loop or backlog arrivals from a :class:`traffic.Plan`, and the
    window's clock (see the module docstring).  ``depth``: requests kept
    queued in a backlog."""

    def __init__(self, plan: traffic.Plan, bat: ContinuousBatcher,
                 warm_s: float, seconds: float, depth: int = 0,
                 on_open: Callable = None, on_close: Callable = None):
        super().__init__([])
        self.plan, self.bat = plan, bat
        self.warm_s, self.seconds, self.depth = warm_s, seconds, depth
        self.on_open, self.on_close = on_open, on_close
        self.t0 = self.w0 = self.w1 = None
        self._steps = None
        self.step_done: Dict[int, float] = {}    # decode_steps -> host time
        self.due: Dict[int, float] = {}          # rid -> due (host time)
        self.submitted: Dict[int, float] = {}    # rid -> submit (host time)
        self.prompt: Dict[int, np.ndarray] = {}
        self.max_new: Dict[int, int] = {}

    def exhausted(self) -> bool:
        return False

    def next_due(self) -> Optional[float]:
        return self.plan.next_due()

    def poll(self, now: float) -> List[Request]:
        t = time.perf_counter()
        if self.t0 is None:
            self.t0 = t - now
        steps = self.bat.stats["decode_steps"]
        if steps != self._steps:
            self.step_done[steps] = t
            self._steps = steps
        if self.w0 is None and now >= self.warm_s:
            if self.on_open is not None:
                self.on_open()
            self.w0 = t = time.perf_counter()
        elif self.w0 is not None and t - self.w0 >= self.seconds:
            self.w1 = t
            if self.on_close is not None:
                self.on_close()
            raise WindowClosed()
        if self.plan.backlog:
            due = []
            while self.bat.pending() + len(due) < self.depth:
                due.append(self.plan.take())
        else:
            due = self.plan.take_due(now)
        out = []
        for p in due:
            self.due[p.idx] = t if p.due is None else self.t0 + p.due
            self.submitted[p.idx] = t
            self.prompt[p.idx] = p.tokens
            self.max_new[p.idx] = p.max_new
            out.append(Request(p.idx, p.tokens, max_new=p.max_new))
        return out


@dataclasses.dataclass
class Stack:
    cfg: ModelConfig
    mesh: object
    layout: object               # the weights' tree of ShapeDtypeStruct
    placement: object            # and their sharding (weights.make rebuilds)
    controller: AdaptiveController
    policy_log: PolicyLog
    batcher: RecordingBatcher
    bcfg: BatcherConfig
    store_dir: str
    log: list


def build(cfg: ModelConfig, mix: dict, chips: int, seed: int) -> Stack:
    """Mesh, store, controller, SLO engine, weights and batcher, as
    ``_run_fleet`` builds them.  As there, the batcher holds the weights
    prepared in place of the raw tree, which is not kept."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_fleet_mesh(chips)
    store_dir = tempfile.mkdtemp(prefix="bench_policy_")
    store = PolicyStore(store_dir)
    log: list = []
    controller = AdaptiveController(
        SwapPolicy.from_ax_policy(cfg.ax), targets=cfg.ax.targets,
        cfg=AdaptiveConfig(min_observe_steps=2, cooldown_steps=2,
                           tile_rows=0, canary=True),
        store=store, log_fn=log.append)
    controller.resume_from_store()
    controller.warmup()
    slo = obs.SLOEngine(obs.default_serving_slos(qor_targets=cfg.ax.targets),
                        audit=controller.audit)
    controller.attach_slo(slo)
    layout = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    placement = NamedSharding(mesh, P())
    params = weights.make(layout, seed, placement)
    bcfg = BatcherConfig(n_slots=mix["slots_per_chip"] * chips,
                         prompt_buckets=tuple(mix["prompt_buckets"]),
                         new_token_bucket=mix["new_token_bucket"],
                         temperature=0.0, token_granular=True)
    plog = PolicyLog(controller)
    bat = RecordingBatcher(params, cfg, bcfg, adaptive=plog, mesh=mesh)
    del params
    plog.stats = bat.stats
    bat.attach_slo(slo)
    return Stack(cfg, mesh, layout, placement, controller, plog, bat, bcfg,
                 store_dir, log)


def warm_up(stack: Stack) -> None:
    """Compile every program the window runs: one prefill per prompt
    bucket, the splice and the token step, by a short drain of one full
    bucket-length request per bucket.  Each request decodes twice: on a
    mesh of several chips the first step takes the cache as the batcher
    placed it and returns it sharded over the slots, and the second step
    compiles for that placement."""
    bat = stack.batcher
    rng = np.random.default_rng(0)
    for i, b in enumerate(stack.bcfg.prompt_buckets):
        bat.submit(Request(-1 - i, rng.integers(0, stack.cfg.vocab, b)
                           .astype(np.int32), max_new=3))
    bat.run()
    bat.finished.clear()


@dataclasses.dataclass
class Window:
    source: WindowSource
    events: list
    clock_offset: float          # host time = offset + recorder ts / 1e6
    compiles: int
    retraces: float


def drive(stack: Stack, plan: traffic.Plan, warm_s: float, seconds: float,
          depth: int, compiles: CompileCounter, on_open=None,
          on_close=None) -> Window:
    """Serve ``plan`` through ``run_arrivals`` until the window closes."""
    bat = stack.batcher
    rec = obs.TraceRecorder()
    offset = time.perf_counter() - rec.now_us() / 1e6
    marks = {}

    def opened():
        if on_open is not None:
            on_open()
        marks["compiles"] = compiles.count
        marks["retraces"] = obs.retrace_total()

    def closed():
        marks["compiles"] = compiles.count - marks["compiles"]
        marks["retraces"] = obs.retrace_total() - marks["retraces"]
        if on_close is not None:
            on_close()

    src = WindowSource(plan, bat, warm_s, seconds, depth, opened, closed)
    prev = obs.install_recorder(rec)
    try:
        bat.run_arrivals(src)
    except WindowClosed:
        pass
    finally:
        obs.install_recorder(prev)
    if src.w1 is None:
        raise RuntimeError("the drain ended before the window closed")
    return Window(src, rec.events(), offset, marks["compiles"],
                  marks["retraces"])
