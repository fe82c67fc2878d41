"""One benchmark run of one cell: resolve, set up, measure, check, report.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Each resolves to files of its own, found by name:

* ``bench/configs/<config>.json`` - the configuration as it is run, and
  ``bench/configs/<config>.py`` - its plain reference model, data builder
  and FLOP counter;
* ``bench/traffic/<traffic>.json`` - the mix's parameters, read by the
  generator its ``kind`` names (``grid``: `_grid_window`);
* ``bench/cells/<cell>.json`` - optional: traffic parameters of this cell
  alone and the limits of its comparison;
* ``bench/metrics/<metric>.py`` - one reader per metric, ``read(ctx)``
  returning a number or None.

`run_cell` returns the result line as a dict; `bench/run.py` prints it.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager, nullcontext
from pathlib import Path
from typing import Any

import numpy as np

from bench import network, reference
from bench import trace as tr

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_WINDOW_S = 10.0      # a traced run measures at most this long


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Resolution of names to files
# ---------------------------------------------------------------------------
def load_module(path: Path):
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path.relative_to(ROOT)))
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return mod


def _read_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    model: Any              # bench/configs/<config>.py
    traffic: dict           # bench/traffic/<traffic>.json + cell overrides
    limits: dict            # number -> limit, from bench/cells/<cell>.json
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list

    @property
    def widths(self) -> dict:
        return {k: self.config[k] for k in self.model.WIDTHS}


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, spec: dict | None = None,
            overrides: dict | None = None) -> Cell:
    """The cell called ``name``, with its configuration, mix and metrics.

    ``overrides`` (for tests) replaces configuration keys (``config``),
    traffic keys (``traffic``) or limits (``limits``).
    """
    spec = _read_json(ROOT / "BENCHMARK.json") if spec is None else spec
    overrides = overrides or {}
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_file = ROOT / configs[w["config"]]["file"]
    config = {**_read_json(cfg_file), **overrides.get("config", {})}
    model = load_module(cfg_file.with_suffix(".py"))
    traffic = _read_json(BENCH / "traffic" / f"{w['traffic']}.json")
    cell_file = BENCH / "cells" / f"{name}.json"
    cell_extra = _read_json(cell_file) if cell_file.exists() else {}
    traffic = {**traffic, **cell_extra.get("traffic", {}),
               **overrides.get("traffic", {})}
    limits = {**cell_extra.get("limits", {}), **overrides.get("limits", {})}
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    # A per-layer metric without a workloads list is reported wherever the
    # end-to-end metric it moves is.
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (_reports(m, name) if "workloads" in m
                 else m["moves"] in moved)]
    return Cell(name, int(w["chips"]), config, model, traffic, limits,
                e2e, layer)


def read_metrics(entries: list, ctx) -> dict:
    """Each metric's reader applied to the run's context; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# The system under test, bound to one configuration
# ---------------------------------------------------------------------------
class System:
    """The program's model, data and network for one configuration."""

    def __init__(self, cell: Cell):
        import jax
        from repro.core import topology
        from repro.data.synthetic import FederatedDataset
        from repro.fl import scenarios, simulator
        from repro.models import smallnets

        cfg = cell.config
        self.cell = cell
        self.scenarios = scenarios
        self.data = cell.model.make_data(cfg)
        coords, adj, eps = network.fig9_network(
            n_relays=cfg["n_relays"], relay_seed=cfg["relay_seed"],
            edge_density=cfg["edge_density"],
            packet_len_bits=cfg["packet_len_bits"],
            tx_power_dbm=cfg["tx_power_dbm"])
        self.link_eps = eps
        self.net = topology.Network(
            coords=coords, adjacency=adj, link_eps=eps,
            n_clients=cfg["n_clients"],
            packet_len_bits=cfg["packet_len_bits"],
            tx_power_dbm=cfg["tx_power_dbm"])
        init, self.apply_fn = smallnets.MODELS[cfg["model"]]
        self.init_fn = functools.partial(init, **cell.widths)
        d = self.data
        self.fed = FederatedDataset(list(d.train_x), list(d.train_y),
                                    d.test_x, d.test_y)
        shapes = jax.eval_shape(self.init_fn, jax.random.PRNGKey(0))
        self.n_params = sum(int(np.prod(l.shape))
                            for l in jax.tree_util.tree_leaves(shapes))
        if self.n_params != cfg["n_params"]:
            raise ValueError(f"{cfg['name']}: the model has {self.n_params} "
                             f"parameters, the configuration says "
                             f"{cfg['n_params']}")
        self.sim_cfg = simulator.SimConfig(
            n_rounds=cfg["n_rounds"], local_epochs=cfg["local_epochs"],
            seg_len=cfg["seg_len"], aayg_mixes=cfg["aayg_mixes"],
            cfl_aggregator=cfg["cfl_aggregator"])

    def grid(self, pairs, seeds):
        """The (protocol, mode) x seed sweep, protocol-major."""
        return self.scenarios.ScenarioGrid.product(
            networks=[("fig9", self.net)], protocols=[tuple(p) for p in pairs],
            seeds=[int(s) for s in seeds], lrs=(self.cell.config["lr"],),
            aggregator=self.cell.config["cfl_aggregator"])

    def flops_per_scenario_round(self) -> float:
        """Model FLOPs of one scenario-round: local training (forward plus
        a backward of twice the forward) on every client's tiled shard,
        the train-loss pass, and every client's test evaluation."""
        cfg, d = self.cell.config, self.data
        fwd = self.cell.model.forward_flops(self.cell.widths,
                                            d.test_x.shape[1:])
        xs, _ = d.tiled()
        n, shard = xs.shape[:2]
        epochs = cfg["local_epochs"]
        return float(n * fwd * (shard * (3 * epochs + 1) + len(d.test_x)))

    def kernel_calls(self, protocol: str, mode: str, batch: int) -> list:
        """The `ra_aggregate` calls one dispatch group of ``batch``
        scenarios makes, per round: (B, N, L, K, mode)."""
        cfg = self.cell.config
        per_round = {"ra": 1, "aayg": cfg["aayg_mixes"]}.get(protocol, 0)
        n = cfg["n_clients"]
        k = cfg["seg_len"]
        l = -(-self.n_params // k)
        return [(batch, n, l, k, mode)] * (per_round * cfg["n_rounds"])


# ---------------------------------------------------------------------------
# Measurement helpers
# ---------------------------------------------------------------------------
class CompileCounter:
    """Backend compilations and persistent-cache hits, via jax.monitoring."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@contextmanager
def profiled(enabled: bool):
    """Profile the block when ``enabled``; yields a holder whose ``trace``
    is the reduced trace once the block has ended."""
    import jax

    holder = type("Traced", (), {"trace": None})()
    if not enabled:
        yield holder
        return
    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(logdir)
        try:
            with jax.profiler.TraceAnnotation(tr.HOST_PREFIX + "window"):
                yield holder
        finally:
            jax.profiler.stop_trace()
        holder.trace = tr.load_xplane(logdir)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def _span(name: str, enabled: bool):
    if not enabled:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(tr.HOST_PREFIX + name)


def _scenario_seeds(rng: np.random.Generator, n: int) -> list[int]:
    """``n`` distinct scenario seeds (non-negative int32)."""
    seeds: list[int] = []
    while len(seeds) < n:
        s = int(rng.integers(0, 2**31 - 1))
        if s not in seeds:
            seeds.append(s)
    return seeds


def _devices(chips: int):
    import jax

    return None if chips == 1 else jax.devices()[:chips]


def _temp_bytes(compiled) -> int:
    """A compiled program's temporary buffers, as its compiler reports them."""
    m = compiled.memory_analysis()
    return int(m.temp_size_in_bytes) if m is not None else 0


def _memory_peak(chips: int) -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Context:
    """What a metric reader may read of one run."""

    cell: Cell
    system: System
    kind: str
    chips: int
    trace_on: bool
    setup_s: float
    window_s: float = 0.0
    scenario_rounds: int = 0
    flops_per_scenario_round: float = 0.0
    peaks: dict = dataclasses.field(default_factory=dict)
    kernel_calls: list = dataclasses.field(default_factory=list)
    trace: dict | None = None
    extra: dict = dataclasses.field(default_factory=dict)
    t_start: float = 0.0
    phases: list = dataclasses.field(default_factory=list)

    def mark(self, phase: str) -> None:
        """Note the end of a set-up phase (seconds since process start)."""
        self.phases.append((phase, time.monotonic() - self.t_start))


# ---------------------------------------------------------------------------
# Traffic
# ---------------------------------------------------------------------------
def _grid_window(system: System, cell: Cell, rng, seconds: float,
                 trace_on: bool, ctx: Context, t_start: float):
    """Back-to-back `GridRunner.run` dispatches; returns the rows to check
    as (protocol, mode, seed, acc, loss, bias) and the attempt counts."""
    t = cell.traffic
    pairs = [tuple(p) for p in t["pairs"]]
    n_seeds = int(t["seeds_per_chip"]) * cell.chips
    devices = _devices(cell.chips)
    runner = system.scenarios.GridRunner(
        system.init_fn, system.apply_fn, system.fed, system.sim_cfg,
        devices=devices)

    def next_grid():
        seeds = _scenario_seeds(rng, n_seeds)
        return seeds, system.grid(pairs, seeds)

    seeds, grid = next_grid()
    runner.warmup(grid)
    ctx.mark("compile")
    ctx.extra["program_temp_mb"] = round(max(
        (_temp_bytes(c) for c in runner.programs.compiled()), default=0) / 2**20, 1)
    runner.run(grid)                       # one warm dispatch
    ctx.mark("warm_dispatch")
    ctx.setup_s = time.monotonic() - t_start
    counter = ctx.extra["compile_counter"]
    compiles0 = counter.compiles
    dispatches, run_s = [], []
    with profiled(trace_on) as traced:
        t0 = time.monotonic()
        while True:
            with _span("grid_build", trace_on):
                seeds, grid = next_grid()
            t_run = time.monotonic()
            with _span("run", trace_on):
                res = runner.run(grid)
            dispatches.append((seeds, res))
            t_end = time.monotonic()
            run_s.append(t_end - t_run)
            if t_end - t0 >= seconds:
                break
    ctx.extra["window_compiles"] = counter.compiles - compiles0
    # A slow run shows here whether one call stalled or every call slowed.
    ctx.extra["run_s_median_max"] = (round(float(np.median(run_s)), 4),
                                     round(max(run_s), 4))
    ctx.window_s = t_end - t0
    ctx.trace = traced.trace
    rounds = cell.config["n_rounds"]
    ctx.scenario_rounds = len(dispatches) * len(pairs) * n_seeds * rounds
    ctx.kernel_calls = [
        c for _ in dispatches for p, m in pairs
        for c in system.kernel_calls(p, m, n_seeds)]
    ctx.extra["dispatches"] = len(dispatches)
    attempted = len(dispatches) * len(pairs) * n_seeds
    # The rows to check: one per pair, from a dispatch drawn from the seed.
    rows = []
    for i, (p, m) in enumerate(pairs):
        seeds, res = dispatches[int(rng.integers(len(dispatches)))]
        j = int(rng.integers(n_seeds))
        r = i * n_seeds + j
        rows.append((p, m, seeds[j], res.acc[r], res.loss[r], res.bias[r]))
    return rows, attempted, 0


WINDOWS = {"grid": _grid_window}


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def use_compile_cache() -> None:
    """JAX's persistent compilation cache where ``JAX_COMPILATION_CACHE_DIR``
    says, else at the fixed ``.jax_cache`` in the checkout; every program
    is cached, however short its compile."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def check_device(chips: int):
    """JAX's devices, if the first is a TPU and there are enough."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX has {len(devs)}")
    return devs


def compare(rows, ref: reference.Reference,
            control: reference.Reference | None = None) -> dict[str, float]:
    """The widest gaps over the checked rows (see `reference.gaps`).

    With ``control``, the control is compared in the program's place: the
    reference at a lower precision, run on the checked rows' scenarios.
    """
    worst = {"loss_gap": 0.0, "acc_gap": 0.0, "bias_gap": 0.0}
    for p, m, seed, acc, loss, bias in rows:
        want = ref.run(seed, p, m)
        got = ({"acc": acc, "loss": loss, "bias": bias} if control is None
               else control.run(seed, p, m))
        g = reference.gaps(got, want)
        print(f"check {p}+{m} seed {seed}: " + ", ".join(
            f"{k} {v:.6g}" for k, v in g.items()), file=sys.stderr)
        for k, v in g.items():
            worst[k] = max(worst[k], v)
    return worst


def measure(name: str, seed: int, seconds: float, trace_on: bool, *,
            t_start: float | None = None, require_tpu: bool = True,
            overrides: dict | None = None):
    """Set up and measure one window of cell ``name``.

    Returns the result line without its verdict, the rows to check, the
    cell and the system.  ``require_tpu=False`` and ``overrides`` exist for
    the tests, which drive a run on the CPU at a small size.
    """
    import jax

    t_start = time.monotonic() if t_start is None else t_start
    cell = resolve(name, overrides=overrides)
    if require_tpu:
        check_device(cell.chips)
    dev = jax.devices()[0]
    peaks = _read_json(BENCH / "peaks.json")["chips"]
    if require_tpu and dev.device_kind not in peaks:
        raise NoChip(f"no published peaks for {dev.device_kind!r} in "
                     f"bench/peaks.json")
    kind = cell.traffic["kind"]
    if trace_on:
        seconds = min(seconds, TRACE_WINDOW_S)
    ctx = Context(cell=cell, system=None, kind=kind, chips=cell.chips,
                  trace_on=trace_on, setup_s=0.0,
                  peaks=peaks.get(dev.device_kind, {}), t_start=t_start)
    counter = ctx.extra["compile_counter"] = CompileCounter()
    ctx.mark("jax_ready")
    system = System(cell)
    ctx.system = system
    ctx.mark("data_and_network")
    ctx.flops_per_scenario_round = system.flops_per_scenario_round()
    rng = np.random.default_rng(seed)
    rows, attempted, failed = WINDOWS[kind](system, cell, rng, seconds,
                                             trace_on, ctx, t_start)
    memory_peak = _memory_peak(cell.chips)
    print("setup: " + ", ".join(f"{k} at {v:.2f} s" for k, v in ctx.phases)
          + f"; {counter.compiles} compiles, {counter.hits} persistent-cache "
          f"hits, {counter.misses} misses", file=sys.stderr)
    print(f"window: {ctx.window_s:.3f} s, {ctx.scenario_rounds} "
          f"scenario-rounds, compiles inside {ctx.extra['window_compiles']}, "
          + ", ".join(f"{k} {v}" for k, v in ctx.extra.items()
                      if k not in ("compile_counter", "window_compiles")),
          file=sys.stderr)
    entries = cell.per_layer if trace_on else cell.end_to_end
    line = {"correct": None, "attempted": attempted, "failed": failed,
            "metrics": read_metrics(entries, ctx),
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": cell.chips,
                       "memory_peak_bytes": memory_peak}}
    if trace_on and ctx.trace is not None:
        busy, window_s = _busy(ctx.trace, cell.chips)
        line["device"].update(busy_s=busy, window_s=window_s)
        line["breakdown"] = _breakdown(ctx.trace)
    return line, rows, cell, system


def check(rows, cell: Cell, system: System,
          control: str | None = None) -> dict[str, float]:
    """The gaps of the checked rows from the reference at the precision
    the configuration states; with ``control`` (a lower precision, such as
    ``bf16_3x``), the gaps of the reference at that precision instead."""
    def ref(precision):
        return reference.Reference(cell.model, cell.widths, cell.config,
                                   system.data, system.link_eps,
                                   precision=precision)

    return compare(rows, ref("highest"), control and ref(control))


def judge(line: dict, rows, cell: Cell, system: System) -> dict:
    """Complete the result line: compare, decide ``correct``, and put each
    number compared beside its limit (stderr and the line's last key).

    The numbers compared are those the cell's file gives a limit; the
    other gaps are printed for information only.
    """
    t0 = time.monotonic()
    numbers = check(rows, cell, system)
    print(f"reference check: {time.monotonic() - t0:.2f} s, "
          f"{len(rows)} scenarios", file=sys.stderr)
    for k, v in numbers.items():
        if k not in cell.limits:
            print(f"{k} {v:.6g} (not compared)", file=sys.stderr)
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in cell.limits.items()}
    line["correct"] = (line["failed"] == 0 and bool(rows) and bool(checks)
                       and all(c["value"] <= c["limit"]
                               for c in checks.values()))
    line["checks"] = checks
    for k, c in checks.items():
        print(f"{k} {c['value']:.6g} limit {c['limit']}", file=sys.stderr)
    return line


def run_cell(name: str, seed: int, seconds: float, trace_on: bool,
             **kw) -> dict:
    """One run of cell ``name``: the result line as a dict."""
    line, rows, cell, system = measure(name, seed, seconds, trace_on, **kw)
    # The check runs once the window has closed, the peak has been read
    # and the program's runner is gone.
    return judge(line, rows, cell, system)


def _busy(trace: dict, chips: int) -> tuple[float, float]:
    """Busy seconds averaged over the chips used, and the window length."""
    t0, t1 = tr.window(trace)
    planes = sorted(trace["devices"])[:chips]
    busy = [tr.busy_ns(trace["devices"][p], t0, t1) for p in planes]
    return (sum(busy) / len(busy) * 1e-9 if busy else 0.0), (t1 - t0) * 1e-9


def _breakdown(trace: dict) -> dict:
    """The ten device ops with most self time (first chip) and the ten
    longest idle gaps, each labelled by the host span it fell in."""
    t0, t1 = tr.window(trace)
    planes = sorted(trace["devices"])
    if not planes:
        return {"device_ops": [], "idle_gaps": []}
    events = trace["devices"][planes[0]]
    ops = tr.self_times(events, t0, t1).most_common(10)
    gaps = sorted(tr.idle_gaps(events, t0, t1),
                  key=lambda g: g[0] - g[1])[:10]
    return {
        "device_ops": [[n, v * 1e-9] for n, v in ops],
        "idle_gaps": [[tr.label_gap(trace["host"], g), (g[1] - g[0]) * 1e-9]
                      for g in gaps],
    }
