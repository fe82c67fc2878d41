#!/usr/bin/env python3
"""Chip smoke run: the scenario-grid engine and its serving tier on a TPU.

Drives the main path once through its public entry points, with the
paper's FashionMNIST CNN at its published widths (conv 32/64, FC 128,
28x28x1 input, 10 classes: 421,546 parameters, L = 412 segments of
K = 1024 float32 values) on the paper's Fig. 9 network (10 clients +
28 relays, 32,768-bit packets).  Weights and data come from ``--seed``;
nothing is downloaded.

  A. one `scenarios.run_grid` dispatch on the Pallas substrate: finite
     metrics, falling ideal C-FL loss, the kernel at these widths against a
     host NumPy float32 evaluation of eq. 6, and the same grid on the jnp
     substrate;
  B. a `GridRunner` warms the grid and runs it twice with buffer donation
     on: the grid programs hold the kernel (``tpu_custom_call``), both runs
     equal A, and nothing new is compiled;
  C. a `ScenarioServer` serves every scenario as its own request: each
     result matches its `run_grid` row.

``--chips 4`` runs only the multi-device path: the grid on a 4-way
``('grid',)`` mesh and on a 2x2 ``('grid', 'model')`` mesh, each against a
single-device run on device 0.

Exits non-zero, without the final JSON line, unless JAX's first device is
a TPU and every check passed.  Wall times printed here are smoke-run
times, not benchmarks.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips, multi-device path only
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

N_CLIENTS = 10
SEG_LEN = 1024
N_RELAYS = 28
ROUNDS = 5
LOCAL_EPOCHS = 2
PROTOCOLS = (("ra", "ra_normalized"), ("ra", "substitution"),
             ("aayg", "ra_normalized"), ("ideal_cfl", "ra_normalized"))
SAMPLES_PER_CLIENT = 200   # mean; sizes drawn from [100, 300)
KERNEL_BATCH = 8

KERNEL_TOL = 1e-5      # kernel vs host float32 eq. 6, max abs
# Whole-run agreement of two compiled programs of the same grid (another
# substrate, batching or partitioning), per client at the final round: the
# accuracy gap, and the loss gap <= atol + rtol * |loss| (allclose-style:
# top-k rows train to local losses near 0).  The simulator computes in
# float32 on the TPU too, so only summation order differs, but five rounds
# of training amplify it; accuracy moves in steps of 1/500 test samples.
RUN_TOL = {"acc": 0.01, "loss_rtol": 1e-3, "loss_atol": 1e-4}


class Smoke:
    """Check bookkeeping: every check prints one PASS/FAIL line."""

    def __init__(self):
        self.failed: list[str] = []

    def check(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"[{'PASS' if ok else 'FAIL'}] {name}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)

    def phase(self, name: str, fn, *args):
        print(f"--- {name}", flush=True)
        counter = _CompileCounter.current
        c0 = counter.snapshot()
        t0 = time.perf_counter()
        try:
            return fn(self, *args)
        except Exception:                                   # noqa: BLE001
            traceback.print_exc()
            self.check(f"{name} raised", False)
            return None
        finally:
            dt = time.perf_counter() - t0
            c1 = counter.snapshot()
            print(f"{name}: smoke-run wall time {dt:.3f} s (not a benchmark); "
                  f"XLA compiles {c1[0] - c0[0]}, persistent-cache hits "
                  f"{c1[1] - c0[1]}", flush=True)


class _CompileCounter:
    """Counts backend compilations and persistent-cache hits via
    `jax.monitoring` (a cache hit still passes the compile event)."""

    current: "_CompileCounter"

    def __init__(self):
        import jax

        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        _CompileCounter.current = self

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.compiles - self.hits, self.hits


@dataclasses.dataclass
class Setup:
    init_fn: object
    apply_fn: object
    data: object
    grid: object
    cfg: object


def build_setup(seed: int) -> Setup:
    """The paper's CNN, label-skewed 28x28 data, and the Fig. 9 grid."""
    from repro.core import topology
    from repro.data import synthetic
    from repro.fl import scenarios, simulator
    from repro.models import registry

    model = registry.sim_model("cnn")

    def apply_fn(params, x):
        return model.apply_fn(params, x.reshape(x.shape[0], 28, 28, 1))

    data = synthetic.fed_image_classification(
        n_clients=N_CLIENTS, d=28 * 28, samples_per_client=SAMPLES_PER_CLIENT,
        seed=seed,
    )
    net = topology.paper_network_with_relays(
        N_RELAYS, packet_len_bits=32 * SEG_LEN
    )
    simulator.check_packet_len(net.packet_len_bits, SEG_LEN, strict=True)
    base = scenarios.ScenarioGrid.product(
        networks=[("fig9", net)], protocols=PROTOCOLS,
        seeds=(seed, seed + 1),
    )
    # One top-k point, so the kernel's transmit-mask variant runs.
    topk = scenarios.ScenarioGrid.product(
        networks=[("fig9", net)], protocols=[("ra", "ra_normalized")],
        seeds=(seed,), codecs=[("topk@0.5", "topk", 0.5)],
    )
    topk.labels = [f"{lbl}/topk@0.5" for lbl in topk.labels]
    grid = scenarios.ScenarioGrid.concat(base, topk)
    cfg = simulator.SimConfig(n_rounds=ROUNDS, local_epochs=LOCAL_EPOCHS,
                              seg_len=SEG_LEN)
    return Setup(model.init_fn, apply_fn, data, grid, cfg)


def run_grid(s: Setup, **kw):
    from repro.fl import scenarios

    cfg = s.cfg if "cfg" not in kw else kw.pop("cfg")
    return scenarios.run_grid(s.init_fn, s.apply_fn, s.data, s.grid, cfg, **kw)


def compare(sm: Smoke, name: str, got, want) -> None:
    """Final-round gaps of two results of the same grid, row by row: the
    largest per-client accuracy gap and loss gap over its bound."""
    tol = RUN_TOL
    acc = np.abs(got.acc[:, -1] - want.acc[:, -1]).max(axis=-1)
    gap = np.abs(got.loss[:, -1] - want.loss[:, -1])
    bound = tol["loss_atol"] + tol["loss_rtol"] * np.abs(want.loss[:, -1])
    loss = (gap / bound).max(axis=-1)
    for i, lbl in enumerate(want.labels):
        print(f"  {lbl}: acc {got.acc[i, -1].mean():.4f} vs "
              f"{want.acc[i, -1].mean():.4f} (max client gap {acc[i]:.4g}), "
              f"loss {got.loss[i, -1].mean():.5f} vs "
              f"{want.loss[i, -1].mean():.5f} (max client gap/bound "
              f"{loss[i]:.4g})")
    sm.check(name, acc.max() <= tol["acc"] and loss.max() <= 1.0,
             f"max |acc diff| {acc.max():.4g} <= {tol['acc']}, max loss "
             f"gap {loss.max():.4g} x (atol {tol['loss_atol']:g} + rtol "
             f"{tol['loss_rtol']:g} |loss|), bitwise {same(got, want)}")


def same(a, b) -> bool:
    """Bitwise-equal metrics (bias is NaN where a protocol leaves it
    undefined)."""
    return all(np.array_equal(getattr(a, f), getattr(b, f), equal_nan=True)
               for f in ("acc", "loss", "bias"))


def host_eq6(w, p, e, tx, mode: str) -> np.ndarray:
    """Eq. 6 (or fused substitution) in NumPy float32, batched over B."""
    ef = e.astype(np.float32)                                 # (B, m, n, L)
    if tx is not None:
        n = e.shape[1]
        own = np.eye(n, dtype=bool)[None, :, :, None]
        ef = np.where(own, np.float32(1.0),
                      ef * tx[:, :, None, :].astype(np.float32))
    coeff = p[:, :, None, None] * ef
    num = np.einsum("bmnl,bmlk->bnlk", coeff, w)
    mass = coeff.sum(axis=1)[..., None]                       # (B, n, L, 1)
    if mode == "ra_normalized":
        return num / np.maximum(mass, np.float32(1e-12))
    return num + (p.sum(axis=1)[:, None, None, None] - mass) * w


def phase_a(sm: Smoke, s: Setup, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.core import errors
    from repro.kernels import ops

    res = run_grid(s)
    # AAYG defines no R&A bias diagnostic (NaN by design); every other
    # protocol of the grid does.
    has_bias = [i for i, lbl in enumerate(res.labels) if "/aayg+" not in lbl]
    sm.check("grid metrics finite",
             np.isfinite(res.acc).all() and np.isfinite(res.loss).all()
             and np.isfinite(res.bias[has_bias]).all(),
             f"acc/loss {res.acc.shape}, bias of {len(has_bias)} "
             f"non-AAYG scenarios")
    for i, lbl in enumerate(res.labels):
        print(f"  {lbl}: mean acc {res.acc[i, 0].mean():.4f} -> "
              f"{res.acc[i, -1].mean():.4f}, mean loss "
              f"{res.loss[i, 0].mean():.5f} -> {res.loss[i, -1].mean():.5f}")
    ideal = [i for i, lbl in enumerate(res.labels) if "ideal_cfl" in lbl]
    sm.check("ideal_cfl loss falls",
             all(res.loss[i, -1].mean() < res.loss[i, 0].mean()
                 for i in ideal), f"{len(ideal)} scenarios")

    # The kernel alone at this configuration's widths, under vmap as
    # run_grid reaches it, against a host float32 evaluation.
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        jax.eval_shape(s.init_fn, jax.random.PRNGKey(0))))
    l = errors.num_segments(n_params, SEG_LEN)
    kw, kp, ke, kr, kt = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (KERNEL_BATCH, N_CLIENTS, l, SEG_LEN)
    w = jax.random.normal(kw, shape, jnp.float32)
    p = jax.nn.softmax(jax.random.normal(kp, (KERNEL_BATCH, N_CLIENTS)))
    rho = jax.random.uniform(kr, (N_CLIENTS, N_CLIENTS), minval=0.3)
    e = jax.vmap(lambda k: errors.sample_success(k, rho, l))(
        jax.random.split(ke, KERNEL_BATCH))
    tx = jax.random.uniform(kt, (KERNEL_BATCH, N_CLIENTS, l)) < 0.5
    host = {name: np.asarray(x) for name, x in
            (("w", w), ("p", p), ("e", e), ("tx", tx))}
    print(f"  kernel shape (B, N, L, K) = {shape}, mask {e.dtype}")
    for mode in ("ra_normalized", "substitution"):
        for use_tx in (False, True):
            if use_tx:
                fn = jax.jit(jax.vmap(lambda w_, p_, e_, t_: ops.ra_aggregate(
                    w_, p_, e_, tx=t_, mode=mode)))
                got = fn(w, p, e, tx)
            else:
                fn = jax.jit(jax.vmap(lambda w_, p_, e_: ops.ra_aggregate(
                    w_, p_, e_, mode=mode)))
                got = fn(w, p, e)
            want = host_eq6(host["w"], host["p"], host["e"],
                            host["tx"] if use_tx else None, mode)
            err = float(np.max(np.abs(np.asarray(got) - want)))
            sm.check(f"kernel {mode}{' +tx' if use_tx else ''} vs host "
                     f"float32 eq. 6", err <= KERNEL_TOL,
                     f"max abs err {err:.3e} <= {KERNEL_TOL:g}")

    res_jnp = run_grid(s, cfg=dataclasses.replace(s.cfg, agg_impl="jnp"))
    compare(sm, "pallas vs jnp substrate, final round", res, res_jnp)
    return res


def phase_b(sm: Smoke, s: Setup, ref):
    from repro.fl import scenarios

    runner = scenarios.GridRunner(s.init_fn, s.apply_fn, s.data, s.cfg)
    compiled = runner.warmup(s.grid)
    programs = runner.programs.compiled()
    sm.check("grid programs contain tpu_custom_call",
             bool(programs) and all("tpu_custom_call" in prog.as_text()
                                    for prog in programs),
             f"{compiled} programs compiled by warmup")
    misses = runner.programs.misses
    runs = [runner.run(s.grid) for _ in range(2)]
    sm.check("warm runs equal run_grid", all(same(r, ref) for r in runs),
             "acc, loss, bias bitwise, two runs with donated inputs")
    sm.check("warm runs compile nothing",
             runner.programs.misses == misses,
             f"ProgramCache {runner.programs.stats}")


def phase_c(sm: Smoke, s: Setup, ref):
    from repro.launch import serving

    # One warm single-scenario program: every request is dispatched alone,
    # so what is served does not depend on arrival timing.
    server = serving.ScenarioServer(
        s.init_fn, s.apply_fn, s.data, s.cfg,
        serve=serving.ServeConfig(max_batch=1, batch_buckets=(1,)),
    )
    requests = [s.grid.take([i]) for i in range(len(s.grid))]
    compiled = server.warmup(*requests)
    print(f"  warmup compiled {compiled} program(s)")
    server.start()
    try:
        futures = [server.submit(r) for r in requests]
        results = [f.result(timeout=600) for f in futures]
    finally:
        server.stop(drain=True)
    served = ref.__class__(
        **{f: np.concatenate([getattr(r, f) for r in results])
           for f in ("acc", "loss", "bias")},
        labels=[lbl for r in results for lbl in r.labels])
    sm.check("at least 8 requests served", len(results) >= 8,
             f"{len(results)} requests")
    compare(sm, "served requests match run_grid rows", served, ref)
    sm.check("server stopped cleanly", not server.healthy())


def phase_mesh(sm: Smoke, s: Setup):
    import jax

    single = run_grid(s)
    for name, devices in (("('grid',) x4", jax.devices()[:4]),
                          ("('grid', 'model') 2x2", (4, 2))):
        compare(sm, f"{name} mesh vs single device",
                run_grid(s, devices=devices), single)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    from repro.core import aggregation
    from repro.kernels import ops
    from repro.launch import compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    if aggregation.resolve_impl() != "pallas" or ops.interpret_default():
        print("chip_smoke: REPRO_AGG_IMPL / REPRO_PALLAS_INTERPRET force "
              f"impl={aggregation.resolve_impl()!r}, interpret="
              f"{ops.interpret_default()}; unset them", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many devices, "
              f"have {len(jax.devices())}", file=sys.stderr)
        return 1
    cache = compile_cache.enable()
    _CompileCounter()
    print(f"jax {jax.__version__}; device {dev.device_kind} x "
          f"{len(jax.devices())}; compile cache {cache}", flush=True)

    sm = Smoke()
    s = build_setup(args.seed)
    print(f"grid: {len(s.grid)} scenarios {s.grid.labels}", flush=True)
    if args.chips == 4:
        sm.phase("multi-device grids", phase_mesh, s)
    else:
        ref = sm.phase("phase A: run_grid", phase_a, s, args.seed)
        if ref is not None:
            sm.phase("phase B: GridRunner warm re-runs", phase_b, s, ref)
            sm.phase("phase C: ScenarioServer", phase_c, s, ref)
        else:
            sm.check("phases B and C need phase A's result", False)
    for d in jax.devices()[:args.chips]:
        stats = d.memory_stats() or {}
        print(f"device {d.id} peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use', 'not reported')}")
    if sm.failed:
        print(f"chip_smoke: {len(sm.failed)} check(s) failed: {sm.failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
