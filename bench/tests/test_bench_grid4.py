"""`cnn_fmnist.grid4` at a CPU test's size on four forced host devices:
the whole run is correct when sound, and not correct when every chip's
slice of the sharded dispatch comes back as the first chip's.

The devices are forced in a child process (``XLA_FLAGS`` takes effect only
before JAX starts), which runs both cases and prints one JSON line.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "cnn_fmnist.grid4"
SEED = 3_000_000_019


def first_chip_everywhere(monkeypatch):
    """Each sharded program's output holds the first device's slice in
    every device's place, as if the gather read one shard for all."""
    import jax
    import jax.numpy as jnp

    from repro.fl import scenarios

    build = scenarios.GridRunner._program_sharded

    def patched(self, sub, mesh):
        program, args = build(self, sub, mesh)
        d = min(mesh.devices.size, sub.link_eps.shape[0])

        def run(a):
            return jax.tree.map(
                lambda l: jnp.concatenate([l[:l.shape[0] // d]] * d),
                program(a))

        return run, args

    monkeypatch.setattr(scenarios.GridRunner, "_program_sharded", patched)


def _child() -> None:
    import jax

    from bench import harness
    from bench.tests import _tiny

    assert jax.device_count() >= 4, jax.devices()
    kw = dict(require_tpu=False, overrides=_tiny.overrides(CELL))
    out = {"sound": harness.run_cell(CELL, SEED, 0.5, False, **kw)}
    with pytest.MonkeyPatch.context() as mp:
        first_chip_everywhere(mp)
        out["first_chip_everywhere"] = harness.run_cell(
            CELL, SEED, 0.5, False, **kw)
    print(json.dumps({k: {"correct": v["correct"], "checks": v["checks"],
                          "attempted": v["attempted"],
                          "count": v["device"]["count"]}
                      for k, v in out.items()}))


@pytest.fixture(scope="module")
def lines():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [str(ROOT), str(ROOT / "src")]
               + ([os.environ["PYTHONPATH"]]
                  if os.environ.get("PYTHONPATH") else []))}
    proc = subprocess.run([sys.executable, __file__, "--child"], env=env,
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_on_four_devices_is_correct(lines):
    line = lines["sound"]
    assert line["count"] == 4
    assert line["attempted"] % 32 == 0        # 4 pairs x 8 seeds a dispatch
    assert line["correct"] is True, line["checks"]


def test_first_chip_slice_everywhere_is_not_correct(lines):
    line = lines["first_chip_everywhere"]
    assert line["correct"] is False, line["checks"]


if __name__ == "__main__" and sys.argv[1:] == ["--child"]:
    _child()
