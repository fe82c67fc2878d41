"""FLOP and byte counters of the benchmark against hand-computed values."""
import json
from pathlib import Path

from bench import harness
from bench.metrics import _shared

ROOT = Path(__file__).resolve().parents[2]


def _config(name):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    mod = harness.load_module(ROOT / "bench" / "configs" / f"{name}.py")
    return cfg, mod, {k: cfg[k] for k in mod.WIDTHS}


def test_cnn_forward_flops_per_sample():
    cfg, mod, w = _config("cnn_fmnist")
    # conv1 2*28*28*9*1*32 + conv2 2*14*14*9*32*64 + fc 2*3136*128 + 2*128*10
    assert mod.forward_flops(w, (28, 28, 1)) == 8_482_304
    assert 451_584 + 7_225_344 + 802_816 + 2_560 == 8_482_304


def test_charrnn_forward_flops_per_token():
    cfg, mod, w = _config("charrnn_shakespeare")
    # LSTM1 2*(8+256)*1024 + LSTM2 2*(256+256)*1024 + output 2*256*80
    assert mod.forward_flops(w, (1,)) == 1_630_208
    assert 540_672 + 1_048_576 + 40_960 == 1_630_208
    assert mod.forward_flops(w, (cfg["seq_len"],)) == 1_630_208 * 80


def test_kernel_flops_and_bytes():
    b, n, l, k = 2, 10, 412, 1024
    flops, nbytes = _shared.kernel_work((b, n, l, k, "ra_normalized"))
    assert flops == 2 * b * n * n * l * k
    seg = b * n * l * k * 4
    assert nbytes == 2 * seg + b * n * n * l + b * n * 4
    _, sub_bytes = _shared.kernel_work((b, n, l, k, "substitution"))
    assert sub_bytes - nbytes == seg        # the receiver's own segments


def test_round_flops_cover_training_loss_pass_and_eval():
    cell = harness.resolve("cnn_fmnist.grid")
    system = object.__new__(harness.System)
    system.cell = cell
    system.data = cell.model.make_data(cell.config)
    xs, _ = system.data.tiled()
    fwd = 8_482_304
    n, shard = xs.shape[:2]
    want = n * fwd * (shard * (3 * cell.config["local_epochs"] + 1)
                      + cell.config["test_samples"])
    assert harness.System.flops_per_scenario_round(system) == want
    # About 5.8e11 FLOPs: the largest shard of data seed 0 holds 840.
    assert shard == 840
    assert want == 10 * fwd * (840 * 7 + 1000)
    assert 5.8e11 < want < 5.9e11
