"""Cells cut to a size the CPU runs in seconds, for the harness's tests."""
import json
import pathlib

import jax

from bench import harness

REDUCED = {
    "llama": dict(num_hidden_layers=2, hidden_size=256, num_attention_heads=4,
                  num_key_value_heads=2, intermediate_size=512,
                  vocab_size=512),
}
SEQ = 64
# At this size on the CPU, float32 at full precision, the program reads
# 1e-7 to 2e-5 against the reference and the bfloat16 control 3e-4 and
# more: the cells' own limits are set for the chip's default precision.
LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-4, "change_gap": 1e-4,
          "fifo_gap": 1e-3, "gate_mismatches": 0, "token_mismatches": 0}


def reduced_cell(name: str) -> dict:
    """The cell ``name`` with the trainer's ``--reduced`` sizes, sequences
    of SEQ tokens and the limits of that size; workers, wire and delay as
    committed."""
    cell = harness.load_cell(name)
    cell["cfg"].update(REDUCED[cell["cfg"]["family"]])
    argv = list(cell["argv"])
    argv[argv.index("--seq") + 1] = str(SEQ)
    cell["argv"] = argv + ["--reduced"]
    cell["limits"] = dict(LIMITS)
    return cell


def v5e_peaks(_kind):
    path = pathlib.Path(harness.BENCH) / "peaks.json"
    return json.load(open(path))["devices"]["TPU v5 lite"]


def run(cell, seed=7, seconds=1.0, trace=False, monkeypatch=None):
    """run_cell on the CPU: the look for a chip is skipped, and the chip's
    peaks stand in for the CPU's."""
    if monkeypatch is not None:
        monkeypatch.setattr(harness, "peaks", v5e_peaks)
    return harness.run_cell(cell, seed, seconds, trace, jax.devices()[:1],
                            born=0.0, log=lambda m: None)
