"""Fixtures of the benchmark's CPU tests: a cell's configuration cut to a
tiny size, and a run of it.  (Not a ``conftest.py``: the repository's own
tests import helpers from a module of that name.)"""
import pytest

from benchmarks.chip import harness

CELLS = {"mllm": "pixtral-12b-mllm.mixed50.1chip",
         "distill": "granite-3.0-8b-2b-distill.text.1chip"}
SEED = 2**31 + 12345


def tiny(which):
    if which == "mllm":
        c = harness.config("pixtral-12b-mllm")
        c.update(num_layers=1, d_model=64, num_heads=4, num_kv_heads=2,
                 head_dim=16, d_ff=128, vocab_size=256, vision_dim=32,
                 max_image_tokens=8)
        c["vision_encoder"].update(num_layers=2, d_model=32, num_heads=2,
                                   d_ff=64, patch_dim=16)
        return c, dict(harness.traffic("mixed50"), batch=4, seq_len=64,
                       mbs=2, pool=8)
    c = harness.config("granite-3.0-8b-2b-distill")
    c["teacher"].update(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=2, head_dim=16, d_ff=128,
                        vocab_size=256)
    c["student"].update(num_layers=2, d_model=32, num_heads=4,
                        num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=256)
    return c, dict(harness.traffic("text"), batch=4, seq_len=64, mbs=2,
                   pool=8)


@pytest.fixture
def small(monkeypatch):
    """Point the harness at a tiny copy of a cell's configuration, and give
    the CPU the chip's peaks (no device metric is read here)."""
    def use(which):
        c, t = tiny(which)
        monkeypatch.setattr(harness, "config", lambda name: c)
        monkeypatch.setattr(harness, "traffic", lambda name: t)
        monkeypatch.setattr(harness, "peak",
                            lambda kind: harness.load_json(
                                harness.HERE / "peaks.json")["TPU v5 lite"])
        return CELLS[which], c, t
    return use


@pytest.fixture
def run_cell():
    """One run of a cell at the tiny size, with a half-second window."""
    return lambda name: harness.run(name, SEED, 0.5, False,
                                    log=lambda s: None)


