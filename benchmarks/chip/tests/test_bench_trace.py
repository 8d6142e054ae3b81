"""``trace.py`` on a small trace in the profiler's own format."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from benchmarks.chip import trace

DATA = Path(__file__).parent / "data"


def _xspace(planes):
    """Text-proto XSpace: planes of [(line, [(name, start_ns, end_ns)])]."""
    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names = {}
        body = ""
        for lid, (line, evs) in enumerate(lines.items(), 1):
            body += f'  lines {{ id: {lid} name: "{line}" timestamp_ns: 0\n'
            for n, s, e in evs:
                i = names.setdefault(n, len(names) + 1)
                body += (f"    events {{ metadata_id: {i} offset_ps: "
                         f"{s * 1000} duration_ps: {(e - s) * 1000} }}\n")
            body += "  }\n"
        meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }}\n' for n, i in names.items())
        out.append(f'planes {{\n  id: {pid}\n  name: "{plane}"\n'
                   f'{body}{meta}}}\n')
    return ProfileData.from_text_proto("".join(out))


def test_busy_idle_kernels_and_gaps():
    t = trace.from_profile(_xspace({
        "/device:TPU:0": {
            "XLA Modules": [("jit_step", 0, 1000)],
            "XLA Ops": [("fusion.1", 50, 300), ("fusion.2", 250, 400),
                        ("flash_attention_fwd", 500, 700),
                        ("flash_attention_fwd", 900, 1100)]},
        "/host:CPU": {"python": [("window", 100, 1000),
                                 ("dispatch", 100, 120),
                                 ("prep", 400, 480), ("wait", 480, 990)]}}))
    assert t.window == (100, 1000) and t.window_ns() == 900
    # ops clipped to the window and merged where they overlap
    assert t.busy_ns("/device:TPU:0") == (400 - 100) + 200 + 100
    assert t.busy_s() == pytest.approx(600e-9)
    assert t.kernel("flash_attention_fwd") == (2, pytest.approx(300e-9))
    assert t.top_ops(2) == [["flash_attention_fwd", pytest.approx(3e-7)],
                            ["fusion.1", pytest.approx(2e-7)]]
    # gaps 400-500 (host in prep at 450) and 700-900 (wait at 800)
    assert t.idle_gaps() == [["wait", pytest.approx(2e-7)],
                             ["prep", pytest.approx(1e-7)]]


def test_refuses_a_trace_without_its_window():
    with pytest.raises(ValueError, match="window"):
        trace.from_profile(_xspace({
            "/device:TPU:0": {"XLA Ops": [("fusion.1", 0, 10)]},
            "/host:CPU": {"python": [("prep", 0, 5)]}}))


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_recorded_chip_step():
    """One step of ``pixtral-12b-mllm.mixed50.1chip`` traced on a TPU v5e,
    trimmed to its 4000 longest device ops and the benchmark's spans, with
    op names cut to their HLO instruction."""
    t = trace.from_profile(ProfileData.from_text_proto(
        (DATA / "trace_mllm_step.pbtxt").read_text()))
    assert list(t.ops) == ["/device:TPU:0"]
    # 4 microbatches x (1 LM + 4 ViT layers), each forward run twice (remat)
    n, seconds = t.kernel("flash_attention_fwd")
    assert n == 40 and 0 < seconds < t.window_ns() / 1e9
    assert 0.9 < t.busy_s() * 1e9 / t.window_ns() < 1.0
    top = t.top_ops()
    assert len(top) == 10 and not any(n.startswith("%while") for n, _ in top)
    assert {n for n, _ in t.idle_gaps()} <= {"schedule", "prep", "dispatch",
                                             "wait", "other"}
